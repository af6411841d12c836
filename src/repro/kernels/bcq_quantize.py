"""Pallas TPU kernel: fused on-the-fly LO-BCQ encode (activation path).

Per (TILE_M, TILE_K) VMEM tile (TILE_K a multiple of L_A):
  1. per-array |max| reduce → s_A, snap s_A/s_X to the E4M3 grid (VPU ops,
     no gather),
  2. normalize the tile,
  3. for each of the N_c ≤ 16 codebooks (unrolled — the whole codebook table
     is ≤ 256 B and lives in VMEM): per-scalar nearest-entry index via 2^B-1
     threshold compares, block MSE, running argmin over codebooks,
  4. bit-pack indices (2 per byte) and selectors and write out.

Steps 1–3 are ``kernels/common.encode_tile`` — shared verbatim with the
fused linear kernel (bcq_linear.py), so the two paths encode bit-identically
by construction.  This is the TPU-native replacement for a GPU LUT/gather
design: everything is compare+select+FMA on the 8×128 VPU, which Mosaic
lowers natively.  Off-TPU the default is ``interpret`` mode (tests assert
exact equivalence with kernels/ref.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bcq import BCQConfig
from repro.kernels.common import encode_tile, pack_u4, resolve_interpret


def _quantize_kernel(x_ref, cb_ref, sx_ref, idx_ref, sel_ref, ratio_ref, *, cfg: BCQConfig):
    xt = x_ref[...].astype(jnp.float32).T  # K-major (TK, TM)
    idx, sel, ratio, _ = encode_tile(xt, cb_ref, sx_ref[0], cfg)
    idx_ref[...] = pack_u4(idx.T)
    sel_ref[...] = pack_u4(sel.T)
    ratio_ref[...] = ratio.T


@functools.partial(
    jax.jit, static_argnames=("cfg", "tile_m", "tile_k", "interpret")
)
def bcq_quantize_pallas(
    x: jax.Array,
    codebooks: jax.Array,
    s_x: jax.Array,
    cfg: BCQConfig,
    tile_m: int = 128,
    tile_k: int = 512,
    interpret: bool | None = None,
):
    """Encode x (M, K) → (idx_packed, sel_packed, ratio). M % tile_m == 0,
    K % tile_k == 0, tile_k % L_A == 0 (caller pads, see ops.py).
    ``interpret=None`` auto-detects the backend (native on TPU)."""
    m, k = x.shape
    assert m % tile_m == 0 and k % tile_k == 0 and tile_k % cfg.array_len == 0
    grid = (m // tile_m, k // tile_k)
    bpb = cfg.block_len * 2  # K scalars per packed selector byte
    kernel = functools.partial(_quantize_kernel, cfg=cfg)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_m, tile_k), lambda i, j: (i, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((tile_m, tile_k // 2), lambda i, j: (i, j)),
            pl.BlockSpec((tile_m, tile_k // bpb), lambda i, j: (i, j)),
            pl.BlockSpec((tile_m, tile_k // cfg.array_len), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k // 2), jnp.uint8),
            jax.ShapeDtypeStruct((m, k // bpb), jnp.uint8),
            jax.ShapeDtypeStruct((m, k // cfg.array_len), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(x, codebooks.astype(jnp.float32), s_x.reshape(1).astype(jnp.float32))
