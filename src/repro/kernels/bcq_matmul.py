"""Pallas TPU kernel: W4A4 LO-BCQ GEMM (decode-in-VMEM + MXU dot).

out[m, n] = Σ_k Â[m, k] · Ŵ[n, k]  where Â/Ŵ are LO-BCQ-encoded operands.

Grid (M/TM, N/TN, K/TK), K innermost for revolving accumulation into the
(TM, TN) f32 output block.  Per K step:

  1. both packed-nibble tiles are unpacked with shift/mask ops,
  2. codewords are decoded by a 2-stage masked sum — first the 2^B entry
     values under each codebook, then the selector mux over N_c books —
     all compare+FMA VPU ops (the ≤256 B codebook table is resident in
     VMEM; no gather, see DESIGN.md §3),
  3. per-array dequant scales (1/(ŝ_A·s_X), precomputed f32) are applied,
  4. an (TM, TK)·(TN, TK)ᵀ dot_general accumulates in f32 on the MXU.

HBM traffic per operand tile is the 4-bit packed stream + 0.5-bit metadata —
the paper's compression is what the memory roofline sees.  For the
single-launch variant that also encodes the activations in VMEM (and
replaces the masked-sum mux with a lane-gather decode) see bcq_linear.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bcq import BCQConfig
from repro.kernels.common import resolve_interpret, unpack_u4


def _decode_tile(idx_p, sel_p, inv_s, cb, cfg: BCQConfig):
    """(T, TK//2) packed idx + (T, TK/Lb/2) packed sel + (T, TK/L_A) inv scales
    → dequantized f32 (T, TK)."""
    idx = unpack_u4(idx_p)  # (T, TK)
    sel = unpack_u4(sel_p)  # (T, TK/Lb)
    t, tk = idx.shape
    lb, la, nc, ne = cfg.block_len, cfg.array_len, cfg.n_codebooks, cfg.n_entries
    idx_b = idx.reshape(t, tk // lb, lb)
    vals = jnp.zeros((t, tk // lb, lb), jnp.float32)
    for i in range(nc):  # selector mux over codebooks
        q_i = jnp.zeros((t, tk // lb, lb), jnp.float32)
        for e in range(ne):  # masked-sum decode of codebook i
            q_i += jnp.where(idx_b == e, cb[i, e], 0.0)
        vals += jnp.where((sel == i)[..., None], q_i, 0.0)
    vals = vals.reshape(t, tk)
    inv = jnp.repeat(inv_s, la, axis=-1)  # (T, TK) broadcast per array
    return vals * inv


def _matmul_kernel(
    a_idx, a_sel, a_inv, w_idx, w_sel, w_inv, cba_ref, cbw_ref, out_ref, *, cfg: BCQConfig
):
    # out block is revisited across the (innermost) K grid dim — the
    # standard revolving-accumulator pattern, no scratch needed (f32 out).
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    cba = cba_ref[...]
    cbw = cbw_ref[...]
    a = _decode_tile(a_idx[...], a_sel[...], a_inv[...], cba, cfg)  # (TM, TK)
    w = _decode_tile(w_idx[...], w_sel[...], w_inv[...], cbw, cfg)  # (TN, TK)
    out_ref[...] += jax.lax.dot_general(
        a, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "tile_m", "tile_n", "tile_k", "interpret"),
)
def bcq_matmul_pallas(
    a_idx: jax.Array,
    a_sel: jax.Array,
    a_inv: jax.Array,
    w_idx: jax.Array,
    w_sel: jax.Array,
    w_inv: jax.Array,
    codebooks_a: jax.Array,
    codebooks_w: jax.Array,
    cfg: BCQConfig,
    tile_m: int = 128,
    tile_n: int = 128,
    tile_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """W4A4 GEMM on packed operands. Shapes (packed along K):
    a_idx (M, K/2), a_sel (M, K/2Lb), a_inv (M, K/L_A); w_* likewise with N
    rows.  Returns f32 (M, N).  Caller pads to tile multiples (ops.py).
    ``interpret=None`` auto-detects the backend (native on TPU)."""
    m = a_idx.shape[0]
    n = w_idx.shape[0]
    k = a_idx.shape[1] * 2
    assert m % tile_m == 0 and n % tile_n == 0 and k % tile_k == 0
    assert tile_k % cfg.array_len == 0
    spb = cfg.block_len * 2
    grid = (m // tile_m, n // tile_n, k // tile_k)
    kernel = functools.partial(_matmul_kernel, cfg=cfg)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_m, tile_k // 2), lambda i, j, s: (i, s)),
            pl.BlockSpec((tile_m, tile_k // spb), lambda i, j, s: (i, s)),
            pl.BlockSpec((tile_m, tile_k // cfg.array_len), lambda i, j, s: (i, s)),
            pl.BlockSpec((tile_n, tile_k // 2), lambda i, j, s: (j, s)),
            pl.BlockSpec((tile_n, tile_k // spb), lambda i, j, s: (j, s)),
            pl.BlockSpec((tile_n, tile_k // cfg.array_len), lambda i, j, s: (j, s)),
            pl.BlockSpec(codebooks_a.shape, lambda i, j, s: (0, 0)),
            pl.BlockSpec(codebooks_w.shape, lambda i, j, s: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_m, tile_n), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(a_idx, a_sel, a_inv, w_idx, w_sel, w_inv, codebooks_a, codebooks_w)
