"""Pallas TPU kernel: fused W4A4 linear — quantize→decode→GEMM in ONE launch.

DESIGN
======

``out = Â · Ŵᵀ`` where Â = LO-BCQ(x) is encoded **inside the kernel** and Ŵ
arrives pre-packed (4-bit indices + selector/scale metadata, the
``ops.PackedOperand`` layout — no re-layout before the launch).  The
two-launch path (`bcq_quantize_pallas` + `bcq_matmul_pallas`) round-trips
packed activations through HBM and re-decodes every weight tile O(M/TM)
times with an O(N_c·2^B) masked-sum mux; this kernel removes both costs:

1. **Grid (N/TN, M/TM), whole K per step.**  Every block's lane dim is
   either 128-aligned or the whole array (the packed selector row is
   K/(2·L_b) bytes and the scale row K/L_A floats — never 128-aligned per
   K tile, always legal whole).  K is walked by an in-kernel
   ``fori_loop`` of ``tile_k`` chunks with the same f32 accumulation
   order as the two-launch path's K grid.

2. **In-VMEM activation encode.**  The wrapper hands the kernel xᵀ, so
   the raw activation arrives as a K-major full-K (K, TM) VMEM slab whose
   block index depends only on the M tile: Pallas fetches it from HBM
   once per M tile (for the serving-decode hot path — a single M tile —
   exactly once per linear, regardless of N/TN), and each loop step takes
   a (TK, TM) chunk at a sublane offset.  The chunk is encoded with
   `common.encode_tile` — the *same* threshold-compare routine the
   standalone quantize kernel runs, so the fused path is bit-exact with
   the two-launch path by construction.  The encode already selects each
   scalar's codeword, so the activation needs no decode at all.  Packed
   activations never touch HBM: the only activation HBM stream is the raw
   read (plus the wrapper's transpose).

3. **Lane-gather weight decode, once per N tile.**  The weight row tile
   (TN, K) decodes at the first M step (i == 0) into a persistent VMEM
   scratch slab reused for every M revisit, so decode cost is O(1) per
   weight tile instead of O(M/TM).  Per 128-lane output chunk: the index
   byte, the block selector byte and the array scale are spread over
   their scalars and the combined codeword ``sel·2^B + idx`` is looked
   up (``common.decode_rows``, shared with the bcq4 page dequant) — lane
   gathers (one ``tpu.dynamic_gather`` per vreg), exact, with the
   ≤128-entry flattened codebook held in one vreg row.

VMEM budget per core (defaults TM=TN=128, paper cfg, K = d_model):

  raw activation slab      2·TM·K·4      = K·1 KiB   (3 MiB @ K=3072)
  packed weight row tile   2·TN·K·0.57   ≈ K·146 B   (440 KiB @ K=3072)
  decoded-weight scratch   TN·K·4        = K·512 B   (1.5 MiB @ K=3072)
  encode temporaries       ~10×TM·TK·4   ≈ 2.5 MiB   (TK = 512)
  f32 out block            2·TM·TN·4     = 128 KiB

≈ 7.5 MiB at K=3072 (d_ff of gpt3_126m) — inside the 16 MiB scoped VMEM
limit; the slabs scale linearly in K, so for very large K lower
``tile_m``/``tile_n``.

HBM traffic per linear: the packed 4.5-bit weight stream + the raw
activation read + the f32 output — no packed-activation round-trip.  For
the serving decode hot path (M one tile) the activation slab's block index
never changes across the whole grid, so the raw read happens exactly once;
multi-M-tile prefill re-streams the slab per N tile like any GEMM operand.

Bit-exactness vs the two-launch path: identical encode (shared
`encode_tile`), identical decoded values (a gather moves bits; the
encode's chosen codeword is ``cb[sel, idx]`` exactly), identical dequant
scales (same ``1/(ŝ_A·s_X)`` f32 arithmetic), and identical accumulation
order over K — tested bitwise in tests/test_fused_linear.py (interpret
mode).  On the chip the final dot runs at the MXU's default precision;
chip_smoke.py states the tolerance against the f32 oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bcq import BCQConfig
from repro.kernels.common import (
    LANES,
    decode_rows,
    encode_tile,
    expand_lanes,
    flat_codebook,
    resolve_interpret,
)


def _fused_kernel(
    xt_ref, w_idx_ref, w_sel_ref, w_inv_ref, cb_ref, cbr_ref, sx_ref,
    out_ref, w_cache, *, cfg: BCQConfig, tile_k: int, n_k: int,
):
    i = pl.program_id(1)  # M tile (grid = (N/TN, M/TM))
    la = cfg.array_len

    # --- weight row tile: decode once per N tile, cached across M revisits
    @pl.when(i == 0)
    def _decode_weight():
        w = decode_rows(
            w_idx_ref[...].astype(jnp.int32), w_sel_ref[...].astype(jnp.int32),
            w_inv_ref[...], cbr_ref[...], cfg, n_k * tile_k,
        )
        for s in range(n_k):
            w_cache[s] = w[:, s * tile_k : (s + 1) * tile_k]

    # --- activation: encode each K chunk in VMEM, accumulate on the MXU --
    s_x = sx_ref[0]

    def k_step(s, acc):
        k0 = pl.multiple_of(s * tile_k, tile_k)
        xt = xt_ref[pl.ds(k0, tile_k), :].astype(jnp.float32)  # K-major (TK, TM)
        _, _, ratio, q = encode_tile(xt, cb_ref, s_x, cfg)
        inv = (1.0 / (ratio * s_x)).T  # (TM, TK/L_A)
        inv = jnp.concatenate(
            [expand_lanes(inv, la, c) for c in range(-(-tile_k // LANES))], axis=1
        )[:, :tile_k]
        # scale AFTER the transpose: the dot's operand is then a product,
        # which XLA cannot fold into the dot's dimension numbers — so the
        # interpreter accumulates exactly like the two-launch path
        a = q.T * inv  # (TM, TK)
        return acc + jax.lax.dot_general(
            a, w_cache[s], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    out_ref[...] = jax.lax.fori_loop(
        0, n_k, k_step, jnp.zeros(out_ref.shape, jnp.float32)
    )


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "tile_m", "tile_n", "tile_k", "interpret"),
)
def bcq_linear_pallas(
    x: jax.Array,
    w_idx: jax.Array,
    w_sel: jax.Array,
    w_inv: jax.Array,
    codebooks: jax.Array,
    s_x: jax.Array,
    cfg: BCQConfig,
    tile_m: int = 128,
    tile_n: int = 128,
    tile_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused W4A4 linear: raw x (M, K) + packed weights (N rows) → f32 (M, N).

    w_idx (N, K/2) u8, w_sel (N, K/2Lb) u8, w_inv (N, K/L_A) f32 = 1/(ŝ_A·s_X)
    with padded-K arrays zeroed (they then contribute exact zeros regardless
    of the activation tile's padding codes).  s_x: per-tensor activation
    scale (global reduction, computed by the caller).  Caller pads to tile
    multiples (ops.py); natively ``tile_k`` must be a multiple of 128.
    ``interpret=None`` auto-detects the backend."""
    m, k = x.shape
    n = w_idx.shape[0]
    assert m % tile_m == 0 and n % tile_n == 0 and k % tile_k == 0
    assert tile_k % cfg.array_len == 0 and tile_k % (2 * cfg.block_len) == 0
    n_k = k // tile_k
    kernel = functools.partial(_fused_kernel, cfg=cfg, tile_k=tile_k, n_k=n_k)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=(n // tile_n, m // tile_m),
        in_specs=[
            pl.BlockSpec((k, tile_m), lambda j, i: (0, i)),
            pl.BlockSpec((tile_n, w_idx.shape[1]), lambda j, i: (j, 0)),
            pl.BlockSpec((tile_n, w_sel.shape[1]), lambda j, i: (j, 0)),
            pl.BlockSpec((tile_n, w_inv.shape[1]), lambda j, i: (j, 0)),
            smem,
            pl.BlockSpec(memory_space=pltpu.VMEM),
            smem,
        ],
        out_specs=pl.BlockSpec((tile_m, tile_n), lambda j, i: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_k, tile_n, tile_k), jnp.float32)],
        interpret=resolve_interpret(interpret),
        name="bcq_linear",
    )(
        x.T, w_idx, w_sel, w_inv, codebooks.astype(jnp.float32),
        flat_codebook(codebooks), s_x.reshape(1).astype(jnp.float32),
    )
