"""Pallas TPU kernel: fused W4A4 linear — quantize→decode→GEMM in ONE launch.

DESIGN
======

``out = Â · Ŵᵀ`` where Â = LO-BCQ(x) is encoded **inside the kernel** and Ŵ
arrives pre-packed (4-bit indices + selector/scale metadata, the
``ops.PackedOperand`` layout — the index bytes, the bulk of the weight
stream, are not re-laid before the launch).  The
two-launch path (`bcq_quantize_pallas` + `bcq_matmul_pallas`) round-trips
packed activations through HBM and re-decodes every weight tile O(M/TM)
times with an O(N_c·2^B) masked-sum mux; this kernel removes both costs:

1. **Grid (N/TN, M/TM[, K/KB]): K in blocks of KB, whole K where it fits.**
   KB is the largest multiple of ``tile_k`` dividing K whose step fits
   the VMEM budget below (``fit_k_block``): all of K at every gpt3_126m
   width, 4096 of StarCoder2-3B's d_ff = 12288.  Inside a step K is
   walked by an in-kernel ``fori_loop`` of ``tile_k`` chunks, and the f32
   out block (resident across the K axis) carries the accumulator from
   one K block to the next, so the accumulation order over K is the
   two-launch path's whatever KB is.  The packed selector and scale rows
   (KB/(2·L_b) bytes, KB/L_A floats: not 128-aligned) reach a K-blocked
   launch re-laid as (K/KB, N, ·), so that each K block's metadata is a
   whole minor dim; a whole-K launch (grid (N/TN, M/TM)) takes them as
   they are, every block's lane dim then whole.

2. **In-VMEM activation encode.**  The wrapper hands the kernel xᵀ, so
   the raw activation arrives as a K-major (KB, TM) VMEM slab whose block
   index depends only on the M tile and the K block: with KB = K Pallas
   fetches it from HBM once per M tile (for the serving-decode hot path
   — a single M tile — exactly once per linear, regardless of N/TN), and
   each loop step takes a (TK, TM) chunk at a sublane offset.  The chunk
   is encoded with `common.encode_tile` — the *same* threshold-compare
   routine the standalone quantize kernel runs, so the fused path is
   bit-exact with the two-launch path by construction.  The encode
   already selects each scalar's codeword, so the activation needs no
   decode at all.  Packed activations never touch HBM: the only
   activation HBM stream is the raw read (plus the wrapper's transpose).

3. **Lane-gather weight decode into a VMEM scratch.**  Per 128-lane
   output chunk: the index byte, the block selector byte and the array
   scale are spread over their scalars and the combined codeword
   ``sel·2^B + idx`` is looked up (``common.decode_rows``, shared with
   the bcq4 page dequant) — lane gathers (one ``tpu.dynamic_gather`` per
   vreg), exact, with the ≤128-entry flattened codebook held in one vreg
   row.  With whole K the row tile (TN, K) decodes at the first M step
   (i == 0) into the scratch, reused for every M revisit (O(1) decodes
   per weight tile).  A K block decodes at every step, M/TM times, TK at
   a time, each 128-lane chunk loaded straight from the packed VMEM block
   — as the activation tile is encoded at every step (N/TN times)
   whatever KB is.

VMEM per grid step (``vmem_bytes``; TM = TN = 128, paper cfg):

  raw activation slab      2·TM·KB·4              = KB·1 KiB
  packed index block       2·TN·KB/2              = KB·128 B
  selector / scale blocks  2·TN·(KB/16 + 4·KB/64), lane-padded to 128
  f32 out block            2·TM·TN·4              = 128 KiB
  decoded-weight scratch   TN·KB·4                = KB·512 B
  decode temporaries       whole K: TN·K·6.25 (int32 bytes, f32 tile);
                           K blocks: one f32 TK tile, TN·TK·4
  encode temporaries       10·TM·TK·4             (2.5 MiB at TK = 512)

  K      TK   KB      stated     compiler  where
  768    384  768     3.96 MiB   2.2 MiB   gpt3 d_model
  3072   512  3072    10.03 MiB  6.6 MiB   gpt3 d_ff, StarCoder2 d_model
  12288  512  4096    9.56 MiB   8.8 MiB   StarCoder2 d_ff
  12288  512  12288   31.94 MiB  > 18.1    (whole K: refused)

"Compiler" is the least scoped VMEM limit at which the step compiles for
a described v5e (M = 512 or 128, the output cast to bf16 as the model
casts it).  Every step's statement stays within ``VMEM_BUDGET`` = 12
MiB, under the chip's 16 MiB default scoped limit, with room for what
XLA places beside the kernel: at M ≥ 256 XLA may keep the kernel's f32
output in VMEM (``S(1)``), and the whole-K step at K = 12288 then failed
to compile, on the chip as for a described v5e (a scoped allocation of
18.12 MiB against 16 MiB).  A K-blocked launch declares the budget to
the compiler as its limit; a whole-K launch is the one gpt3_126m's
widths always had, compiler default included.

HBM traffic per linear: the packed 4.5-bit weight stream + the raw
activation read + the f32 output — no packed-activation round-trip.  For
the serving decode hot path (M one tile) with KB = K the activation slab's
block index never changes across the whole grid, so the raw read happens
exactly once; with KB < K, and in multi-M-tile prefill, the slab re-streams
per N tile like any GEMM operand (12288 → 3072 at M = 128: 24 × 6 MiB,
against 21.7 MB of packed weights).

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


VMEM_BUDGET = 12 * 2**20  # bytes one grid step may hold (chip limit: 16 MiB)
ENCODE_TEMPS = 10  # (TM, TK) f32 temporaries of encode_tile, at most


def _lanes(n: int) -> int:
    return -(-n // LANES) * LANES


def vmem_bytes(tile_m: int, tile_n: int, k: int, k_block: int, tile_k: int, cfg: BCQConfig) -> int:
    """VMEM one grid step of the fused linear holds (DESIGN's table): the
    double-buffered input and output blocks, the decoded-weight scratch,
    the weight decode's temporaries (the whole row tile at once when
    ``k_block == k``, one TK tile at a time otherwise) and the encode's."""
    blocks = (
        k_block * tile_m * 4  # raw activation slab, f32
        + tile_n * k_block // 2  # packed index bytes
        + tile_n * _lanes(k_block // (2 * cfg.block_len))  # selector bytes
        + tile_n * _lanes(k_block // cfg.array_len) * 4  # dequant scales
        + tile_m * tile_n * 4  # f32 out block
    )
    if k_block == k:  # index and selector bytes widened to int32, decoded f32 tile
        decode = tile_n * (k // 2 + k // (2 * cfg.block_len) + k) * 4
    else:
        decode = tile_n * tile_k * 4
    return 2 * blocks + tile_n * k_block * 4 + decode + ENCODE_TEMPS * tile_m * tile_k * 4


def fit_k_block(k: int, tile_m: int, tile_n: int, tile_k: int, cfg: BCQConfig) -> int:
    """The K extent of one grid step: the largest multiple of ``tile_k``
    dividing ``k`` (a multiple of ``tile_k``) whose step fits
    ``VMEM_BUDGET``; whole K wherever it fits.  Natively each K block's
    packed index bytes must fill whole 128-lane rows (KB % 256 == 0)
    unless KB = K."""
    n = k // tile_k
    for d in sorted((d for d in range(1, n + 1) if n % d == 0), reverse=True):
        kb = d * tile_k
        if (kb == k or kb % (2 * LANES) == 0) and vmem_bytes(tile_m, tile_n, k, kb, tile_k, cfg) <= VMEM_BUDGET:
            return kb
    return tile_k


def _fused_kernel(
    xt_ref, w_idx_ref, w_sel_ref, w_inv_ref, cb_ref, cbr_ref, sx_ref,
    out_ref, w_cache, *, cfg: BCQConfig, tile_k: int, n_k: int, n_kb: int,
):
    i = pl.program_id(1)  # M tile (grid = (N/TN, M/TM), then K/KB if split)
    la = cfg.array_len

    if n_kb == 1:
        # --- whole K: decode the weight row tile once per N tile, cached
        # across M revisits
        @pl.when(i == 0)
        def _decode_weight():
            w = decode_rows(
                w_idx_ref[...].astype(jnp.int32), w_sel_ref[...].astype(jnp.int32),
                w_inv_ref[...], cbr_ref[...], cfg, n_k * tile_k,
            )
            for s in range(n_k):
                w_cache[s] = w[:, s * tile_k : (s + 1) * tile_k]

        def acc0():
            return jnp.zeros(out_ref.shape, jnp.float32)
    else:
        # --- one K block: decoded TK at a time, 128 lanes read from the
        # refs at a time; the out block carries the accumulator over K
        cb_row = cbr_ref[...]
        for s in range(n_k):
            w_cache[s] = decode_rows(
                w_idx_ref, w_sel_ref, w_inv_ref, cb_row, cfg, tile_k, start=s * tile_k
            )

        @pl.when(pl.program_id(2) == 0)
        def _zero():
            out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)

        def acc0():
            return out_ref[...]

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

    out_ref[...] = jax.lax.fori_loop(0, n_k, k_step, acc0())


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
    The K extent of a grid step is ``fit_k_block``'s.  ``interpret=None``
    auto-detects the backend."""
    m, k = x.shape
    n = w_idx.shape[0]
    assert m % tile_m == 0 and n % tile_n == 0 and k % tile_k == 0
    assert tile_k % cfg.array_len == 0 and tile_k % (2 * cfg.block_len) == 0
    kb = fit_k_block(k, tile_m, tile_n, tile_k, cfg)
    n_kb = k // kb
    if n_kb == 1:  # whole K: the launch every gpt3_126m width has had
        grid = (n // tile_n, m // tile_m)
        in_specs = [
            pl.BlockSpec((k, tile_m), lambda j, i: (0, i)),
            pl.BlockSpec((tile_n, w_idx.shape[1]), lambda j, i: (j, 0)),
            pl.BlockSpec((tile_n, w_sel.shape[1]), lambda j, i: (j, 0)),
            pl.BlockSpec((tile_n, w_inv.shape[1]), lambda j, i: (j, 0)),
        ]
        out_spec = pl.BlockSpec((tile_m, tile_n), lambda j, i: (i, j))
        params = None
    else:  # K blocks, each block's metadata re-laid as a whole minor dim
        w_sel, w_inv = (a.reshape(n, n_kb, -1).swapaxes(0, 1) for a in (w_sel, w_inv))
        grid = (n // tile_n, m // tile_m, n_kb)
        in_specs = [
            pl.BlockSpec((kb, tile_m), lambda j, i, s: (s, i)),
            pl.BlockSpec((tile_n, kb // 2), lambda j, i, s: (j, s)),
            pl.BlockSpec((None, tile_n, w_sel.shape[2]), lambda j, i, s: (s, j, 0)),
            pl.BlockSpec((None, tile_n, w_inv.shape[2]), lambda j, i, s: (s, j, 0)),
        ]
        out_spec = pl.BlockSpec((tile_m, tile_n), lambda j, i, s: (i, j))
        params = pltpu.CompilerParams(vmem_limit_bytes=VMEM_BUDGET)
    kernel = functools.partial(_fused_kernel, cfg=cfg, tile_k=tile_k, n_k=kb // tile_k, n_kb=n_kb)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs + [smem, pl.BlockSpec(memory_space=pltpu.VMEM), smem],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((kb // tile_k, tile_n, tile_k), jnp.float32)],
        compiler_params=params,
        interpret=resolve_interpret(interpret),
        name="bcq_linear",
    )(
        x.T, w_idx, w_sel, w_inv, codebooks.astype(jnp.float32),
        flat_codebook(codebooks), s_x.reshape(1).astype(jnp.float32),
    )
