"""LO-BCQ: block clustered quantization (paper §2) — pure-JAX reference.

Pipeline (encode, Eqs. 1–8):

  tensor X --(reshape last/reduction axis)--> block arrays of L_A scalars
    s_X  = (2^(B_c-1)-1) / amax|X|                  per-tensor scale
    s_A  = (2^(B_c-1)-1) / amax|A|                  per-array scale
    ŝ_A  = Q_E4M3(s_A / s_X)                        8-bit stored scale
    y    = X · ŝ_A · s_X                            normalized into ±31
  each block b (L_b scalars of y):
    sel(b) = argmin_i ||b - C_i(b)||²               log2(N_c)-bit selector
    idx[l] = argmin_k |b[l] - C_sel[k]|             B-bit index per scalar
  decode:  x̂ = C_sel[idx] / (ŝ_A · s_X)

Codebooks C are (N_c, 2^B) INT-(B_c) integer grids fitted offline by
``fit_lobcq`` (alternating block-clustering / batched Lloyd-Max, §2.2) and
frozen ("universal") afterwards.

This module is the *oracle*: `kernels/` re-implements encode and the
decode-GEMM as Pallas TPU kernels and is tested against this file.
"""
from __future__ import annotations

import dataclasses
import json
from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import formats
from repro.core.lloyd_max import (
    kmeanspp_seeds,
    lloyd_max_batched,
    quantile_init,
)


@dataclasses.dataclass(frozen=True)
class BCQConfig:
    """LO-BCQ format hyper-parameters (Table 1)."""

    block_len: int = 8  # L_b
    array_len: int = 64  # L_A (scalars per block array)
    n_codebooks: int = 8  # N_c
    index_bits: int = 4  # B
    scale_bits: int = 8  # B_s (E4M3)
    codeword_bits: int = 6  # B_c (INT6)

    def __post_init__(self):
        assert self.array_len % self.block_len == 0, "L_A must be a multiple of L_b"

    @property
    def n_entries(self) -> int:
        return 2**self.index_bits

    @property
    def blocks_per_array(self) -> int:
        return self.array_len // self.block_len

    @property
    def codeword_max(self) -> float:
        return float(2 ** (self.codeword_bits - 1) - 1)

    @property
    def selector_bits(self) -> float:
        return float(np.log2(self.n_codebooks))

    def bitwidth(self, tensor_size: int | None = None) -> float:
        """Effective bits/scalar (Eq. 9)."""
        bw = (
            self.index_bits
            + self.selector_bits / self.block_len
            + self.scale_bits / self.array_len
        )
        if tensor_size:
            bw += self.n_codebooks * self.n_entries * self.codeword_bits / tensor_size
        return bw

    def tag(self) -> str:
        return f"g{self.array_len}_Lb{self.block_len}_Nc{self.n_codebooks}"


@dataclasses.dataclass
class CodebookSet:
    """N_c frozen codebooks (sorted, INT-(B_c) integer values)."""

    levels: np.ndarray  # (N_c, 2^B) float32 holding integers in ±(2^(B_c-1)-1)
    cfg: BCQConfig
    history: list | None = None  # calibration MSE trajectory

    def as_jnp(self) -> jax.Array:
        return jnp.asarray(self.levels, dtype=jnp.float32)

    def nbytes(self) -> float:
        return self.levels.size * self.cfg.codeword_bits / 8.0

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(
                {
                    "levels": self.levels.tolist(),
                    "cfg": dataclasses.asdict(self.cfg),
                    "history": list(map(float, self.history or [])),
                },
                f,
            )

    @staticmethod
    def load(path: str) -> "CodebookSet":
        with open(path) as f:
            d = json.load(f)
        return CodebookSet(
            levels=np.asarray(d["levels"], dtype=np.float32),
            cfg=BCQConfig(**d["cfg"]),
            history=d.get("history"),
        )


class Encoded(NamedTuple):
    """Bit-true packed LO-BCQ tensor (storage = Eq. 9 exactly)."""

    packed_idx: jax.Array  # uint8 (..., Kp//2)   two 4-bit indices / byte
    packed_sel: jax.Array  # uint8 (..., ceil(n_blocks/2)) two selectors / byte
    scale_code: jax.Array  # uint8 (..., n_arrays) E4M3 bit patterns of ŝ_A
    s_x: jax.Array  # f32 scalar per-tensor scale


# ------------------------------------------------------------------ helpers
def pad_to_multiple(x: jax.Array, mult: int, axis: int = -1):
    k = x.shape[axis]
    pad = (-k) % mult
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    return x, pad


def pack_nibbles(x: jax.Array) -> jax.Array:
    """Pack 4-bit values (last axis, even length) two per uint8.

    Even positions go to the low nibble, odd to the high one.  The pairs
    come from a reshape to (..., n, 2), which lowers to slices: the same
    bytes as strided ``x[..., 0::2]`` / ``x[..., 1::2]``, which lower to
    gathers.
    """
    x = x.astype(jnp.uint8)
    pairs = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    return (pairs[..., 1] << 4) | pairs[..., 0]


def unpack_nibbles(p: jax.Array) -> jax.Array:
    lo = p & 0xF
    hi = p >> 4
    return jnp.stack([lo, hi], axis=-1).reshape(*p.shape[:-1], p.shape[-1] * 2)


def nearest_level_idx(y: jax.Array, levels_sorted: jax.Array) -> jax.Array:
    """Index (int32) of the nearest entry of a sorted level set, per scalar.

    ``levels_sorted`` holds the levels along its last axis; its leading
    axes, if any, broadcast against ``y`` (one level set per block).
    Counts the midpoints ``y`` reaches, ``Σ_t (y >= thr_t)`` over the
    2^B−1 thresholds, unrolled into elementwise compares as in the Pallas
    kernels' ``encode_tile``.  For finite ``y`` this is
    ``searchsorted(thr, y, side="right")`` by definition (exact midpoints
    round to the upper level), without its per-value binary-search loop
    of gathers.
    """
    idx = jnp.zeros(y.shape, jnp.int32)
    for t in range(levels_sorted.shape[-1] - 1):
        thr = 0.5 * (levels_sorted[..., t + 1] + levels_sorted[..., t])
        idx += (y >= thr).astype(jnp.int32)
    return idx


def level_values(idx: jax.Array, levels: jax.Array) -> jax.Array:
    """``levels[idx]`` for a 1-D level set, as a chain of selects.

    One compare and select per level instead of a per-value gather; each
    output is exactly one entry of ``levels``.
    """
    q = jnp.broadcast_to(levels[0], idx.shape)
    for t in range(1, levels.shape[-1]):
        q = jnp.where(idx == t, levels[t], q)
    return q


def block_sse(d: jax.Array) -> jax.Array:
    """Σ d² over the last (block) axis, added left to right.

    The order is fixed in the program, not left to XLA's reduce emitter,
    which sums differently in different fusions: two codebooks that leave
    a block the same residual get the same error bit for bit, so an exact
    tie goes to the first one wherever the encode is inlined.
    """
    sq = d * d
    err = sq[..., 0]
    for j in range(1, sq.shape[-1]):
        err = err + sq[..., j]
    return err


# -------------------------------------------------------------- encode path
def tensor_scale(x: jax.Array, cfg: BCQConfig) -> jax.Array:
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    return jnp.where(amax > 0, cfg.codeword_max / amax, 1.0)


def _array_scales(arrays: jax.Array, cfg: BCQConfig, s_x: jax.Array):
    """ŝ_A (E4M3-snapped) and the total scale ŝ_A·s_X per array (Eqs. 7/8)."""
    amax = jnp.max(jnp.abs(arrays), axis=-1)
    s_a = jnp.where(amax > 0, cfg.codeword_max / amax, s_x)
    ratio = formats.E4M3.quantize(s_a / s_x)
    ratio = jnp.maximum(ratio, formats.E4M3.min_subnormal)
    return ratio, ratio * s_x


def _select_and_index(blocks: jax.Array, codebooks: jax.Array):
    """Per-block codebook selector + per-scalar nearest-entry index (Eqs. 2/4).

    blocks: (..., L_b) normalized values; codebooks: (N_c, 2^B) sorted.
    Returns (sel int32 (...,), idx int32 (..., L_b)).

    Each codebook's block error comes from :func:`nearest_level_idx`'s
    threshold compares, :func:`level_values`' selects and
    :func:`block_sse`'s fixed-order sum; one ``argmin`` over the N_c
    errors picks the codebook (the first one wins ties).  ``idx`` is then
    taken from the chosen codebook's own levels, so it agrees with ``sel``
    however XLA fuses or duplicates the work.  The lowered encode holds
    no gather and no loop.
    """
    nc = codebooks.shape[0]
    errs = []
    for c in range(nc):
        levels = codebooks[c]
        q = level_values(nearest_level_idx(blocks, levels), levels)
        errs.append(block_sse(blocks - q))
    sel = jnp.argmin(jnp.stack(errs), axis=0).astype(jnp.int32)
    chosen = jnp.broadcast_to(codebooks[0], sel.shape + codebooks.shape[1:])
    for c in range(1, nc):
        chosen = jnp.where((sel == c)[..., None], codebooks[c], chosen)
    return sel, nearest_level_idx(blocks, chosen[..., None, :])


@partial(jax.jit, static_argnames=("cfg",))
def encode(x: jax.Array, codebooks: jax.Array, cfg: BCQConfig, s_x=None) -> Encoded:
    """Encode ``x`` (blocks along the last axis) to packed LO-BCQ."""
    xf = x.astype(jnp.float32)
    if s_x is None:
        s_x = tensor_scale(xf, cfg)
    xp, _ = pad_to_multiple(xf, cfg.array_len)
    lead = xp.shape[:-1]
    na = xp.shape[-1] // cfg.array_len
    arrays = xp.reshape(*lead, na, cfg.array_len)
    ratio, scale = _array_scales(arrays, cfg, s_x)
    y = arrays * scale[..., None]
    blocks = y.reshape(*lead, na, cfg.blocks_per_array, cfg.block_len)
    sel, idx = _select_and_index(blocks, codebooks)
    idx_flat = idx.reshape(*lead, na * cfg.array_len)
    sel_flat = sel.reshape(*lead, na * cfg.blocks_per_array)
    sel_flat, _ = pad_to_multiple(sel_flat, 2)
    return Encoded(
        packed_idx=pack_nibbles(idx_flat),
        packed_sel=pack_nibbles(sel_flat),
        scale_code=formats.e4m3_to_bits(ratio),
        s_x=s_x.astype(jnp.float32),
    )


@partial(jax.jit, static_argnames=("cfg", "out_len"))
def decode(enc: Encoded, codebooks: jax.Array, cfg: BCQConfig, out_len: int) -> jax.Array:
    """Inverse of :func:`encode`; ``out_len`` is the unpadded last-dim size."""
    idx = unpack_nibbles(enc.packed_idx).astype(jnp.int32)
    lead = idx.shape[:-1]
    kp = idx.shape[-1]
    na = kp // cfg.array_len
    nblocks = na * cfg.blocks_per_array
    sel = unpack_nibbles(enc.packed_sel).astype(jnp.int32)[..., :nblocks]
    ratio = formats.bits_to_e4m3(enc.scale_code)
    scale = ratio * enc.s_x  # (..., na)
    flat_cb = codebooks.reshape(-1)
    sel_per_scalar = jnp.repeat(sel, cfg.block_len, axis=-1)
    vals = flat_cb[sel_per_scalar * cfg.n_entries + idx]
    vals = vals.reshape(*lead, na, cfg.array_len) / scale[..., None]
    return vals.reshape(*lead, kp)[..., :out_len]


@partial(jax.jit, static_argnames=("cfg",))
def fake_quant(x: jax.Array, codebooks: jax.Array, cfg: BCQConfig, s_x=None) -> jax.Array:
    """Quantize-dequantize in one shot (bit-identical to decode∘encode)."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    if s_x is None:
        s_x = tensor_scale(xf, cfg)
    xp, pad = pad_to_multiple(xf, cfg.array_len)
    lead = xp.shape[:-1]
    na = xp.shape[-1] // cfg.array_len
    arrays = xp.reshape(*lead, na, cfg.array_len)
    ratio, scale = _array_scales(arrays, cfg, s_x)
    y = arrays * scale[..., None]
    blocks = y.reshape(*lead, na, cfg.blocks_per_array, cfg.block_len)
    sel, idx = _select_and_index(blocks, codebooks)
    flat_cb = codebooks.reshape(-1)
    vals = flat_cb[sel[..., None] * cfg.n_entries + idx]
    out = (vals.reshape(*lead, na, cfg.array_len) / scale[..., None]).reshape(
        *lead, na * cfg.array_len
    )
    return out[..., : x.shape[-1]].astype(dt)


@partial(jax.jit, static_argnames=("cfg",))
def encode_stats(x: jax.Array, codebooks: jax.Array, cfg: BCQConfig, s_x=None):
    """Online quantization-error stats of encoding ``x``: the NMSE of the
    quantize-dequantize round trip and the per-codebook selector
    occupancy (how often each cluster wins the per-block argmin of Eq. 4).

    This is the telemetry probe behind ``Runtime.quant_probe``
    (serving.telemetry.QuantProbeSink): it re-runs the encode path on the
    raw activation, so it is opt-in — the serving fast path never pays
    for it.  Returns (nmse f32 scalar, occupancy (N_c,) int32).  Padding
    to a whole array is excluded from the NMSE but its (all-zero) blocks
    do count toward occupancy, same as in the stored encoding."""
    xf = x.astype(jnp.float32)
    if s_x is None:
        s_x = tensor_scale(xf, cfg)
    xp, _ = pad_to_multiple(xf, cfg.array_len)
    lead = xp.shape[:-1]
    na = xp.shape[-1] // cfg.array_len
    arrays = xp.reshape(*lead, na, cfg.array_len)
    _, scale = _array_scales(arrays, cfg, s_x)
    y = arrays * scale[..., None]
    blocks = y.reshape(*lead, na, cfg.blocks_per_array, cfg.block_len)
    sel, idx = _select_and_index(blocks, codebooks)
    flat_cb = codebooks.reshape(-1)
    vals = flat_cb[sel[..., None] * cfg.n_entries + idx]
    xq = (vals.reshape(*lead, na, cfg.array_len) / scale[..., None]).reshape(
        *lead, na * cfg.array_len
    )[..., : x.shape[-1]]
    occupancy = jnp.zeros((cfg.n_codebooks,), jnp.int32).at[
        sel.reshape(-1)
    ].add(1)
    return quantization_nmse(xf, xq), occupancy


def quantization_nmse(x: jax.Array, xq: jax.Array) -> jax.Array:
    x = x.astype(jnp.float32)
    d = x - xq.astype(jnp.float32)
    return jnp.sum(d * d) / jnp.maximum(jnp.sum(x * x), 1e-12)


# ----------------------------------------------------------- LO-BCQ fitting
def _normalized_blocks(t: jax.Array, cfg: BCQConfig) -> jax.Array:
    """Reshape a tensor into per-array-normalized blocks (calibration prep)."""
    xf = jnp.ravel(t).astype(jnp.float32)
    n = (xf.shape[0] // cfg.array_len) * cfg.array_len
    arrays = xf[:n].reshape(-1, cfg.array_len)
    s_x = tensor_scale(xf, cfg)
    _, scale = _array_scales(arrays, cfg, s_x)
    y = arrays * scale[:, None]
    return y.reshape(-1, cfg.block_len)


@partial(jax.jit, static_argnames=())
def _assign_mse(blocks: jax.Array, codebooks: jax.Array):
    """Cluster assignment (Eq. 4) + resulting per-block MSE."""

    def one_cb(levels):
        levels = jnp.sort(levels)
        idx = nearest_level_idx(blocks, levels)
        return block_sse(blocks - level_values(idx, levels))

    errs = jax.vmap(one_cb)(codebooks)  # (N_c, N_b)
    assign = jnp.argmin(errs, axis=0)
    return assign.astype(jnp.int32), jnp.min(errs, axis=0)


def fit_lobcq(
    tensors: Sequence[jax.Array] | jax.Array,
    cfg: BCQConfig,
    key: jax.Array | None = None,
    iters: int = 30,
    lm_iters: int = 25,
    max_blocks: int = 65536,
    tol: float = 1e-7,
    quantize_codewords: bool = True,
) -> CodebookSet:
    """Calibrate N_c codebooks with the LO-BCQ alternating algorithm (§2.2).

    ``tensors`` — calibration operands (weights and/or captured activations).
    Returns a :class:`CodebookSet` whose ``history`` is the (non-increasing)
    per-iteration quantization MSE — the paper's §A.2 invariant.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    if isinstance(tensors, (jnp.ndarray, np.ndarray)):
        tensors = [tensors]
    blocks = jnp.concatenate([_normalized_blocks(t, cfg) for t in tensors], axis=0)
    if blocks.shape[0] > max_blocks:
        key, kp = jax.random.split(key)
        sel = jax.random.choice(kp, blocks.shape[0], (max_blocks,), replace=False)
        blocks = blocks[sel]
    nb = blocks.shape[0]
    scalars = blocks.reshape(-1)

    # --- init: k-means++ seeds over blocks, per-cluster quantile levels ----
    key, ks = jax.random.split(key)
    seeds = kmeanspp_seeds(blocks, cfg.n_codebooks, ks)
    d = jnp.sum((blocks[:, None, :] - seeds[None, :, :]) ** 2, axis=-1)
    assign = jnp.argmin(d, axis=1).astype(jnp.int32)
    glob = quantile_init(scalars, cfg.n_entries)
    levels = jnp.tile(glob[None, :], (cfg.n_codebooks, 1))
    levels = lloyd_max_batched(
        scalars, jnp.repeat(assign, cfg.block_len), levels, iters=lm_iters
    )

    history = []
    prev = np.inf
    for _ in range(iters):
        # step 1: re-cluster blocks against current codebooks (Eq. 4/5)
        assign, errs = _assign_mse(blocks, levels)
        # step 2: Lloyd-Max refit per cluster, warm-started (Eq. 6)
        levels = lloyd_max_batched(
            scalars, jnp.repeat(assign, cfg.block_len), levels, iters=lm_iters
        )
        _, errs2 = _assign_mse(blocks, levels)
        j = float(jnp.mean(errs2) / cfg.block_len)
        history.append(j)
        if prev - j < tol * max(prev, 1e-12):
            break
        prev = j

    if quantize_codewords:
        levels = jnp.clip(jnp.round(levels), -cfg.codeword_max, cfg.codeword_max)
    levels = jnp.sort(levels, axis=-1)
    return CodebookSet(levels=np.asarray(levels), cfg=cfg, history=history)


def naive_init_fit(
    tensors, cfg: BCQConfig, key: jax.Array | None = None, **kw
) -> CodebookSet:
    """Ablation baseline: random codebook init instead of k-means++ (Fig. 4)."""
    if key is None:
        key = jax.random.PRNGKey(0)
    if isinstance(tensors, (jnp.ndarray, np.ndarray)):
        tensors = [tensors]
    blocks = jnp.concatenate([_normalized_blocks(t, cfg) for t in tensors], axis=0)
    scalars = blocks.reshape(-1)
    levels = jax.random.uniform(
        key, (cfg.n_codebooks, cfg.n_entries), minval=-cfg.codeword_max, maxval=cfg.codeword_max
    )
    history = []
    for _ in range(kw.get("iters", 30)):
        assign, _ = _assign_mse(blocks, levels)
        levels = lloyd_max_batched(
            scalars, jnp.repeat(assign, cfg.block_len), levels, iters=kw.get("lm_iters", 25)
        )
        _, errs2 = _assign_mse(blocks, levels)
        history.append(float(jnp.mean(errs2) / cfg.block_len))
    levels = jnp.clip(jnp.round(levels), -cfg.codeword_max, cfg.codeword_max)
    return CodebookSet(np.asarray(jnp.sort(levels, -1)), cfg, history)
