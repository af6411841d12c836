"""Public jit'd wrappers around the Pallas kernels.

Dispatch policy: on TPU the compiled Pallas kernels run natively; on CPU
(this container) the default is the pure-jnp oracle (`ref.py`) for speed,
with ``impl="pallas"`` forcing interpret-mode Pallas — that is what the
kernel test-suite sweeps.  Wrappers own all padding so kernels only ever
see tile-aligned shapes.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import bcq, formats
from repro.core.bcq import BCQConfig
from repro.kernels import ref
from repro.kernels.bcq_linear import bcq_linear_pallas
from repro.kernels.bcq_matmul import bcq_matmul_pallas
from repro.kernels.bcq_quantize import bcq_quantize_pallas


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedOperand:
    idx_packed: jax.Array  # uint8 (R, Kp//2)
    sel_packed: jax.Array  # uint8 (R, Kp//(2·L_b))
    inv_scale: jax.Array  # f32  (R, Kp//L_A) = 1/(ŝ_A·s_X)
    k: int  # unpadded reduction length (K % L_A == 0 required) — static
    rows: int  # unpadded row count — static

    def tree_flatten(self):
        return (self.idx_packed, self.sel_packed, self.inv_scale), (self.k, self.rows)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def _default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _pad2d(x, row_mult, col_mult):
    r, c = x.shape
    pr, pc = (-r) % row_mult, (-c) % col_mult
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    return x


@partial(jax.jit, static_argnames=("cfg", "impl", "tile_m", "tile_k"))
def quantize(
    x: jax.Array,
    codebooks: jax.Array,
    cfg: BCQConfig,
    s_x: jax.Array | None = None,
    impl: str | None = None,
    tile_m: int = 128,
    tile_k: int = 512,
) -> PackedOperand:
    """Encode a 2-D operand (rows × reduction-K) to packed LO-BCQ.

    K must be a multiple of L_A so that tile padding consists of whole
    arrays, which the inv-scale mask then zeroes exactly.
    """
    impl = impl or _default_impl()
    rows, k = x.shape
    assert k % cfg.array_len == 0, "packed path requires K % L_A == 0"
    xf = x.astype(jnp.float32)
    if s_x is None:
        s_x = bcq.tensor_scale(xf, cfg)
    if impl == "ref":
        xp = _pad2d(xf, 1, cfg.array_len)
        idx_p, sel_p, ratio = ref.quantize_ref(xp, codebooks, cfg, s_x)
    else:
        xp = _pad2d(xf, tile_m, tile_k)
        idx_p, sel_p, ratio = bcq_quantize_pallas(
            xp, codebooks, s_x, cfg, tile_m=tile_m, tile_k=tile_k,
        )
    inv = 1.0 / (ratio * s_x)
    # zero padded-K arrays so they contribute nothing to matmuls
    ka = xp.shape[1] // cfg.array_len
    valid = (jnp.arange(ka) * cfg.array_len) < k
    inv = inv * valid[None, :]
    return PackedOperand(idx_p, sel_p, inv, k, rows)


@partial(jax.jit, static_argnames=("cfg", "impl", "tile_m", "tile_n", "tile_k"))
def matmul(
    a: PackedOperand,
    w: PackedOperand,
    codebooks: jax.Array,
    cfg: BCQConfig,
    impl: str | None = None,
    tile_m: int = 128,
    tile_n: int = 128,
    tile_k: int = 512,
) -> jax.Array:
    """W4A4 GEMM: (M, K)·(N, K)ᵀ on packed operands → f32 (M, N)."""
    impl = impl or _default_impl()
    if impl == "ref":
        out = ref.matmul_ref(
            a.idx_packed, a.sel_packed, a.inv_scale,
            w.idx_packed, w.sel_packed, w.inv_scale,
            codebooks, codebooks, cfg,
        )
        return out[: a.rows, : w.rows]

    def padded(op: PackedOperand, rm: int) -> PackedOperand:
        spb = cfg.block_len * 2
        return PackedOperand(
            _pad2d(op.idx_packed, rm, tile_k // 2),
            _pad2d(op.sel_packed, rm, tile_k // spb),
            _pad2d(op.inv_scale, rm, tile_k // cfg.array_len),
            op.k,
            op.rows,
        )

    ap, wp = padded(a, tile_m), padded(w, tile_n)
    out = bcq_matmul_pallas(
        ap.idx_packed, ap.sel_packed, ap.inv_scale,
        wp.idx_packed, wp.sel_packed, wp.inv_scale,
        codebooks, codebooks, cfg,
        tile_m=tile_m, tile_n=tile_n, tile_k=tile_k,
    )
    return out[: a.rows, : w.rows]


@partial(jax.jit, static_argnames=("cfg", "impl"))
def w4a4_linear(
    x: jax.Array,
    w_packed: PackedOperand,
    codebooks: jax.Array,
    cfg: BCQConfig,
    impl: str | None = None,
) -> jax.Array:
    """Full LO-BCQ linear: on-the-fly activation quantization (dynamic s_X)
    + W4A4 GEMM.  x: (..., K); weights pre-encoded (N, K).  Returns (..., N).

    Two kernel launches (quantize, then matmul) — packed activations
    round-trip through HBM.  Prefer :func:`w4a4_linear_fused`."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    a = quantize(x2, codebooks, cfg, impl=impl)
    out = matmul(a, w_packed, codebooks, cfg, impl=impl)
    return out.reshape(*lead, -1).astype(x.dtype)


def packed_operand(pk: dict) -> PackedOperand:
    """View a model-side packed weight dict (models/layers.pack_weight
    layout: idx / sel / E4M3 scale bits / s_x) as a kernel PackedOperand
    with the dequant scales pre-inverted (zero where never written)."""
    assert pk["idx"].ndim == 2, "packed_operand takes one (N, K) weight"
    ratio = formats.bits_to_e4m3(pk["scale"])
    inv = jnp.where(ratio > 0, 1.0 / (ratio * pk["s_x"]), 0.0)
    n, kp2 = pk["idx"].shape
    return PackedOperand(pk["idx"], pk["sel"], inv.astype(jnp.float32), kp2 * 2, n)


def default_tile_k(k: int, cfg: BCQConfig) -> int:
    """The fused linear's K tile: the largest of 512/384/256/128 that
    divides K and is a whole number of arrays (no K padding at model
    widths); 512 when none does."""
    return next(
        (t for t in (512, 384, 256, 128) if k % t == 0 and t % cfg.array_len == 0),
        512,
    )


@partial(jax.jit, static_argnames=("cfg", "impl", "tile_m", "tile_n", "tile_k"))
def w4a4_linear_fused(
    x: jax.Array,
    w_packed: PackedOperand,
    codebooks: jax.Array,
    cfg: BCQConfig,
    s_x: jax.Array | None = None,
    impl: str | None = None,
    tile_m: int = 128,
    tile_n: int = 128,
    tile_k: int | None = None,
) -> jax.Array:
    """Single-launch fused W4A4 linear (kernels/bcq_linear.py): the raw
    activation tile is encoded in VMEM and the weights decode by lane
    gathers — packed activations never touch HBM.  Bit-exact with
    :func:`w4a4_linear` at matching tile sizes.  x: (..., K); weights
    pre-encoded (N, K); ``s_x`` overrides the per-tensor activation scale
    (defaults to the dynamic reduction over x).  ``tile_k`` None picks the
    largest of 512/384/256/128 dividing K (no K padding at model widths).
    Returns (..., N)."""
    impl = impl or _default_impl()
    if tile_k is None:
        tile_k = default_tile_k(w_packed.k, cfg)
    lead = x.shape[:-1]
    k = x.shape[-1]
    assert k == w_packed.k, "activation/weight reduction dims must match"
    assert k % cfg.array_len == 0, "fused path requires K % L_A == 0"
    x2 = x.reshape(-1, k).astype(jnp.float32)
    rows = x2.shape[0]
    if s_x is None:
        s_x = bcq.tensor_scale(x2, cfg)
    if impl == "ref":
        out = ref.fused_linear_ref(
            x2, w_packed.idx_packed, w_packed.sel_packed, w_packed.inv_scale,
            codebooks, cfg, s_x, valid_k=k,
        )
    else:
        spb = cfg.block_len * 2
        xp = _pad2d(x2, tile_m, tile_k)
        out = bcq_linear_pallas(
            xp,
            _pad2d(w_packed.idx_packed, tile_n, tile_k // 2),
            _pad2d(w_packed.sel_packed, tile_n, tile_k // spb),
            _pad2d(w_packed.inv_scale, tile_n, tile_k // cfg.array_len),
            codebooks, s_x, cfg,
            tile_m=tile_m, tile_n=tile_n, tile_k=tile_k,
        )
    out = out[:rows, : w_packed.rows]
    return out.reshape(*lead, -1).astype(x.dtype)
