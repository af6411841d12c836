"""Pure-jnp oracles for the Pallas kernels.

Contracts (all 2-D, blocks along K):

``quantize_ref(x, codebooks, cfg, s_x)``
    x: (M, K) with K % L_A == 0.  Returns
      idx_packed: uint8 (M, K//2)          two 4-bit codeword indices / byte
      sel_packed: uint8 (M, K//L_b//2)     two 4-bit codebook selectors / byte
      ratio:      f32  (M, K//L_A)         E4M3-snapped ŝ_A = Q(s_A/s_X)
    (s_X is computed by the caller — a per-tensor reduction.)

``matmul_ref(a..., w..., inv scales)``
    W4A4 GEMM: decode both operands' INT-B_c codewords, apply per-array
    dequant scales, contract over K in f32:  out[m,n] = Σ_k Â[m,k]·Ŵ[n,k].
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import bcq, formats
from repro.core.bcq import BCQConfig, pack_nibbles, unpack_nibbles


@partial(jax.jit, static_argnames=("cfg",))
def quantize_ref(x: jax.Array, codebooks: jax.Array, cfg: BCQConfig, s_x: jax.Array):
    m, k = x.shape
    assert k % cfg.array_len == 0
    xf = x.astype(jnp.float32)
    arrays = xf.reshape(m, k // cfg.array_len, cfg.array_len)
    ratio, scale = bcq._array_scales(arrays, cfg, s_x)
    y = arrays * scale[..., None]
    blocks = y.reshape(m, -1, cfg.block_len)
    sel, idx = bcq._select_and_index(blocks, codebooks)
    return (
        pack_nibbles(idx.reshape(m, k)),
        pack_nibbles(sel.reshape(m, -1)),
        ratio.astype(jnp.float32),
    )


@partial(jax.jit, static_argnames=("cfg",))
def decode_ref(
    idx_packed: jax.Array,
    sel_packed: jax.Array,
    inv_scale: jax.Array,
    codebooks: jax.Array,
    cfg: BCQConfig,
) -> jax.Array:
    """Dequantize packed operand to f32 (M, K). inv_scale = 1/(ŝ_A·s_X)."""
    idx = unpack_nibbles(idx_packed).astype(jnp.int32)  # (M, K)
    m, k = idx.shape
    nb = k // cfg.block_len
    sel = unpack_nibbles(sel_packed).astype(jnp.int32)[..., :nb]
    flat = codebooks.reshape(-1)
    sel_s = jnp.repeat(sel, cfg.block_len, axis=-1)
    vals = flat[sel_s * cfg.n_entries + idx]
    inv_s = jnp.repeat(inv_scale, cfg.array_len, axis=-1)
    return vals * inv_s


@partial(jax.jit, static_argnames=("cfg",))
def matmul_ref(
    a_idx, a_sel, a_inv, w_idx, w_sel, w_inv, codebooks_a, codebooks_w, cfg: BCQConfig
) -> jax.Array:
    """out (M, N) f32 = dequant(A) @ dequant(W)^T, K contraction."""
    a = decode_ref(a_idx, a_sel, a_inv, codebooks_a, cfg)
    w = decode_ref(w_idx, w_sel, w_inv, codebooks_w, cfg)
    return jax.lax.dot_general(
        a, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def inv_scale(ratio: jax.Array, s_x: jax.Array) -> jax.Array:
    return 1.0 / (ratio * s_x)


@partial(jax.jit, static_argnames=("cfg",))
def fused_linear_ref(
    x: jax.Array,
    w_idx: jax.Array,
    w_sel: jax.Array,
    w_inv: jax.Array,
    codebooks: jax.Array,
    cfg: BCQConfig,
    s_x: jax.Array,
) -> jax.Array:
    """Oracle for the fused W4A4 linear (kernels/bcq_linear.py).

    Encode x (M, K) on the fly, decode both operands, contract over K in
    one dot: the jnp composition of quantize_ref + matmul_ref."""
    idx_p, sel_p, ratio = quantize_ref(x, codebooks, cfg, s_x)
    return matmul_ref(
        idx_p, sel_p, inv_scale(ratio, s_x), w_idx, w_sel, w_inv,
        codebooks, codebooks, cfg,
    )


# ---------------------------------------------------- paged attention oracle
def _dequant_pool_ref(pool: dict, nm: str, kind: str, cfg: BCQConfig) -> jax.Array:
    """Dequantize the whole page pool's K or V side to f32 (P, ps, H, D)."""
    if kind == "bf16":
        return pool[nm].astype(jnp.float32)
    if kind == "int8":
        return pool[nm].astype(jnp.float32) * pool[f"{nm}_scale"][..., None]
    if kind == "bcq4":
        idx = unpack_nibbles(pool[f"{nm}_idx"]).astype(jnp.int32)
        d = idx.shape[-1]
        if d % cfg.array_len:
            import dataclasses

            cfg = dataclasses.replace(cfg, array_len=min(cfg.array_len, d))
        nb = d // cfg.block_len
        sel = unpack_nibbles(pool[f"{nm}_sel"]).astype(jnp.int32)[..., :nb]
        ratio = formats.bits_to_e4m3(pool[f"{nm}_scale"])
        inv = jnp.where(ratio > 0, 1.0 / (ratio * pool[f"{nm}_sx"]), 0.0)
        flat = pool["_cb"].reshape(-1)
        vals = flat[jnp.repeat(sel, cfg.block_len, -1) * cfg.n_entries + idx]
        return vals * jnp.repeat(inv, cfg.array_len, -1)
    raise ValueError(kind)


def paged_attention_ref(
    q: jax.Array,
    pool: dict,
    block_tables: jax.Array,
    lengths: jax.Array,
    kind: str,
    cfg: BCQConfig,
    cb: jax.Array | None = None,
) -> jax.Array:
    """Oracle for the Pallas paged decode kernel: exact masked softmax over
    the block-table-gathered, dequantized pages.

    q (B, H, D); pool leaves (P, ps, Hkv, ...); block_tables (B, MAXP);
    lengths (B,) live tokens.  Returns (B, H, D) f32."""
    pool = dict(pool)
    if cb is not None:
        pool["_cb"] = cb
    b, h, d = q.shape
    kf = _dequant_pool_ref(pool, "k", kind, cfg)  # (P, ps, Hkv, D)
    vf = _dequant_pool_ref(pool, "v", kind, cfg)
    ps = kf.shape[1]
    hkv = kf.shape[2]

    def gather(x):
        g = x[block_tables]  # (B, MAXP, ps, Hkv, D)
        return g.reshape(b, -1, hkv, d)

    kg, vg = gather(kf), gather(vf)
    rep = h // hkv
    if rep > 1:
        kg = jnp.repeat(kg, rep, axis=2)
        vg = jnp.repeat(vg, rep, axis=2)
    s = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32), kg) * (d**-0.5)
    tpos = jnp.arange(kg.shape[1])
    mask = tpos[None, None, :] < lengths[:, None, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,bthd->bhd", p, vg)


def chunked_prefill_ref(
    q: jax.Array,
    pool: dict,
    block_tables: jax.Array,
    n_past: jax.Array,
    kind: str,
    cfg: BCQConfig,
    cb: jax.Array | None = None,
) -> jax.Array:
    """Oracle for the Pallas chunked-prefill kernel: the query chunk's
    exact masked softmax over the block-table-gathered, dequantized pages.

    q (B, C, H, D) — query c sits at absolute position n_past[b] + c;
    pool leaves (P, ps, Hkv, ...) with the chunk's own K/V already written
    into its pages; block_tables (B, MAXP); n_past (B,) tokens in pages
    before the chunk.  Page token t (absolute position t in the gathered
    sequence) is visible iff t <= n_past[b] + c — prefix tokens see the
    whole chunk, chunk tokens mask causally, unwritten tails are hidden.
    Returns (B, C, H, D) f32."""
    pool = dict(pool)
    if cb is not None:
        pool["_cb"] = cb
    b, c, h, d = q.shape
    kf = _dequant_pool_ref(pool, "k", kind, cfg)  # (P, ps, Hkv, D)
    vf = _dequant_pool_ref(pool, "v", kind, cfg)
    ps = kf.shape[1]
    hkv = kf.shape[2]

    def gather(x):
        g = x[block_tables]  # (B, MAXP, ps, Hkv, D)
        return g.reshape(b, -1, hkv, d)

    kg, vg = gather(kf), gather(vf)
    rep = h // hkv
    if rep > 1:
        kg = jnp.repeat(kg, rep, axis=2)
        vg = jnp.repeat(vg, rep, axis=2)
    s = jnp.einsum("bchd,bthd->bhct", q.astype(jnp.float32), kg) * (d**-0.5)
    tpos = jnp.arange(kg.shape[1])
    qpos = n_past[:, None] + jnp.arange(c)  # (B, C)
    mask = tpos[None, None, None, :] <= qpos[:, None, :, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhct,bthd->bchd", p, vg)
