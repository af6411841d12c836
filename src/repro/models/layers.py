"""Shared model primitives: norms, RoPE, quantized dense, GQA attention,
MLPs, KV caches (bf16 / int8 / packed-BCQ4).

Everything is functional: ``init_*`` builds param dicts; apply functions are
pure.  Quantization is threaded via ``Runtime`` (static) + codebooks (traced
array living in the param tree), so a single model definition serves:

  quant_mode='none'      bf16 baseline,
  quant_mode='fake'      W4A4 serving: acts quantized on the fly, weights
                         PTQ'd offline (paper §4.1 fn.3 emulation),
  quant_mode='fake_full' also quantizes weights in-graph,
  quant_mode='packed'    weights stored as packed 4-bit buffers and decoded
                         in-graph (true-storage serving path; on TPU the
                         Pallas kernels of kernels/ implement the same math).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import bcq, formats
from repro.core.bcq import BCQConfig


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Static per-run model configuration (hashable → jit-static)."""

    # none      — bf16 baseline
    # fake      — W4A4 serving: activations quantize-dequantize on the fly;
    #             weights are PTQ'd *offline* (core/ptq.py) so carry no
    #             in-graph quantization ops (the paper's deployment)
    # fake_full — also quantize weights in-graph (calibration/ablation runs)
    # packed    — weights stored as packed 4-bit buffers, decoded in-graph
    quant_mode: str = "none"
    bcq_cfg: BCQConfig = BCQConfig()
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    cache_kind: str = "bf16"  # bf16 | int8 | bcq4
    attn_chunk: int = 1024  # query-chunked attention block
    remat: bool = False
    logit_chunk: int = 0  # 0 = unchunked loss
    # Fully unroll every scan/map (dry-run only): XLA's HloCostAnalysis
    # counts while-loop bodies once, so unrolled lowering is what makes
    # cost_analysis FLOPs/bytes exact for the roofline.
    unroll: bool = False
    # on-the-fly activation quantizer for 'fake'/'fake_full' modes:
    # bcq (paper) | mx4 | mxfp4 | vsq | int4 — enables honest W4A4
    # baseline comparisons (Table 2/6)
    act_format: str = "bcq"
    # remat policy when remat=True: 'full' (save nothing) | 'dots' (save
    # GEMM outputs — avoids re-running the FSDP weight all-gathers in bwd)
    remat_policy: str = "full"
    # sequence-sharded exact-softmax decode attention (shard_map over the
    # 'model' axis): replaces XLA's KV-cache all-gather with tiny
    # pmax/psum partials — the §Perf lever for full-MHA decode
    flash_decode: bool = False
    # f32 attention scores (default, safest) vs bf16 scores with f32
    # softmax reduction — halves the dominant prefill score traffic
    attn_f32: bool = True
    # route self-attention through the Pallas flash kernel
    # (kernels/flash_attention.py): O(S·d) HBM instead of O(S²) scores.
    # interpret-mode on CPU (tests); native on TPU.  Causal, no window.
    flash_kernel: bool = False
    # route paged decode attention through the Pallas paged kernel
    # (kernels/paged_attention.py) instead of the gather+dequant jnp path.
    # interpret-mode on CPU (tests); native on TPU.
    paged_kernel: bool = False
    # route quant_mode='packed' linears through the fused single-launch
    # quantize→decode→GEMM (kernels/bcq_linear.py) instead of the in-graph
    # decode_packed_weight + einsum: raw activations encode in VMEM, the
    # weights decode by lane gathers, packed activations never
    # round-trip HBM.  Native Pallas on TPU; elsewhere the ref-oracle
    # composition runs (bit-exact with the two-launch kernels).
    fused_linear: bool = True
    mesh: Any = None  # required (hashable) when flash_decode is set
    # opt-in online quantization-error probe (serving telemetry): a
    # host-side sink called as sink(site_tag, nmse, occupancy) via
    # jax.debug.callback from every BCQ activation-encode site.  None
    # (default) stages nothing — the serving graphs are unchanged.  The
    # sink is compared/hashes by object identity, so two Runtimes with
    # different sinks are distinct jit-static values (separate caches,
    # no silent cross-engine probe sharing).
    quant_probe: Any = None


# ------------------------------------------------------------------- init
def uinit(key, shape, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else (1.0 / max(shape[0], 1)) ** 0.5
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def init_dense(key, d_in, d_out, bias=False, dtype=jnp.float32):
    p = {"kernel": uinit(key, (d_in, d_out), dtype=dtype)}
    if bias:
        p["bias"] = jnp.zeros((d_out,), dtype)
    return p


def init_norm(d, kind="rmsnorm", dtype=jnp.float32):
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["nbias"] = jnp.zeros((d,), dtype)
    return p


# ------------------------------------------------------------------ norms
def norm_apply(x, p, kind="rmsnorm", eps=1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    else:
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32)
    if "nbias" in p:
        y = y + p["nbias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ------------------------------------------------------- quantized dense
def _fq(x, cb, cfg):
    """Fake-quant activations/weights along the last (reduction) axis."""
    return bcq.fake_quant(x, cb, cfg)


def _quantize_act(x, rt: "Runtime", cb):
    """On-the-fly activation quantization per rt.act_format
    ('none' = weight-only W4A16, paper Table 4)."""
    if rt.act_format == "none":
        return x
    if rt.act_format == "bcq":
        return _fq(x, cb, rt.bcq_cfg)
    from repro.core import baselines as B

    fn = {
        "mx4": B.mx_quantize,
        "mxfp4": B.mxfp4_quantize,
        "vsq": B.vsq_quantize,
        "int4": lambda v: B.int_pertensor(v, 4),
    }[rt.act_format]
    return fn(x)


def _emit_quant_probe(x, rt: "Runtime", cb, tag) -> None:
    """Report the would-be activation-quant error at one GEMM site.

    Stages ``bcq.encode_stats`` over the RAW (pre-quantization)
    activation and ships (nmse, selector occupancy) to the host sink via
    an ordered ``jax.debug.callback`` — ordered so emissions arrive in
    program order even from inside the backbone's ``lax.scan``, which is
    what lets the sink attribute layers by arrival count.  Only fires for
    the paper's BCQ activation quantizer (other act_formats have no
    codebooks to occupy); no-op unless ``rt.quant_probe`` is set AND the
    call site passed a tag (``qdense_shared`` tags once for its head
    group and strips the tag from the per-head calls)."""
    if rt.quant_probe is None or tag is None or cb is None:
        return
    if rt.quant_mode not in ("fake", "fake_full", "packed"):
        return
    if rt.act_format != "bcq":
        return
    nmse, occ = bcq.encode_stats(x.astype(jnp.float32), cb, rt.bcq_cfg)
    jax.debug.callback(
        functools.partial(rt.quant_probe, tag), nmse, occ, ordered=True
    )


def decode_packed_weight(pk: dict, cfg: BCQConfig, cb: jax.Array) -> jax.Array:
    """In-graph dequant of a packed (..., N, K) weight: storage stays 4-bit
    in HBM; decode is gather + multiply (the jnp analogue of the Pallas
    decode-GEMM's VMEM stage)."""
    idx = bcq.unpack_nibbles(pk["idx"]).astype(jnp.int32)  # (..., N, K)
    k = idx.shape[-1]
    nb = k // cfg.block_len
    sel = bcq.unpack_nibbles(pk["sel"]).astype(jnp.int32)[..., :nb]
    ratio = formats.bits_to_e4m3(pk["scale"])  # (..., N, K/L_A)
    flat = cb.reshape(-1)
    sel_s = jnp.repeat(sel, cfg.block_len, axis=-1)
    vals = flat[sel_s * cfg.n_entries + idx]
    s_x = pk["s_x"]
    if getattr(s_x, "ndim", 0):  # per-expert scales (E,) on stacked weights
        s_x = s_x.reshape(s_x.shape + (1,) * (ratio.ndim - s_x.ndim))
    inv = jnp.repeat(1.0 / (ratio * s_x), cfg.array_len, axis=-1)
    return vals * inv  # f32 (..., N, K)


def fused_packed_linear(x, pk: dict, rt: "Runtime", cb, s_x=None):
    """quant_mode='packed' linear through the fused single-launch Pallas
    kernel (kernels/bcq_linear.py via ops.w4a4_linear_fused): activations
    encode on the fly in VMEM; the packed weight buffers stream 4.5-bit.
    x: (..., K); pk: pack_weight dict (N, K).  Returns f32-accurate (..., N)
    in x.dtype."""
    from repro.kernels import ops as kernel_ops

    return kernel_ops.w4a4_linear_fused(
        x, kernel_ops.packed_operand(pk), cb, rt.bcq_cfg, s_x=s_x
    )


def pack_weight(w: jax.Array, cfg: BCQConfig, cb: jax.Array) -> dict:
    """Offline PTQ: (K, N) kernel → packed dict (blocks along K)."""
    wt = jnp.asarray(w).T.astype(jnp.float32)  # (N, K)
    enc = bcq.encode(wt, cb, cfg)
    return {
        "idx": enc.packed_idx,
        "sel": enc.packed_sel,
        "scale": enc.scale_code,
        "s_x": enc.s_x,
    }


def packed_weight_shapes(d_in: int, d_out: int, cfg: BCQConfig) -> dict:
    """ShapeDtypeStructs of a packed (d_in→d_out) kernel (for dry-runs)."""
    n, k = d_out, d_in
    return {
        "idx": jax.ShapeDtypeStruct((n, k // 2), jnp.uint8),
        "sel": jax.ShapeDtypeStruct((n, k // (2 * cfg.block_len)), jnp.uint8),
        "scale": jax.ShapeDtypeStruct((n, k // cfg.array_len), jnp.uint8),
        "s_x": jax.ShapeDtypeStruct((), jnp.float32),
    }


def qdense_shared(x, ps: list, rt: Runtime, cb, tag=None):
    """Several linear heads over the SAME input (QKV, MLP wi/wg): quantize
    the activation ONCE and reuse — bit-identical to per-head quantization
    (same xq), but 1× instead of N× encode cost/traffic.

    The fused packed path skips the shared pre-quantization: each fused
    kernel encodes the raw tile in VMEM (per-head encode is bit-identical
    anyway — same x, same dynamic s_X — and never round-trips HBM).  The
    fused kernel implements the paper's BCQ activation quantizer only, so
    other act_formats ('none' = W4A16, mx4/…) keep the pre-quantized
    decode+einsum path.

    ``tag`` names this head group for the opt-in quant-error probe —
    emitted ONCE here (the heads share one activation encode), with the
    per-head qdense calls untagged so the probe never double-counts."""
    _emit_quant_probe(x, rt, cb, tag)
    if (
        rt.quant_mode == "packed" and rt.fused_linear
        and rt.act_format == "bcq" and cb is not None
    ):
        return [qdense(x, p, rt, cb) for p in ps]
    if rt.quant_mode in ("fake", "fake_full", "packed") and cb is not None:
        xq = _quantize_act(x.astype(jnp.float32), rt, cb)
        rt = dataclasses.replace(rt, act_format="_pre_quantized")
        x = xq
    return [qdense(x, p, rt, cb) for p in ps]


def qdense(x, p, rt: Runtime, cb: Optional[jax.Array], tag=None):
    """Linear layer honoring rt.quant_mode.  x: (..., K); kernel (K, N).
    ``tag`` (optional) names the site for the quant-error probe; callers
    routing through qdense_shared leave it None (already probed)."""
    if rt.act_format != "_pre_quantized":
        _emit_quant_probe(x, rt, cb, tag)
    dt = rt.compute_dtype
    if rt.act_format == "_pre_quantized" and rt.quant_mode != "none" and cb is not None:
        # input already quantized by qdense_shared
        if rt.quant_mode in ("fake", "fake_full"):
            wk = p["kernel"].astype(dt)
            if rt.quant_mode == "fake_full":
                wk = _fq(p["kernel"].astype(jnp.float32).T, cb, rt.bcq_cfg).astype(dt).T
            y = jnp.einsum("...k,kn->...n", x.astype(dt), wk)
        else:
            w = decode_packed_weight(p["kernel_packed"], rt.bcq_cfg, cb).astype(dt)
            y = jnp.einsum("...k,nk->...n", x.astype(dt), w)
        if "bias" in p:
            y = y + p["bias"].astype(y.dtype)
        return y
    if rt.quant_mode == "none" or cb is None:
        y = jnp.einsum("...k,kn->...n", x.astype(dt), p["kernel"].astype(dt))
    elif rt.quant_mode == "fake":
        # weights already PTQ'd offline; only activations quantize on the fly
        xq = _quantize_act(x.astype(jnp.float32), rt, cb)
        y = jnp.einsum("...k,kn->...n", xq.astype(dt), p["kernel"].astype(dt))
    elif rt.quant_mode == "fake_full":
        xq = _quantize_act(x.astype(jnp.float32), rt, cb)
        wt = p["kernel"].astype(jnp.float32).T  # (N, K): blocks along K
        wq = _fq(wt, cb, rt.bcq_cfg)
        y = jnp.einsum("...k,nk->...n", xq.astype(dt), wq.astype(dt))
    elif rt.quant_mode == "packed":
        if rt.fused_linear:
            y = fused_packed_linear(x, p["kernel_packed"], rt, cb).astype(dt)
        else:
            xq = _fq(x.astype(jnp.float32), cb, rt.bcq_cfg).astype(dt)
            w = decode_packed_weight(p["kernel_packed"], rt.bcq_cfg, cb).astype(dt)
            y = jnp.einsum("...k,nk->...n", xq, w)
    else:
        raise ValueError(rt.quant_mode)
    if "bias" in p:
        y = y + p["bias"].astype(y.dtype)
    return y


def init_qdense(key, d_in, d_out, rt: Runtime, bias=False):
    """Init respecting quant_mode: packed mode stores 4-bit buffers."""
    if rt.quant_mode == "packed":
        p = {
            "kernel_packed": {
                k: jnp.zeros(s.shape, s.dtype)
                for k, s in packed_weight_shapes(d_in, d_out, rt.bcq_cfg).items()
            }
        }
        if bias:
            p["bias"] = jnp.zeros((d_out,), rt.param_dtype)
        return p
    return init_dense(key, d_in, d_out, bias, rt.param_dtype)


# -------------------------------------------------------------------- RoPE
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D) with D even; positions: (B, S) absolute indices."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


# -------------------------------------------------------------- KV caches
def _cache_cfg(cfg: BCQConfig, d_head: int) -> BCQConfig:
    """BCQ config for per-head-vector cache quantization: the array length
    shrinks to d_head when d_head < L_A (small smoke heads)."""
    if d_head % cfg.array_len == 0:
        return cfg
    la = min(cfg.array_len, d_head)
    assert la % cfg.block_len == 0 and d_head % la == 0
    return dataclasses.replace(cfg, array_len=la)


def cache_init(batch, seq, n_kv, d_head, kind, cfg: BCQConfig, dtype=jnp.bfloat16):
    """Empty cache leaves for ONE layer (zoo stacks over layers)."""
    if kind == "bf16":
        z = jnp.zeros((batch, seq, n_kv, d_head), dtype)
        return {"k": z, "v": z}
    if kind == "int8":
        z = jnp.zeros((batch, seq, n_kv, d_head), jnp.int8)
        s = jnp.zeros((batch, seq, n_kv), jnp.float32)
        return {"k": z, "v": z, "k_scale": s, "v_scale": s}
    if kind == "bcq4":
        cfg = _cache_cfg(cfg, d_head)
        return {
            "k_idx": jnp.zeros((batch, seq, n_kv, d_head // 2), jnp.uint8),
            "v_idx": jnp.zeros((batch, seq, n_kv, d_head // 2), jnp.uint8),
            "k_sel": jnp.zeros((batch, seq, n_kv, d_head // (2 * cfg.block_len)), jnp.uint8),
            "v_sel": jnp.zeros((batch, seq, n_kv, d_head // (2 * cfg.block_len)), jnp.uint8),
            "k_scale": jnp.zeros((batch, seq, n_kv, max(d_head // cfg.array_len, 1)), jnp.uint8),
            "v_scale": jnp.zeros((batch, seq, n_kv, max(d_head // cfg.array_len, 1)), jnp.uint8),
            "k_sx": jnp.ones((), jnp.float32),
            "v_sx": jnp.ones((), jnp.float32),
        }
    raise ValueError(kind)


def _cache_quant_int8(x):
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]), -127, 127)
    return q.astype(jnp.int8), s


def cache_write(cache, k_new, v_new, pos, kind, cfg: BCQConfig, cb):
    """Insert (B, S_new, H, D) keys/values at offset ``pos`` (scalar)."""

    def put(buf, val):
        return jax.lax.dynamic_update_slice(
            buf, val.astype(buf.dtype), (0, pos, 0, 0)
        )

    if kind == "bf16":
        return {"k": put(cache["k"], k_new), "v": put(cache["v"], v_new)}
    if kind == "int8":
        kq, ks = _cache_quant_int8(k_new)
        vq, vs = _cache_quant_int8(v_new)
        return {
            "k": put(cache["k"], kq),
            "v": put(cache["v"], vq),
            "k_scale": jax.lax.dynamic_update_slice(cache["k_scale"], ks, (0, pos, 0)),
            "v_scale": jax.lax.dynamic_update_slice(cache["v_scale"], vs, (0, pos, 0)),
        }
    if kind == "bcq4":
        cfg = _cache_cfg(cfg, k_new.shape[-1])
        out = dict(cache)
        for nm, val, sx in (("k", k_new, cache["k_sx"]), ("v", v_new, cache["v_sx"])):
            enc = bcq.encode(val.astype(jnp.float32), cb, cfg, s_x=sx)
            out[f"{nm}_idx"] = put(out[f"{nm}_idx"], enc.packed_idx)
            out[f"{nm}_sel"] = put(out[f"{nm}_sel"], enc.packed_sel)
            out[f"{nm}_scale"] = put(out[f"{nm}_scale"], enc.scale_code)
        return out
    raise ValueError(kind)


def cache_write_rows(cache, k_new, v_new, pos_rows, kind, cfg: BCQConfig, cb):
    """Insert (B, 1, H, D) keys/values at per-row offsets ``pos_rows`` (B,).

    The per-row sibling of ``cache_write`` for batched decode over rows at
    heterogeneous positions (the paged state engine: every resident slot
    sits at its own absolute position).  Row i writes cache[i, pos_rows[i]];
    quantization is the same per-(token, head)-vector path as
    ``cache_write``, so the bytes written for a row at position p are
    bit-identical to a scalar-pos ``cache_write`` of that row at p."""
    b = k_new.shape[0]
    rows = jnp.arange(b)

    def put(buf, val):
        return buf.at[rows, pos_rows].set(val[:, 0].astype(buf.dtype))

    if kind == "bf16":
        return {"k": put(cache["k"], k_new), "v": put(cache["v"], v_new)}
    if kind == "int8":
        kq, ks = _cache_quant_int8(k_new)
        vq, vs = _cache_quant_int8(v_new)
        return {
            "k": put(cache["k"], kq),
            "v": put(cache["v"], vq),
            "k_scale": put(cache["k_scale"], ks),
            "v_scale": put(cache["v_scale"], vs),
        }
    if kind == "bcq4":
        cfg = _cache_cfg(cfg, k_new.shape[-1])
        out = dict(cache)  # keeps the per-tensor k_sx / v_sx scalars
        for nm, val, sx in (("k", k_new, cache["k_sx"]), ("v", v_new, cache["v_sx"])):
            enc = bcq.encode(val.astype(jnp.float32), cb, cfg, s_x=sx)
            out[f"{nm}_idx"] = put(out[f"{nm}_idx"], enc.packed_idx)
            out[f"{nm}_sel"] = put(out[f"{nm}_sel"], enc.packed_sel)
            out[f"{nm}_scale"] = put(out[f"{nm}_scale"], enc.scale_code)
        return out
    raise ValueError(kind)


def cache_read(cache, kind, cfg: BCQConfig, cb, dtype, valid_len: Optional[int] = None):
    """Dequantize cache → (k, v) in compute dtype.

    ``valid_len`` (STATIC) bounds the read to the first ``valid_len``
    sequence positions: the int8/bcq4 dequant (gathers + multiplies) then
    runs over only the written prefix instead of the whole max-length
    buffer.  Callers that know a static upper bound on the number of live
    tokens (e.g. bucketed decode, paged gathers) pass it; ``None`` keeps
    the full-cache behaviour."""
    if valid_len is not None:
        cache = {
            n: (leaf[:, :valid_len] if getattr(leaf, "ndim", 0) >= 2 else leaf)
            for n, leaf in cache.items()
        }
    if kind == "bf16":
        return cache["k"].astype(dtype), cache["v"].astype(dtype)
    if kind == "int8":
        k = cache["k"].astype(jnp.float32) * cache["k_scale"][..., None]
        v = cache["v"].astype(jnp.float32) * cache["v_scale"][..., None]
        return k.astype(dtype), v.astype(dtype)
    if kind == "bcq4":
        outs = []
        for nm in ("k", "v"):
            idx = bcq.unpack_nibbles(cache[f"{nm}_idx"]).astype(jnp.int32)
            d = idx.shape[-1]
            cfg = _cache_cfg(cfg, d)
            nb = d // cfg.block_len
            sel = bcq.unpack_nibbles(cache[f"{nm}_sel"]).astype(jnp.int32)[..., :nb]
            ratio = formats.bits_to_e4m3(cache[f"{nm}_scale"])
            # unwritten slots hold ratio == 0 → decode to 0, not inf
            inv_r = jnp.where(ratio > 0, 1.0 / (ratio * cache[f"{nm}_sx"]), 0.0)
            flat = cb.reshape(-1)
            vals = flat[jnp.repeat(sel, cfg.block_len, -1) * cfg.n_entries + idx]
            inv = jnp.repeat(inv_r, cfg.array_len, -1)
            outs.append((vals * inv).astype(dtype))
        return outs[0], outs[1]
    raise ValueError(kind)


def cache_sx_calibrate(cache, k_sample, v_sample, kind, cfg: BCQConfig):
    """Set per-tensor cache scales from the prefill K/V (bcq4 only)."""
    if kind != "bcq4":
        return cache
    out = dict(cache)
    out["k_sx"] = bcq.tensor_scale(k_sample.astype(jnp.float32), cfg)
    out["v_sx"] = bcq.tensor_scale(v_sample.astype(jnp.float32), cfg)
    return out


# ------------------------------------------------------- paged KV pages
# A page pool is structurally a KV cache whose batch axis is the global
# page pool and whose sequence axis is the page slot: leaves are
# (n_pages, page_size, H, ...) built by cache_init(n_pages, page_size, ...).
# Because cache quantization is per (token, head) vector along d_head —
# an integer number of L_A block arrays — a page boundary never splits a
# BCQ block array, so pages carry their own scale/selector metadata and
# dequantize independently.


def pool_page_size(pool: dict) -> int:
    """Page size (tokens) of a single-layer page-pool tree."""
    for leaf in pool.values():
        if getattr(leaf, "ndim", 0) >= 2:
            return leaf.shape[1]
    raise ValueError("pool has no paged leaves")


def paged_token_write(pool, k_new, v_new, page_ids, offsets, kind, cfg: BCQConfig, cb):
    """Quantize one new token per sequence and scatter it into its page.

    pool: single-layer page-pool tree, leaves (P, ps, H, ...);
    k_new/v_new: (B, 1, H, D); page_ids/offsets: (B,) int32 page slot of
    each sequence's tail.  Sequences never share a mutable page (the
    engine's copy-on-write guarantees the tail page is private), so the
    per-batch scatters are disjoint."""
    b = k_new.shape[0]
    stage = cache_init(b, 1, k_new.shape[2], k_new.shape[3], kind, cfg)
    for n in ("k_sx", "v_sx"):
        if n in pool:
            stage[n] = pool[n]
    enc = cache_write(stage, k_new, v_new, 0, kind, cfg, cb)
    out = dict(pool)
    for n, leaf in pool.items():
        if getattr(leaf, "ndim", 0) < 2:
            continue  # per-tensor scales are pool-global
        out[n] = leaf.at[page_ids, offsets].set(enc[n][:, 0].astype(leaf.dtype))
    return out


def paged_chunk_write(pool, k_new, v_new, chunk_page_ids, kind, cfg: BCQConfig, cb,
                      chunk_len=None):
    """Quantize a prefill chunk's K/V and scatter it into pool pages.

    pool: single-layer page-pool tree, leaves (P, ps, H, ...);
    k_new/v_new: (B, C, H, D) — the chunk's fresh keys/values;
    chunk_page_ids: (B, n_cp) int32 destination pages, n_cp = ceil(C/ps).
    The chunk starts at a page boundary (the engine aligns chunk size to
    the page size, so only a prompt's LAST chunk is ragged) and its pages
    are freshly allocated and private, so whole-page scatters are safe.
    Quantization is per (token, head) vector — bit-identical to what a
    full-prompt prefill writes for the same tokens, so chunked pages are
    byte-for-byte the pages scatter_prefill_pages would have produced
    (the tail beyond C holds cache_init zeros either way).

    ``chunk_len`` (B,) int32, optional: valid tokens per row when C is a
    padded bucket (the batched engine tick stacks ragged tail chunks into
    one launch).  Encoded leaves past each row's chunk_len are reset to
    the all-zero ``cache_init`` state before the scatter, so a padded row
    writes byte-identical pages to an exact-length launch; pages wholly
    past a row's chunk route to NULL_PAGE via ``chunk_page_ids``."""
    b = k_new.shape[0]
    ps = pool_page_size(pool)
    n_cp = chunk_page_ids.shape[1]
    stage = cache_init(b, n_cp * ps, k_new.shape[2], k_new.shape[3], kind, cfg)
    for n in ("k_sx", "v_sx"):
        if n in pool:
            stage[n] = pool[n]
    enc = cache_write(stage, k_new, v_new, 0, kind, cfg, cb)
    if chunk_len is not None:
        pos = jnp.arange(n_cp * ps, dtype=jnp.int32)
        valid = pos[None, :] < chunk_len[:, None]  # (B, n_cp·ps)
    out = dict(pool)
    for n, leaf in pool.items():
        if getattr(leaf, "ndim", 0) < 2:
            continue  # per-tensor scales are pool-global
        src = enc[n]  # (B, n_cp·ps, ...)
        if chunk_len is not None:
            m = valid.reshape(valid.shape + (1,) * (src.ndim - 2))
            src = jnp.where(m, src, jnp.zeros_like(src))
        pages = src.reshape((b, n_cp, ps) + src.shape[2:])
        out[n] = leaf.at[chunk_page_ids].set(pages.astype(leaf.dtype))
    return out


def paged_gather_kv(pool, block_tables, kind, cfg: BCQConfig, cb, dtype):
    """Gather each sequence's pages via its block table and dequantize.

    block_tables: (B, MAXP) int32 page ids (0 = reserved null page).
    Returns (k, v) of shape (B, MAXP·ps, H, D) — only referenced pages are
    read from the pool; dead/beyond-length positions hold garbage and must
    be masked by the caller's validity mask."""
    gathered = {}
    for n, leaf in pool.items():
        if getattr(leaf, "ndim", 0) < 2:
            gathered[n] = leaf
            continue
        g = leaf[block_tables]  # (B, MAXP, ps, ...)
        gathered[n] = g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])
    return cache_read(gathered, kind, cfg, cb, dtype)


def maybe_remat(fn, rt: Runtime):
    if not rt.remat:
        return fn
    pol = None
    if rt.remat_policy == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint(fn, policy=pol)


def flash_decode_sharded(q, kf, vf, valid, rt: Runtime):
    """Exact-softmax decode attention with the KV sequence sharded over the
    'model' mesh axis.  Per shard: local scores → running (max, sum, acc);
    cross-shard combine via pmax + two psums of (B, H[, D]) — O(MBs)
    instead of all-gathering the multi-GiB KV cache.

    q: (B, 1, H, D) replicated over 'model'; kf/vf: (B, S, Hkv, D) with S
    sharded; valid: traced scalar (# valid cache slots)."""
    from jax.sharding import PartitionSpec as P

    mesh = rt.mesh
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    b, sq, h, d = q.shape
    skv, hkv = kf.shape[1], kf.shape[2]
    if sq != 1 or "model" not in axes or skv % axes["model"]:
        return None  # caller falls back to the gathered path
    dax = "data" if b % axes.get("data", 1) == 0 and "data" in axes else None
    qs = P(dax, None, None, None)
    kvs = P(dax, "model", None, None)

    def core(qb, kb, vb, vd):
        rep = h // hkv
        kx = jnp.repeat(kb, rep, 2) if rep > 1 else kb
        vx = jnp.repeat(vb, rep, 2) if rep > 1 else vb
        s_loc = jnp.einsum(
            "bqhd,bkhd->bhqk", qb.astype(jnp.float32), kx.astype(jnp.float32)
        ) * (d ** -0.5)
        sl = kb.shape[1]
        j = jax.lax.axis_index("model") * sl + jnp.arange(sl)
        mask = j[None, None, None, :] < vd
        s_loc = jnp.where(mask, s_loc, -1e30)
        m_loc = jnp.max(s_loc, axis=-1)  # (B, H, 1)
        m = jax.lax.pmax(m_loc, "model")
        p = jnp.exp(s_loc - m[..., None])
        l = jax.lax.psum(jnp.sum(p, -1), "model")  # (B, H, 1)
        acc = jax.lax.psum(
            jnp.einsum("bhqk,bkhd->bqhd", p, vx.astype(jnp.float32)), "model"
        )
        return acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]

    out = jax.shard_map(
        core, mesh=mesh, in_specs=(qs, kvs, kvs, P()), out_specs=qs,
        check_vma=False,
    )(q, kf, vf, jnp.asarray(valid))
    return out.astype(q.dtype)


def cache_write_sharded(cache, k_new, v_new, pos, rt: Runtime, cb):
    """Decode-step cache insert with the sequence dim sharded over 'model'.

    A plain dynamic-update-slice at a *traced* position into a sharded dim
    makes XLA SPMD replicate (all-gather) the whole cache — the dominant
    collective in full-MHA decode.  Instead, quantize the new token tile,
    then let the owning shard update locally: owner = pos // shard_len,
    local offset = pos % shard_len, others pass through.  Zero collectives.
    """
    from jax.sharding import PartitionSpec as P

    mesh = rt.mesh
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    mp = axes.get("model", 1)
    # quantize the (B, 1, H, D) token via a length-1 staging cache
    b = k_new.shape[0]
    stage = cache_init(b, 1, k_new.shape[2], k_new.shape[3], rt.cache_kind, rt.bcq_cfg)
    for n in ("k_sx", "v_sx"):
        if n in cache:
            stage[n] = cache[n]
    new_vals = cache_write(stage, k_new, v_new, 0, rt.cache_kind, rt.bcq_cfg, cb)

    out = {}
    for n, buf in cache.items():
        if buf.ndim < 2 or buf.shape[1] % mp:
            out[n] = new_vals.get(n, buf) if buf.ndim < 2 else buf
            continue
        val = new_vals[n]
        shard_len = buf.shape[1] // mp
        dax = "data" if "data" in axes and buf.shape[0] % axes["data"] == 0 else None
        tail = [None] * (buf.ndim - 2)
        bspec = P(dax, "model", *tail)
        vspec = P(dax, None, *tail)

        def core(bm, vm, p, _sl=shard_len):
            owner = p // _sl
            lp = p % _sl
            upd = jax.lax.dynamic_update_slice(
                bm, vm.astype(bm.dtype), (0, lp) + (0,) * (bm.ndim - 2)
            )
            here = jax.lax.axis_index("model") == owner
            return jnp.where(here.reshape((1,) * bm.ndim), upd, bm)

        out[n] = jax.shard_map(
            core, mesh=mesh, in_specs=(bspec, vspec, P()), out_specs=bspec,
            check_vma=False,
        )(buf, val, jnp.asarray(pos))
    return out


def scan_layers(body, carry, xs, unroll_flag: bool, length=None):
    """lax.scan wrapper honoring Runtime.unroll (full unroll for dry-runs)."""
    if length is None:
        length = jax.tree.leaves(xs)[0].shape[0]
    return jax.lax.scan(body, carry, xs, unroll=length if unroll_flag else 1)


# ---------------------------------------------------------------- attention
def _attend_chunked(q, k, v, q_pos, kv_valid_len, causal, window, chunk, unroll=False, score_f32=True):
    """Exact softmax attention, scanned over query chunks.

    q: (B, Sq, H, D); k/v: (B, Sk, Hkv, D); q_pos: (B, Sq) absolute
    positions; kv position j is absolute index j.  Masks: j <= pos (causal),
    pos - j < window (local), j < kv_valid_len.
    Memory per chunk: B·H·chunk·Sk — never the full Sq×Sk score matrix.
    """
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    kx = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    vx = jnp.repeat(v, rep, axis=2) if rep > 1 else v
    scale = d ** -0.5
    j_idx = jnp.arange(sk)

    sdt = jnp.float32 if score_f32 else jnp.bfloat16
    neg = -1e30 if score_f32 else -3e38

    def one_chunk(args):
        qc, pc = args  # (B, C, H, D), (B, C)
        s = jnp.einsum("bchd,bkhd->bhck", qc.astype(sdt), kx.astype(sdt))
        s = s * jnp.asarray(scale, sdt)
        m = j_idx[None, None, None, :] < kv_valid_len
        if causal:
            m = m & (j_idx[None, None, None, :] <= pc[:, None, :, None])
        if window:
            m = m & (pc[:, None, :, None] - j_idx[None, None, None, :] < window)
        s = jnp.where(m, s, jnp.asarray(neg, sdt))
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(sdt)
        return jnp.einsum("bhck,bkhd->bchd", p, vx.astype(sdt)).astype(jnp.float32)

    while chunk > 1 and sq % chunk:  # largest divisor ≤ requested chunk
        chunk //= 2
    if sq <= chunk or sq % chunk:
        out = one_chunk((q, q_pos))
    else:
        n = sq // chunk
        qs = q.reshape(b, n, chunk, h, d).transpose(1, 0, 2, 3, 4)
        ps = q_pos.reshape(b, n, chunk).transpose(1, 0, 2)
        _, out = jax.lax.scan(
            lambda c, xs: (c, one_chunk(xs)), None, (qs, ps),
            unroll=n if unroll else 1,
        )
        out = out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, d)
    return out.astype(q.dtype)


def init_attention(key, cfg, rt: Runtime):
    ks = jax.random.split(key, 4)
    hd = cfg.head_dim
    return {
        "wq": init_qdense(ks[0], cfg.d_model, cfg.n_heads * hd, rt, bias=cfg.qkv_bias),
        "wk": init_qdense(ks[1], cfg.d_model, cfg.n_kv_heads * hd, rt, bias=cfg.qkv_bias),
        "wv": init_qdense(ks[2], cfg.d_model, cfg.n_kv_heads * hd, rt, bias=cfg.qkv_bias),
        "wo": init_qdense(ks[3], cfg.n_heads * hd, cfg.d_model, rt),
    }


def attention(
    x,
    p,
    cfg,
    rt: Runtime,
    cb,
    positions,
    cache=None,
    cache_pos=None,
    causal=True,
    window=None,
    kv_override=None,
    use_rope=True,
    kv_bound=None,
    paged=None,
):
    """GQA attention.  With ``cache``: read-modify-write decode/prefill path
    (returns (out, new_cache)); without: self-attention over x itself.
    ``kv_override``: (k, v) for cross-attention (enc-dec).
    ``kv_bound``: STATIC upper bound on live cache positions — the decode
    read dequantizes/attends over only that prefix (bucketed decode).
    ``paged``: (pool, block_tables, lengths) page-pool state; the new token
    is scattered into its page and attention gathers live pages only.
    Returns (out, new_pool).
    A 4/5-tuple ``paged`` = (pool, block_tables, n_past, chunk_page_ids
    [, chunk_len]) is the CHUNKED-PREFILL path: x is a whole prompt chunk
    starting at page-aligned position ``n_past``; its K/V are quantized and
    scattered whole-page into ``chunk_page_ids``, and the chunk attends
    causally to itself plus every earlier page through the block table —
    prefix-hit pages are read (gather + dequant), never recomputed.
    ``chunk_len`` (B,) marks each row's valid tokens when the chunk axis is
    a padded bucket (batched engine tick); padded positions write the
    cache_init zero state and their attention rows are discarded by the
    caller."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    if kv_override is None:
        q, k, v = qdense_shared(x, [p["wq"], p["wk"], p["wv"]], rt, cb, tag="attn_qkv")
        q = q.reshape(b, s, cfg.n_heads, hd)
        k = k.reshape(b, s, cfg.n_kv_heads, hd)
        v = v.reshape(b, s, cfg.n_kv_heads, hd)
        if use_rope:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
    else:
        q = qdense(x, p["wq"], rt, cb, tag="attn_q").reshape(b, s, cfg.n_heads, hd)
        k, v = kv_override

    if paged is not None and len(paged) >= 4:
        pool, block_tables, n_past, chunk_page_ids = paged[:4]
        chunk_len = paged[4] if len(paged) == 5 else None
        new_pool = paged_chunk_write(
            pool, k, v, chunk_page_ids, rt.cache_kind, rt.bcq_cfg, cb,
            chunk_len=chunk_len,
        )
        if rt.paged_kernel and window is None:
            from repro.kernels.chunked_prefill import chunked_prefill

            out = chunked_prefill(
                q, new_pool, block_tables, n_past, rt.cache_kind, rt.bcq_cfg, cb
            ).astype(q.dtype)
        else:
            kf, vf = paged_gather_kv(
                new_pool, block_tables, rt.cache_kind, rt.bcq_cfg, cb, rt.compute_dtype
            )
            # gathered index j IS absolute position j, so the standard
            # causal mask (j <= position) gives prefix visibility, chunk
            # causality, and tail masking in one condition — identical
            # row-wise to what a full-prompt prefill computes.
            out = _attend_chunked(
                q, kf, vf, positions, (n_past + s).reshape(b, 1, 1, 1), causal,
                window, rt.attn_chunk, rt.unroll, rt.attn_f32,
            )
        out = qdense(out.reshape(b, s, cfg.n_heads * hd), p["wo"], rt, cb, tag="attn_out")
        return out, new_pool

    if paged is not None:
        pool, block_tables, lengths = paged
        ps = pool_page_size(pool)
        page_ids = block_tables[jnp.arange(b), lengths // ps]
        new_pool = paged_token_write(
            pool, k, v, page_ids, lengths % ps, rt.cache_kind, rt.bcq_cfg, cb
        )
        valid = lengths + s  # (B,) per-sequence live tokens incl. the new one
        if rt.paged_kernel and s == 1 and window is None:
            from repro.kernels.paged_attention import paged_attention

            out = paged_attention(
                q[:, 0], new_pool, block_tables, valid, rt.cache_kind, rt.bcq_cfg, cb
            ).astype(q.dtype)[:, None]
        else:
            kf, vf = paged_gather_kv(
                new_pool, block_tables, rt.cache_kind, rt.bcq_cfg, cb, rt.compute_dtype
            )
            out = _attend_chunked(
                q, kf, vf, positions, valid.reshape(b, 1, 1, 1), causal, window,
                rt.attn_chunk, rt.unroll, rt.attn_f32,
            )
        out = qdense(out.reshape(b, s, cfg.n_heads * hd), p["wo"], rt, cb, tag="attn_out")
        return out, new_pool

    new_cache = None
    if cache is not None:
        # per-row decode: cache_pos is a (B,) array of heterogeneous
        # absolute positions (paged state engine) — scatter row-wise and
        # bound validity per row; the math row i computes is identical to
        # a scalar-pos decode of that row alone at cache_pos[i].
        per_row = getattr(cache_pos, "ndim", 0) >= 1
        use_flash = (
            rt.flash_decode and rt.mesh is not None and s == 1
            and window is None and not per_row
        )
        if use_flash:
            new_cache = cache_write_sharded(cache, k, v, cache_pos, rt, cb)
        elif per_row:
            assert s == 1, "per-row cache_pos implies single-token decode"
            new_cache = cache_write_rows(cache, k, v, cache_pos, rt.cache_kind, rt.bcq_cfg, cb)
        else:
            new_cache = cache_write(cache, k, v, cache_pos, rt.cache_kind, rt.bcq_cfg, cb)
        kf, vf = cache_read(
            new_cache, rt.cache_kind, rt.bcq_cfg, cb, rt.compute_dtype,
            valid_len=None if use_flash else kv_bound,
        )
        valid = cache_pos + s
        if per_row:
            valid = valid.reshape(b, 1, 1, 1)
        out = None
        if use_flash:
            out = flash_decode_sharded(q, kf, vf, valid, rt)
        if out is None:
            out = _attend_chunked(q, kf, vf, positions, valid, causal, window, rt.attn_chunk, rt.unroll, rt.attn_f32)
    else:
        valid = k.shape[1]
        if rt.flash_kernel and causal and window is None and s == k.shape[1]:
            from repro.kernels.flash_attention import flash_attention

            out = flash_attention(q, k, v, causal=True).astype(q.dtype)
        else:
            out = _attend_chunked(q, k, v, positions, valid, causal, window, rt.attn_chunk, rt.unroll, rt.attn_f32)
    out = qdense(out.reshape(b, s, cfg.n_heads * hd), p["wo"], rt, cb, tag="attn_out")
    return out, new_cache


# ------------------------------------------------------------------- MLPs
def init_mlp(key, d_model, d_ff, act, rt: Runtime):
    ks = jax.random.split(key, 3)
    p = {"wi": init_qdense(ks[0], d_model, d_ff, rt), "wo": init_qdense(ks[1], d_ff, d_model, rt)}
    if act == "swiglu":
        p["wg"] = init_qdense(ks[2], d_model, d_ff, rt)
    return p


def mlp(x, p, act, rt: Runtime, cb):
    if act == "swiglu":
        h, g = qdense_shared(x, [p["wi"], p["wg"]], rt, cb, tag="mlp_in")
        h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
    else:
        h = qdense(x, p["wi"], rt, cb, tag="mlp_in")
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(h.dtype)
    return qdense(h, p["wo"], rt, cb, tag="mlp_out")
