"""Mixture-of-Experts block: top-k routing, capacity-bounded sort-based
dispatch (no T×E×C one-hot tensors), expert-parallel einsums.

Experts live on the 'model' mesh axis (EP); the gather/scatter pair between
token-sharded activations and expert-sharded FFNs is where XLA inserts the
all-to-alls.  The router is deliberately *not* quantized (accuracy-critical,
negligible FLOPs — DESIGN.md §5); expert GEMMs follow rt.quant_mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bcq
from repro.models import layers
from repro.models.layers import Runtime, init_dense


def init_moe(key, cfg, rt: Runtime):
    m = cfg.moe
    ks = jax.random.split(key, 4)
    d, f, e = cfg.d_model, m.d_ff_expert, m.n_experts

    def expert_kernels(k, d_in, d_out):
        if rt.quant_mode == "packed":
            shp = layers.packed_weight_shapes(d_in, d_out, rt.bcq_cfg)
            return {
                "kernel_packed": {
                    n: jnp.zeros((e,) + s.shape if n != "s_x" else (e,), s.dtype)
                    for n, s in shp.items()
                }
            }
        return {"kernel": layers.uinit(k, (e, d_in, d_out), scale=d_in**-0.5, dtype=rt.param_dtype)}

    return {
        "router": init_dense(ks[0], d, e, dtype=jnp.float32),
        "wi": expert_kernels(ks[1], d, f),
        "wg": expert_kernels(ks[2], d, f),
        "wo": expert_kernels(ks[3], f, d),
    }


def _expert_matmul(xe, wp, rt: Runtime, cb, tag=None):
    """xe: (E, C, K) tokens per expert; weight (E, K, N) → (E, C, N).
    ``tag`` names the site for the opt-in quant-error probe (stats pool
    every expert's tokens, matching the shared per-tensor s_X)."""
    layers._emit_quant_probe(xe, rt, cb, tag)
    dt = rt.compute_dtype
    if rt.quant_mode == "none" or cb is None:
        return jnp.einsum("eck,ekn->ecn", xe.astype(dt), wp["kernel"].astype(dt))
    if rt.quant_mode == "fake":
        xq = layers._quantize_act(xe.astype(jnp.float32), rt, cb).astype(dt)
        return jnp.einsum("eck,ekn->ecn", xq, wp["kernel"].astype(dt))
    if rt.quant_mode == "fake_full":
        xq = bcq.fake_quant(xe.astype(jnp.float32), cb, rt.bcq_cfg).astype(dt)
        wt = jnp.swapaxes(wp["kernel"], -1, -2).astype(jnp.float32)  # (E, N, K)
        wq = bcq.fake_quant(wt, cb, rt.bcq_cfg).astype(dt)
        return jnp.einsum("eck,enk->ecn", xq, wq)
    if rt.quant_mode == "packed":
        # one fused W4A4 linear per expert; s_X stays the per-tensor
        # reduction over ALL experts' tokens, as fake_quant(xe) takes it
        s_x = bcq.tensor_scale(xe.astype(jnp.float32), rt.bcq_cfg)
        pks = wp["kernel_packed"]
        outs = [
            layers.fused_packed_linear(
                xe[e], jax.tree.map(lambda v: v[e], pks), rt, cb, s_x=s_x
            )
            for e in range(xe.shape[0])
        ]
        return jnp.stack(outs).astype(dt)
    raise ValueError(rt.quant_mode)


def moe_ffn(x, p, cfg, rt: Runtime, cb):
    """x: (B, S, D) → (out (B, S, D), aux_loss scalar)."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = m.n_experts, m.top_k
    xt = x.reshape(t, d)

    logits = xt.astype(jnp.float32) @ p["router"]["kernel"]  # (T, E) — bf16-free
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_ids = jax.lax.top_k(probs, k)  # (T, K)
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch-style)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(expert_ids[:, 0], e, dtype=jnp.float32), axis=0
    )
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)

    cap = int(m.capacity_factor * t * k / e) + 1

    # rank of each (token, slot) pair within its expert via one stable sort
    flat_e = expert_ids.reshape(-1)  # (T·K,)
    tk = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    grp_start = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    rank_sorted = jnp.arange(tk) - grp_start[sorted_e]
    rank = jnp.zeros((tk,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    keep = rank < cap
    slot = jnp.where(keep, rank, cap)  # overflow → trash column

    tok_of_pair = jnp.arange(tk, dtype=jnp.int32) // k
    table = jnp.full((e, cap + 1), t, jnp.int32).at[flat_e, slot].set(tok_of_pair)
    idx_ec = table[:, :cap]  # (E, C) token ids, t = padding row

    xpad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
    xe = xpad[idx_ec]  # (E, C, D) — gather across the data↔model axes (A2A)

    h = _expert_matmul(xe, p["wi"], rt, cb, tag="moe_wi")
    g = _expert_matmul(xe, p["wg"], rt, cb, tag="moe_wg")
    h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
    ye = _expert_matmul(h, p["wo"], rt, cb, tag="moe_wo")  # (E, C, D)

    # combine: gather each pair's output and scatter-add into tokens
    # (dropped pairs read a clipped slot but are zeroed by ``keep``)
    contrib = ye[flat_e, jnp.minimum(slot, cap - 1)]  # (T·K, D)
    w_pair = (gate.reshape(-1) * keep.astype(jnp.float32)).astype(contrib.dtype)
    contrib = contrib * w_pair[:, None]
    out = jnp.zeros((t, d), contrib.dtype).at[tok_of_pair].add(contrib)
    return out.reshape(b, s, d).astype(x.dtype), aux
