"""GPipe-style pipeline parallelism over a mesh axis (cross-pod option).

At multi-pod scale the 'pod' axis rides DCN; instead of data-parallel
gradient all-reduce (the default) a pipeline keeps only activations on
DCN.  This module implements the schedule with shard_map + ppermute:

* the layer stack is split into ``n_stages`` contiguous stages, stage s
  living on pod s (stage-stacked params sharded over the axis),
* a microbatched loop runs the classic GPipe fill/steady/drain schedule:
  at tick t, stage s processes microbatch (t - s) and ppermutes its output
  to stage s+1.

``pipeline_apply`` is differentiable (jax AD through ppermute/scan), so it
drops into the training step.  Bubble fraction = (S-1)/(T+S-1) — choose
microbatches T ≫ stages S.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x,
    mesh,
    axis: str = "pod",
    n_micro: int | None = None,
):
    """Run ``x`` through ``n_stages`` pipelined stages.

    stage_fn(params_stage, x_micro) -> y_micro — one stage's computation.
    stage_params: pytree stacked on leading stage axis (sharded over
    ``axis``).  x: (B, ...) global batch; split into ``n_micro``
    microbatches (default = n_stages).  Returns y with x's shape.
    """
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_stages = axes[axis]
    b = x.shape[0]
    n_micro = n_micro or n_stages
    assert b % n_micro == 0
    mb = b // n_micro
    ticks = n_micro + n_stages - 1

    x_micro = x.reshape(n_micro, mb, *x.shape[1:])

    p_stage_spec = jax.tree.map(lambda _: P(axis), stage_params)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(p_stage_spec, P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
    def run(params_local, xs_local):
        # params_local: stage slice (leading dim 1); xs_local: this shard's
        # share of microbatches — stage 0 feeds the pipe, others get zeros.
        params_me = jax.tree.map(lambda a: a[0], params_local)
        stage_id = jax.lax.axis_index(axis)
        n_local = xs_local.shape[0]  # n_micro / n_stages per shard

        # gather all microbatches to stage 0's input stream conceptually:
        # we instead index the local buffer when (tick - 0) belongs to us.
        # For simplicity every shard holds the SAME full microbatch stream
        # (replicated input path), stage 0 selects micro t at tick t.
        xs_all = jax.lax.all_gather(xs_local, axis, tiled=True)  # (n_micro, mb, ...)

        carry0 = jnp.zeros(xs_all.shape[1:], xs_all.dtype)
        outs0 = jnp.zeros((n_micro,) + xs_all.shape[1:], xs_all.dtype)

        def tick(state, t):
            inflight, outs = state
            # stage 0 ingests microbatch t (if valid); others use inflight
            take = jnp.clip(t, 0, n_micro - 1)
            x_in = jnp.where(
                (stage_id == 0)
                & (t < n_micro),
                xs_all[take],
                inflight,
            )
            y = stage_fn(params_me, x_in)
            # pass to next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            nxt = jax.lax.ppermute(y, axis, perm)
            # last stage emits microbatch (t - (S-1)) at this tick
            emit_idx = t - (n_stages - 1)
            valid = (emit_idx >= 0) & (emit_idx < n_micro)
            outs = jax.lax.cond(
                valid & (stage_id == n_stages - 1),
                lambda o: o.at[jnp.clip(emit_idx, 0, n_micro - 1)].set(y),
                lambda o: o,
                outs,
            )
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(tick, (carry0, outs0), jnp.arange(ticks))
        # broadcast final outputs from the last stage to all shards, then
        # return this shard's slice of the microbatch stream
        outs = jax.lax.psum(
            jnp.where(stage_id == n_stages - 1, outs, jnp.zeros_like(outs)), axis
        )
        k = n_micro // n_stages
        return jax.lax.dynamic_slice_in_dim(outs, stage_id * k, k, 0)

    y = run(stage_params, x_micro)
    return y.reshape(b, *x.shape[1:])


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
