"""End-to-end training driver: pjit train step, checkpoint/restart,
preemption hook, elastic resume, optional compressed-DP step.

CLI (CPU-scale example):
  PYTHONPATH=src python -m repro.launch.train --arch gpt3_126m --smoke \
      --steps 200 --batch 16 --seq 128 --ckpt /tmp/ck
Resuming after a kill restarts from the latest checkpoint automatically.
"""
from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import manager as ckpt_lib
from repro.configs.base import get_arch, get_smoke
from repro.data.pipeline import DataConfig, Prefetcher, eval_stream
from repro.launch import mesh as mesh_lib
from repro.models import zoo
from repro.models.layers import Runtime
from repro.optim import adamw
from repro.optim.compress import compress_grads_tree, init_error_state, make_compressed_psum
from repro.runtime.elastic import Watchdog, derive_mesh


def make_train_step(api, opt_cfg: adamw.AdamWConfig):
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(api.loss_fn)(params, batch)
        params, opt_state, metrics = adamw.apply_updates(params, grads, opt_state, opt_cfg)
        return params, opt_state, {"loss": loss, **metrics}

    return train_step


def make_compressed_dp_step(api, opt_cfg: adamw.AdamWConfig, mesh, axis: str = "data"):
    """Pure-DP variant with int8 error-feedback gradient all-reduce
    (the cross-pod DCN pattern; testable on any ≥2-device mesh)."""
    data_spec = P(axis)

    def step(params, opt_state, err, batch):
        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(P(), P(), P(), jax.tree.map(lambda _: data_spec, batch)),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        )
        def inner(p, s, e, b):
            loss, grads = jax.value_and_grad(api.loss_fn)(p, b)
            loss = jax.lax.pmean(loss, axis)
            from repro.optim.compress import compressed_allreduce_local

            flat_g, tdef = jax.tree.flatten(grads)
            flat_e = jax.tree.leaves(e)
            new_g, new_e = [], []
            for g, eb in zip(flat_g, flat_e):
                gg, ee = compressed_allreduce_local(g, eb, axis)
                new_g.append(gg)
                new_e.append(ee)
            grads = jax.tree.unflatten(tdef, new_g)
            e = jax.tree.unflatten(tdef, new_e)
            p, s, metrics = adamw.apply_updates(p, grads, s, opt_cfg)
            return p, s, e, {"loss": loss, **metrics}

        return inner(params, opt_state, err, batch)

    return step


def shardings_for(mesh, api, params_shapes):
    axes = mesh_lib.axis_sizes(mesh)
    pspecs = zoo.param_pspecs(params_shapes, axes)
    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                            is_leaf=lambda x: isinstance(x, P))
    opt_sh = {"m": param_sh, "v": param_sh, "step": NamedSharding(mesh, P())}
    return param_sh, opt_sh


def run(args):
    cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    rt = Runtime(
        quant_mode=args.quant,
        compute_dtype=jnp.float32 if args.smoke else jnp.bfloat16,
        param_dtype=jnp.float32,
        remat=args.remat,
    )
    api = zoo.build(cfg, rt)
    mesh = derive_mesh(model_parallel=args.model_parallel)
    axes = mesh_lib.axis_sizes(mesh)
    print(f"mesh={axes} arch={cfg.name} params≈{cfg.param_count()/1e6:.1f}M")

    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch, seed=args.seed)
    opt_cfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=args.warmup, total_steps=args.steps)
    train_step = make_train_step(api, opt_cfg)

    cm = ckpt_lib.CheckpointManager(args.ckpt, keep=2)
    restored = cm.restore() if args.resume else None
    if restored is not None:
        start_step, state = restored
        params = jax.tree.map(jnp.asarray, state["params"])
        opt_state = jax.tree.map(jnp.asarray, state["opt"])
        print(f"resumed from step {start_step}")
    else:
        start_step = 0
        params = api.init(jax.random.PRNGKey(args.seed))
        if rt.quant_mode != "none":
            from repro.core.calibrate import default_universal_codebooks

            params["codebooks"] = default_universal_codebooks(rt.bcq_cfg).as_jnp()
        opt_state = adamw.init_state(params)

    params_shapes = jax.eval_shape(lambda: params)
    param_sh, opt_sh = shardings_for(mesh, api, params_shapes)
    step_fn = jax.jit(
        train_step,
        in_shardings=(param_sh, opt_sh, None),
        out_shardings=(param_sh, opt_sh, None),
        donate_argnums=(0, 1),
    )

    # preemption: blocking snapshot on SIGTERM
    latest = {"step": start_step, "params": params, "opt": opt_state}
    ckpt_lib.install_sigterm_hook(
        lambda: cm.save(latest["step"], {"params": latest["params"], "opt": latest["opt"]}, blocking=True)
    )

    pf = Prefetcher(dcfg, start_step=start_step)
    it = iter(pf)
    t0 = time.time()
    losses = []
    wd = Watchdog(n_hosts=1)
    tokens_per_step = args.batch * args.seq
    model_flops_step = 6.0 * cfg.param_count() * tokens_per_step
    with mesh:
        for _ in range(start_step, args.steps):
            step, batch = next(it)
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            latest.update(step=step + 1, params=params, opt=opt_state)
            losses.append(float(metrics["loss"]))
            wd.beat(0, step)
            if (step + 1) % args.log_every == 0:
                dt = (time.time() - t0) / args.log_every
                t0 = time.time()
                stragglers = wd.stragglers()
                print(
                    f"step {step+1} loss {np.mean(losses[-args.log_every:]):.4f} "
                    f"gnorm {float(metrics['grad_norm']):.3f} lr {float(metrics['lr']):.2e} "
                    f"{dt*1e3:.0f} ms/step {tokens_per_step/dt:.0f} tok/s "
                    f"flops/step {model_flops_step:.2e}"
                    + (f" STRAGGLERS {stragglers}" if stragglers else "")
                )
            if (step + 1) % args.save_every == 0:
                cm.save(step + 1, {"params": params, "opt": opt_state})
    pf.close()
    cm.save(args.steps, {"params": params, "opt": opt_state}, blocking=True)

    # held-out eval
    ev = []
    for batch in eval_stream(dcfg, 4):
        ev.append(float(api.loss_fn(params, batch)))
    print(f"final train loss {np.mean(losses[-20:]):.4f} eval loss {np.mean(ev):.4f} ppl {np.exp(np.mean(ev)):.2f}")
    return params, np.mean(ev)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt3_126m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quant", default="none", choices=["none", "fake"])
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--resume", action="store_true", default=True)
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument("--save-every", type=int, default=50)
    run(ap.parse_args())


if __name__ == "__main__":
    main()
