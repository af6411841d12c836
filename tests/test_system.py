"""End-to-end system tests: train → loss decreases; checkpoint kill/resume
determinism; PTQ serving pipeline (the paper's deployment flow)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import manager as ckpt_lib
from repro.configs.base import get_smoke
from repro.core import ptq
from repro.core.bcq import BCQConfig
from repro.core.calibrate import calibrate_from_model
from repro.data.pipeline import DataConfig, batch_at, eval_stream
from repro.launch.train import make_train_step
from repro.models import zoo
from repro.models.layers import Runtime
from repro.optim import adamw

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RT = Runtime(quant_mode="none", compute_dtype=jnp.float32, param_dtype=jnp.float32)


def _train(api, dcfg, steps, params=None, opt=None, start=0, lr=2e-3, total=None):
    step_fn = jax.jit(make_train_step(api, adamw.AdamWConfig(lr=lr, warmup_steps=10, total_steps=total or steps)))
    params = params if params is not None else api.init(jax.random.PRNGKey(0))
    opt = opt if opt is not None else adamw.init_state(params)
    losses = []
    for s in range(start, steps):
        params, opt, m = step_fn(params, opt, batch_at(dcfg, s))
        losses.append(float(m["loss"]))
    return params, opt, losses


def test_training_reduces_loss():
    cfg = get_smoke("gpt3_126m")
    api = zoo.build(cfg, RT)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8)
    _, _, losses = _train(api, dcfg, 60)
    assert np.mean(losses[:5]) - np.mean(losses[-5:]) > 0.5, losses[::10]


def test_checkpoint_resume_bitexact(tmp_path):
    """train 30 = train 15 + save + restore + train 15 (fault-tolerance
    contract: a restart is invisible to the training trajectory)."""
    cfg = get_smoke("gpt3_126m")
    api = zoo.build(cfg, RT)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=4)

    p_full, _, _ = _train(api, dcfg, 30)

    p_half, opt_half, _ = _train(api, dcfg, 15, total=30)
    cm = ckpt_lib.CheckpointManager(str(tmp_path))
    cm.save(15, {"params": p_half, "opt": opt_half}, blocking=True)
    step, state = cm.restore()
    assert step == 15
    p_r = jax.tree.map(jnp.asarray, state["params"])
    o_r = jax.tree.map(jnp.asarray, state["opt"])
    o_r["step"] = jnp.asarray(o_r["step"]).astype(jnp.int32).reshape(())
    p_resumed, _, _ = _train(api, dcfg, 30, params=p_r, opt=o_r, start=15)

    for a, b in zip(jax.tree.leaves(p_full), jax.tree.leaves(p_resumed)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_ptq_pipeline_ppl_close():
    """Paper pipeline: train → calibrate universal codebooks on ONE batch →
    PTQ (no weight updates) → W4A4 PPL within a small delta of bf16, and
    clearly better than INT4-per-tensor activations."""
    cfg = get_smoke("gpt3_126m")
    api = zoo.build(cfg, RT)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8)
    params, _, _ = _train(api, dcfg, 120)

    def ppl(a, p):
        return float(np.exp(np.mean([float(a.loss_fn(p, b)) for b in eval_stream(dcfg, 3)])))

    p_bf16 = ppl(api, params)
    bcq_cfg = BCQConfig()
    cbs = calibrate_from_model(params, batch_at(dcfg, 777)["tokens"][:2], cfg, RT, bcq_cfg, iters=8)
    pq = ptq.quantize_params(params, cbs.as_jnp(), bcq_cfg)
    pq["codebooks"] = cbs.as_jnp()
    api_q = zoo.build(cfg, Runtime(quant_mode="fake", bcq_cfg=bcq_cfg,
                                   compute_dtype=jnp.float32, param_dtype=jnp.float32))
    p_w4a4 = ppl(api_q, pq)
    assert p_w4a4 < p_bf16 * 1.10, (p_bf16, p_w4a4)
    api_int4 = zoo.build(cfg, Runtime(quant_mode="fake", bcq_cfg=bcq_cfg, act_format="int4",
                                      compute_dtype=jnp.float32, param_dtype=jnp.float32))
    assert p_w4a4 < ppl(api_int4, pq)


def test_train_cli_resume(tmp_path):
    """The real CLI: run 12 steps, then rerun to 30 → resumes from ckpt."""
    env = dict(os.environ, PYTHONPATH="src")
    base = [
        sys.executable, "-m", "repro.launch.train", "--arch", "gpt3_126m",
        "--smoke", "--batch", "2", "--seq", "32",
        "--save-every", "10", "--log-every", "10", "--ckpt", str(tmp_path),
    ]
    r1 = subprocess.run(base + ["--steps", "12"], capture_output=True, text=True,
                        env=env, cwd=REPO, timeout=500)
    assert r1.returncode == 0, r1.stderr[-2000:]
    r2 = subprocess.run(base + ["--steps", "30"], capture_output=True, text=True,
                        env=env, cwd=REPO, timeout=500)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed from step" in r2.stdout, r2.stdout
