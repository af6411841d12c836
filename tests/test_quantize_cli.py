"""Offline PTQ CLI: checkpoint → serving artifacts roundtrip."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import manager as ckpt_lib
from repro.configs.base import get_smoke
from repro.core.bcq import BCQConfig, CodebookSet
from repro.launch.quantize import quantize_checkpoint
from repro.models import zoo
from repro.models.layers import Runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RT = Runtime(quant_mode="none", compute_dtype=jnp.float32, param_dtype=jnp.float32)


def test_quantize_checkpoint_artifacts(tmp_path):
    cfg = get_smoke("gpt3_126m")
    api = zoo.build(cfg, RT)
    params = api.init(jax.random.PRNGKey(0))
    m = quantize_checkpoint(params, cfg, BCQConfig(), str(tmp_path))
    assert os.path.exists(tmp_path / "codebooks.json")
    assert os.path.exists(tmp_path / "weights_w4_fake.npz")
    assert os.path.exists(tmp_path / "weights_w4_packed.npz")
    assert m["compression_vs_bf16"] > 1.5
    cbs = CodebookSet.load(str(tmp_path / "codebooks.json"))
    assert cbs.levels.shape == (8, 16)
    # fake-quant artifact serves and is finite
    pq = ckpt_lib.load_pytree(str(tmp_path / "weights_w4_fake.npz"))
    pq = jax.tree.map(jnp.asarray, pq)
    api_q = zoo.build(cfg, Runtime(quant_mode="fake", compute_dtype=jnp.float32, param_dtype=jnp.float32))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab)
    lg, _ = api_q.prefill_fn(pq, {"tokens": toks}, 12)
    assert np.isfinite(np.asarray(lg, np.float32)).all()


def test_quantize_cli_end_to_end(tmp_path):
    env = dict(os.environ, PYTHONPATH="src")
    ck = tmp_path / "ck"
    out = tmp_path / "w4"
    r1 = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "gpt3_126m", "--smoke",
         "--steps", "5", "--batch", "2", "--seq", "32", "--ckpt", str(ck),
         "--save-every", "5", "--log-every", "5"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=400,
    )
    assert r1.returncode == 0, r1.stderr[-1500:]
    r2 = subprocess.run(
        [sys.executable, "-m", "repro.launch.quantize", "--ckpt", str(ck),
         "--arch", "gpt3_126m", "--smoke", "--out", str(out)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=400,
    )
    assert r2.returncode == 0, r2.stderr[-1500:]
    man = json.load(open(out / "manifest.json"))
    assert man["bcq"]["bits"] == 4.5
