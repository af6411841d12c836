"""Multi-device tests via subprocess (XLA_FLAGS host-device override):
pjit sharded training, compressed-DP step, elastic mesh, and a real
dry-run cell on the production 512-device mesh."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, n_dev: int = 8, timeout: int = 540):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=timeout,
    )
    assert r.returncode == 0, f"STDOUT:{r.stdout[-2000:]}\nSTDERR:{r.stderr[-3000:]}"
    return r.stdout


def test_pjit_train_step_8dev():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import get_smoke
        from repro.models import zoo
        from repro.models.layers import Runtime
        from repro.optim import adamw
        from repro.launch.train import make_train_step
        from repro.data.pipeline import DataConfig, batch_at
        assert len(jax.devices()) == 8
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_smoke("gpt3_126m")
        rt = Runtime(quant_mode="none", compute_dtype=jnp.float32, param_dtype=jnp.float32)
        api = zoo.build(cfg, rt)
        params = api.init(jax.random.PRNGKey(0))
        opt = adamw.init_state(params)
        shapes = jax.eval_shape(lambda: params)
        pspecs = zoo.param_pspecs(shapes, {"data": 4, "model": 2})
        psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                           is_leaf=lambda x: isinstance(x, P))
        osh = {"m": psh, "v": psh, "step": NamedSharding(mesh, P())}
        fn = jax.jit(make_train_step(api, adamw.AdamWConfig(lr=1e-3)),
                     in_shardings=(psh, osh, None), out_shardings=(psh, osh, None))
        dcfg = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8)
        with mesh:
            l0 = None
            for s in range(8):
                params, opt, m = fn(params, opt, batch_at(dcfg, s))
                l0 = l0 or float(m["loss"])
        # sharded result == single-device result
        api2 = zoo.build(cfg, rt)
        p2 = api2.init(jax.random.PRNGKey(0))
        o2 = adamw.init_state(p2)
        f2 = jax.jit(make_train_step(api2, adamw.AdamWConfig(lr=1e-3)))
        for s in range(8):
            p2, o2, m2 = f2(p2, o2, batch_at(dcfg, s))
        np.testing.assert_allclose(float(m["loss"]), float(m2["loss"]), rtol=1e-3)
        print("OK sharded==single loss", float(m["loss"]))
    """)
    assert "OK sharded==single" in out


def test_compressed_dp_step_8dev():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs.base import get_smoke
        from repro.models import zoo
        from repro.models.layers import Runtime
        from repro.optim import adamw
        from repro.optim.compress import init_error_state
        from repro.launch.train import make_compressed_dp_step
        from repro.data.pipeline import DataConfig, batch_at
        mesh = make_mesh((8,), ("data",))
        cfg = get_smoke("gpt3_126m")
        rt = Runtime(quant_mode="none", compute_dtype=jnp.float32, param_dtype=jnp.float32)
        api = zoo.build(cfg, rt)
        params = api.init(jax.random.PRNGKey(0))
        opt = adamw.init_state(params)
        err = init_error_state(params)
        step = jax.jit(make_compressed_dp_step(api, adamw.AdamWConfig(lr=1e-3), mesh))
        dcfg = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8)
        losses = []
        with mesh:
            for s in range(10):
                params, opt, err, m = step(params, opt, err, batch_at(dcfg, s))
                losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses
        print("OK compressed-DP loss", losses[0], "->", losses[-1])
    """)
    assert "OK compressed-DP" in out


def test_sharded_decode_8dev():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import get_smoke
        from repro.models import zoo
        from repro.models.layers import Runtime
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_smoke("qwen1_5_32b")
        rt = Runtime(quant_mode="none", compute_dtype=jnp.float32, param_dtype=jnp.float32)
        api = zoo.build(cfg, rt)
        params = api.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)
        lg_ref, caches = api.prefill_fn(params, {"tokens": toks}, 24)
        lg2_ref, _ = api.decode_fn(params, caches, toks[:, :1], jnp.int32(16))
        with mesh:
            lg, caches = jax.jit(lambda p, b: api.prefill_fn(p, b, 24))(params, {"tokens": toks})
            lg2, _ = jax.jit(api.decode_fn)(params, caches, toks[:, :1], jnp.int32(16))
        np.testing.assert_allclose(np.asarray(lg2), np.asarray(lg2_ref), rtol=5e-3, atol=5e-3)
        print("OK sharded decode matches")
    """)
    assert "OK sharded decode" in out


def test_elastic_mesh_shrink():
    """Mesh re-derivation for a 'failed node' count (6 of 8 devices)."""
    out = _run("""
        import jax
        from repro.runtime.elastic import derive_mesh
        m8 = derive_mesh(model_parallel=4)
        assert m8.devices.size == 8 and dict(zip(m8.axis_names, m8.devices.shape)) == {"data": 2, "model": 4}
        m6 = derive_mesh(n_devices=6, model_parallel=4)  # 4 doesn't divide 6 → mp degrades
        assert m6.devices.size == 6, m6
        print("OK elastic", m6.axis_names, m6.devices.shape)
    """)
    assert "OK elastic" in out


@pytest.mark.slow
def test_dryrun_one_cell_512dev():
    """The real deliverable path: production (16,16) mesh, one decode cell."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "whisper_base",
         "--shape", "decode_32k", "--mesh", "single", "--no-unroll"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=560,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert '"status": "ok"' in r.stdout


def test_flash_decode_matches_gathered_8dev():
    """Sequence-sharded shard_map decode == reference attention decode."""
    out = _run("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs.base import get_smoke
        from repro.models import zoo
        from repro.models.layers import Runtime
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_smoke("qwen1_5_32b")
        rt0 = Runtime(quant_mode="none", compute_dtype=jnp.float32, param_dtype=jnp.float32)
        rt1 = dataclasses.replace(rt0, flash_decode=True, mesh=mesh)
        api0, api1 = zoo.build(cfg, rt0), zoo.build(cfg, rt1)
        params = api0.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)
        lg0, c0 = api0.prefill_fn(params, {"tokens": toks}, 24)
        r0, _ = api0.decode_fn(params, c0, toks[:, :1], jnp.int32(16))
        with mesh:
            lg1, c1 = jax.jit(lambda p, b: api1.prefill_fn(p, b, 24))(params, {"tokens": toks})
            r1, _ = jax.jit(api1.decode_fn)(params, c1, toks[:, :1], jnp.int32(16))
        np.testing.assert_allclose(np.asarray(r1), np.asarray(r0), rtol=5e-3, atol=5e-3)
        print("OK flash decode matches")
    """)
    assert "OK flash decode" in out
