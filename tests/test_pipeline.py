"""Pipeline parallelism (GPipe over a mesh axis) — correctness + AD."""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, n_dev=4, timeout=420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, env=env, cwd=REPO, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:{r.stdout[-1500:]}\nSTDERR:{r.stderr[-2500:]}"
    return r.stdout


def test_pipeline_matches_sequential_4dev():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.runtime.pipeline import pipeline_apply, bubble_fraction
        mesh = make_mesh((4,), ("pod",))
        S, D = 4, 16
        keys = jax.random.split(jax.random.PRNGKey(0), S)
        stage_params = {"w": jnp.stack([jax.random.normal(k, (D, D)) * 0.3 for k in keys])}
        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"])
        x = jax.random.normal(jax.random.PRNGKey(1), (8, D))
        # sequential reference
        ref = x
        for s in range(S):
            ref = stage_fn({"w": stage_params["w"][s]}, ref)
        with mesh:
            got = jax.jit(lambda p, v: pipeline_apply(stage_fn, p, v, mesh, "pod", n_micro=8))(stage_params, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)
        assert abs(bubble_fraction(4, 8) - 3/11) < 1e-9
        print("OK pipeline matches sequential")
    """)
    assert "OK pipeline" in out


def test_pipeline_differentiable_4dev():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.runtime.pipeline import pipeline_apply
        mesh = make_mesh((4,), ("pod",))
        S, D = 4, 8
        stage_params = {"w": jnp.stack([jnp.eye(D) * 0.9 for _ in range(S)])}
        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"])
        x = jax.random.normal(jax.random.PRNGKey(1), (4, D))
        def loss_pipe(p):
            with mesh:
                y = pipeline_apply(stage_fn, p, x, mesh, "pod", n_micro=4)
            return jnp.sum(y ** 2)
        def loss_seq(p):
            h = x
            for s in range(S):
                h = stage_fn({"w": p["w"][s]}, h)
            return jnp.sum(h ** 2)
        g1 = jax.jit(jax.grad(loss_pipe))(stage_params)
        g2 = jax.grad(loss_seq)(stage_params)
        np.testing.assert_allclose(np.asarray(g1["w"]), np.asarray(g2["w"]), rtol=1e-4, atol=1e-5)
        print("OK pipeline grads match")
    """)
    assert "OK pipeline grads" in out
