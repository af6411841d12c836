"""The system under test, built from a configuration file.

A configuration file (``configs/<name>.json``) holds the model's sizes at
its top level, the ``runtime`` switches and the ``engine`` settings.  This
module turns it into the program's model API, makes the served weights on
the device from the seed in one jitted call, builds the engine, and warms
up every step program the engine can launch.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ARCH_KEYS = (
    "name", "family", "n_layers", "d_model", "n_heads", "n_kv_heads",
    "d_head", "d_ff", "vocab", "qkv_bias", "rope_theta", "act", "norm",
    "tie_embeddings",
)

# E4M3 codes of the per-array scale ratio drawn for the seeded weights:
# 56..63 decode to 1.0, 1.125, ..., 1.875 (exponent 0, every mantissa).
RATIO_CODES = (56, 64)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def codebooks() -> np.ndarray:
    """(N_c, 2^B) float32 levels of the benchmark's copy of the codebooks."""
    return np.asarray(load_json(os.path.join(HERE, "codebooks.json"))["levels"], np.float32)


def seed_words(seed: int, stream: int) -> np.ndarray:
    """Two uint32 words from any non-negative seed (wider than 32 bits
    included) and a stream number: the key material of every draw."""
    return np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)


def build_api(cfg: dict):
    import jax.numpy as jnp

    from repro.configs.base import ArchConfig
    from repro.core.bcq import BCQConfig
    from repro.models import zoo
    from repro.models.layers import Runtime

    arch = ArchConfig(**{k: cfg[k] for k in ARCH_KEYS if k in cfg})
    r = dict(cfg["runtime"])
    for k in ("compute_dtype", "param_dtype"):
        r[k] = jnp.dtype(r[k])
    fmt = load_json(os.path.join(HERE, "codebooks.json"))["format"]
    return zoo.build(arch, Runtime(bcq_cfg=BCQConfig(**fmt), **r))


def make_params(api, cfg: dict, seed: int):
    """The served parameter tree, made on the device in one jitted call.

    The tree's structure, shapes and dtypes are the model's own packed
    layout (``api.init`` traced abstractly).  Every packed weight gets
    seeded 4-bit codes, 3-bit selectors (two per byte), E4M3 array-scale
    codes in [1, 1.875] and a per-tensor ``s_x`` that gives the decoded
    weight an RMS of 1/sqrt(K); norms, biases, the embedding and an untied
    LM head get seeded values in the parameter dtype."""
    import jax
    import jax.numpy as jnp

    cb = codebooks()
    n_c = cb.shape[0]
    ratios = np.array([1.0 + m / 8 for m in range(8)])
    gain = float(np.sqrt(np.mean(cb**2)) * np.sqrt(np.mean(1.0 / ratios**2)))
    shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))

    def packed(key, sd: dict):
        k_idx, k_sel, k_sc = jax.random.split(key, 3)
        lo_hi = jax.random.randint(k_sel, sd["sel"].shape + (2,), 0, n_c, jnp.int32)
        k_in = 2 * sd["idx"].shape[-1]
        return {
            "idx": jax.random.bits(k_idx, sd["idx"].shape, jnp.uint8),
            "sel": (lo_hi[..., 0] | (lo_hi[..., 1] << 4)).astype(jnp.uint8),
            "scale": jax.random.randint(k_sc, sd["scale"].shape, *RATIO_CODES, jnp.int32).astype(jnp.uint8),
            "s_x": jnp.full(sd["s_x"].shape, gain * np.sqrt(k_in), jnp.float32),
        }

    def leaf(key, name: str, sd):
        z = jax.random.normal(key, sd.shape, jnp.float32)
        if name == "scale":  # norm gain
            return (1.0 + 0.1 * z).astype(sd.dtype)
        return (0.02 * z).astype(sd.dtype)  # embed, lm_head, biases, norm shifts

    def walk(key, tree):
        out = {}
        for i, (name, sub) in enumerate(sorted(tree.items())):
            k = jax.random.fold_in(key, i)
            if name == "codebooks":
                out[name] = jnp.asarray(cb)
            elif name == "kernel_packed":
                out[name] = packed(k, sub)
            elif isinstance(sub, dict):
                out[name] = walk(k, sub)
            else:
                out[name] = leaf(k, name, sub)
        return out

    key = jax.random.wrap_key_data(jnp.asarray(seed_words(seed, 1), jnp.uint32))
    return jax.jit(lambda k: walk(k, shapes))(key)


def build_engine(api, params, cfg: dict, telemetry=None):
    from repro.serving.engine import PagedEngine

    e = dict(cfg["engine"])
    return PagedEngine(api, params, eos_id=-1, telemetry=telemetry, **e)


def pow2_upto(n: int) -> list[int]:
    out, b = [], 1
    while b < n:
        out.append(b)
        b *= 2
    return out + [n]


def step_shapes(engine, prefill_batches) -> list[tuple[str, object, tuple]]:
    """The (program, arguments) the engine launches while serving a
    traffic mix: the decode tick, and the chunk tick at each chunk bucket
    (the full chunk and the power-of-two ragged tails) × each prefill batch
    bucket the mix reaches.  Each row writes pages of its own, as rows do
    when serving."""
    import jax.numpy as jnp

    w = engine.tables.shape[1]
    n_free = engine.pool_mgr.n_pages - 1
    dec = np.zeros((engine.n_slots, 3 + w), np.int32)
    dec[:, 3] = 1 + np.arange(engine.n_slots) % n_free
    out = [("decode", engine._decode, (engine.params, engine.pool, jnp.asarray(dec), engine._chain_tok))]
    ps, chunk = engine.ps, engine.prefill_chunk
    for c in pow2_upto(chunk):
        n_cp = -(-c // ps)
        fn = engine._chunk_fn(c, n_cp)
        for bb in prefill_batches:
            packed = np.zeros((bb, c + 2 + n_cp + w), np.int32)
            ids = 1 + np.arange(bb * n_cp).reshape(bb, n_cp) % n_free
            packed[:, c + 1 : c + 1 + n_cp] = ids
            packed[:, c + 1 + n_cp] = c
            packed[:, c + 2 + n_cp : c + 2 + 2 * n_cp] = ids
            out.append((f"chunk c={c} b={bb}", fn, (engine.params, engine.pool, jnp.asarray(packed))))
    return out


def warm_up(engine, vocab_p: int, prefill_batches, log=print) -> dict:
    """Run every step program the mix reaches once (compiling it, or
    loading it from the persistent compilation cache), one at a time so
    that no two compiles contend for the cache's lock; then the eager
    per-row logits slices and the row-stats program of prefill completion
    for every prefill batch bucket."""
    import time

    import jax
    import jax.numpy as jnp

    shapes = step_shapes(engine, prefill_batches)
    t0 = time.perf_counter()
    for _, fn, args in shapes:
        jax.block_until_ready(fn(*args))
    t2 = time.perf_counter()
    dt = jnp.dtype(engine.api.rt.compute_dtype)
    for bb in prefill_batches:
        logits = jnp.zeros((bb, 1, vocab_p), dt)
        for r in range(bb):
            engine._row_stats(logits[r : r + 1])
    t3 = time.perf_counter()
    out = {"programs": len(shapes), "programs_s": t2 - t0, "slices_s": t3 - t2}
    log(f"warm-up {out}")
    return out
