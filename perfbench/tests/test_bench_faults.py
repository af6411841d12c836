"""A whole run with the timed path broken underneath must come out not
correct.  The run skips the harness's look for a chip and drives the rest
(weights, engine, pre-roll, window, reference check) on the CPU at smoke
size, once sound and once with each fault a served cell can have: a
token altered where the decode step produces it, the decode step
returning its page pool unchanged (KV writes dropped), and half of the
decode batch left out (the upper half of the live rows repeat their
input token).  The control run (``control=True``: the reference at
3-bit judged in the served tokens' place) must come out not correct
too."""
import importlib.util
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import cells, model  # noqa: E402
from test_bench_reference import TINY  # noqa: E402

# One prompt length, so that the run launches four step programs only
# (decode, and a 16-token chunk for one, two or four rows), and a load
# that keeps every slot busy so that a fault in half of the decode rows
# reaches the sample.
MIX = {
    "rate_per_s": 3.0, "preroll_s": 10.0,
    "prompt": {"median": 16, "sigma": 0.0, "min": 16, "max": 16},
    "output": {"median": 8, "sigma": 0.2, "min": 6, "max": 10},
    "warm_prefill_batches": [1, 2, 4],
}


class CpuDevice:
    platform, device_kind = "cpu", "cpu"

    def memory_stats(self):
        return None


def load_run():
    spec = importlib.util.spec_from_file_location("perfbench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def broken_decode(fault):
    import jax.numpy as jnp

    from repro.serving import engine as engine_mod

    make = engine_mod._make_fused_decode

    def wrap(fn, guard):
        fused = make(fn, guard)

        def step(params, pool, packed, chain_tok):
            logits, nxt, fin, new_pool = fused(params, pool, packed, chain_tok)
            if fault == "token":
                nxt = (nxt + 1) % logits.shape[-1]
            elif fault == "state":
                new_pool = pool
            elif fault == "half_batch":  # the upper half of the live rows
                tok = jnp.where(packed[:, 1] == 1, packed[:, 0], chain_tok)
                live = packed[:, 2] > 0
                rank = jnp.cumsum(live) - 1
                nxt = jnp.where(live & (rank >= jnp.sum(live) // 2), tok, nxt)
            return logits, nxt, fin, new_pool

        return step

    return wrap


@pytest.mark.parametrize("fault", [None, "token", "state", "half_batch", "control"])
def test_run_is_correct_only_when_sound(fault, monkeypatch):
    from repro.serving import engine as engine_mod

    run = load_run()
    if fault not in (None, "control"):
        monkeypatch.setattr(engine_mod, "_make_fused_decode", broken_decode(fault))
    # warm up only the chunk bucket of the mix's one prompt length, so the
    # set-up compiles the four step programs and the pre-roll serves
    monkeypatch.setattr(model, "pow2_upto", lambda n: [MIX["prompt"]["median"]])
    cfg = dict(TINY, correct=dict(TINY["correct"], sample_tokens=40))
    b = cells.benchmark()
    cell = {"workload": {"name": "tiny", "chips": 1}, "config": cfg, "traffic": MIX,
            "end_to_end": b["end_to_end"], "per_layer": b["per_layer"]}
    res = run.run_cell(cell, 2**33 + 17, 4.0, False, CpuDevice(), control=fault == "control")
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(res)[-1] == "compared"
    assert res["correct"] is (fault is None), res["compared"]
    if fault is None:
        assert set(res["metrics"]) == {m["name"] for m in b["end_to_end"]}
