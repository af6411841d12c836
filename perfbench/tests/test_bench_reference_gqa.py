"""The float32 reference against the main serving path at StarCoder2's
head ratio: 12 query heads over one KV head of 64 (StarCoder2-3B: 24
over 2 of 128), so each KV head serves a head-major block of 12 query
rows per token in the page-gather kernel and the reference repeats K/V
12 times.  Otherwise as ``test_bench_reference.py``, whose smoke model it
reuses: prefill plus cached decode through ``PagedEngine`` agrees with
the reference's full forward within the limit, and the 3-bit control
fails it.

Smoke-size readings (CPU, seeds 1-8 drawn as in the fixture, 48 tokens
each): the served gap share is 0 on five seeds, 0.0208 (one token) on
two and 0.0417 (two) on one; the control's 0.0833-0.2708.  The limit
lets two tokens in 48 pass and fails three: between the two readings,
where the MHA model's 0.04 would fail the sound seed 6."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(os.path.abspath(__file__))]

from harness import correct, model, reference  # noqa: E402
from test_bench_reference import FMT, TINY  # noqa: E402

GQA12 = dict(TINY, name="tiny-gqa12", n_heads=12, n_kv_heads=1, d_head=64,
             correct=dict(TINY["correct"], limit_gap_share=0.06))


@pytest.fixture(scope="module")
def served():
    from repro.serving.generate import Request

    seed = 3
    api = model.build_api(GQA12)
    params = model.make_params(api, GQA12, seed)
    engine = model.build_engine(api, params, GQA12)
    rng = np.random.default_rng(seed)
    reqs = [
        Request(rid=i, prompt=rng.integers(0, GQA12["vocab"], int(n)).astype(np.int32), max_new=11)
        for i, n in enumerate(rng.integers(20, 90, 4))
    ]
    for r in reqs:
        engine.submit(r)
    engine.run_to_completion()
    rs = [reference.token_readings(params, GQA12, FMT, r.prompt, r.out, control=True) for r in reqs]
    return reqs, {k: np.concatenate([x[k] for x in rs]) for k in rs[0]}


def test_gqa12_main_path_agrees_with_the_reference(served):
    reqs, rd = served
    assert all(len(r.out) == 12 for r in reqs)
    assert rd["gap"].min() >= 0 and np.all(rd["margin"] >= 0)
    lim = GQA12["correct"]
    assert correct.gap_share(rd["gap"], rd["spread"], lim["gap_over_spread"]) <= lim["limit_gap_share"], rd


def test_gqa12_lower_precision_fails_the_limit(served):
    _, rd = served
    lim = GQA12["correct"]
    assert correct.gap_share(rd["control_gap"], rd["spread"], lim["gap_over_spread"]) > lim["limit_gap_share"]
