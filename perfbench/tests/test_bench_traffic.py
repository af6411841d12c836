"""The arrival and length generator: deterministic per seed, the same
arrivals and sizes for every seed, the stated medians and clips."""
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import traffic  # noqa: E402

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")) if f.endswith(".json"))


def mix(name):
    return json.load(open(os.path.join(BENCH, "traffic", f"{name}.json")))


@pytest.mark.parametrize("name", MIXES)
def test_deterministic_per_seed(name):
    a = traffic.schedule(mix(name), 2**33 + 5, 30, 50000)
    b = traffic.schedule(mix(name), 2**33 + 5, 30, 50000)
    c = traffic.schedule(mix(name), 7, 30, 50000)
    assert [(x.due, x.n_out, x.prompt.tolist()) for x in a] == [(x.due, x.n_out, x.prompt.tolist()) for x in b]
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in c]


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    m = mix(name)
    runs = [traffic.schedule(m, s, 30, 50000) for s in (1, 2, 3**20)]
    for phase, length in (("preroll", m["preroll_s"]), ("window", 30)):
        start = 0.0 if phase == "preroll" else m["preroll_s"]
        sizes = [[(len(x.prompt), x.n_out) for x in r if x.phase == phase] for r in runs]
        dues = [[x.due for x in r if x.phase == phase] for r in runs]
        n = len(sizes[0])
        assert n == int(np.ceil(m["rate_per_s"] * length))
        # every seed replays one trace: the same due times and sizes, in one order
        assert sizes[0] == sizes[1] == sizes[2]
        assert dues[0] == dues[1] == dues[2]
        due = np.array(dues[0])
        assert due[0] == start and np.all(np.diff(due) > 0) and due[-1] < start + length
        # the trace is a draw of the process, not a grid: its gaps and sizes vary
        if n > 3:
            assert len(set(sizes[0])) > 1 and np.ptp(np.diff(due)) > 0
        # the seed still draws what is served: the prompts' token ids
        ids = [[x.prompt.tolist() for x in r if x.phase == phase] for r in runs]
        assert ids[0] != ids[1] != ids[2]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_follow_the_stated_distribution(name):
    m = mix(name)
    rng = np.random.default_rng(0)
    for key in ("prompt", "output"):
        spec = m[key]
        x = traffic.lognormal_lengths(spec, 20000, rng)
        assert x.min() >= spec["min"] and x.max() <= spec["max"]
        assert abs(np.median(x) - spec["median"]) <= 0.03 * spec["median"]
        clipped = np.exp(np.log(spec["median"]) + spec["sigma"] * rng.standard_normal(20000))
        assert np.mean(x == spec["max"]) == pytest.approx(np.mean(clipped >= spec["max"] - 0.5), abs=0.01)


def test_prompt_ids_are_in_the_vocabulary():
    for x in traffic.schedule(mix(MIXES[0]), 11, 10, 321):
        assert x.prompt.dtype == np.int32 and x.prompt.min() >= 0 and x.prompt.max() < 321
