"""The one traffic generator: open-loop Poisson arrivals with lognormal
prompt and output lengths, read from a mix's data file
(``traffic/<mix>.json``).

Steadiness across seeds: each phase (the pre-roll, then the measured
window) has a fixed number of requests, ``ceil(rate · seconds)``, whose
inter-arrival gaps and (prompt, output) lengths are one draw of the
process from the mix's own fixed stream, the gaps scaled so that they sum
to the phase's length.  Every seed replays that same trace: the same due
times and the same sizes in the same order.  ``--seed`` draws the prompt
token ids (and, in the harness, the weights), which change what is served
but not how long it takes.  An order of its own per seed would move a
tail taken over a few requests by a third from seed to seed, while two
runs of one order agree to a fraction of a percent.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

FIXED_STREAM = 7  # the mix's own stream: same sizes for every seed


@dataclasses.dataclass
class Arrival:
    rid: int
    due: float  # seconds after the pre-roll starts
    prompt: np.ndarray  # int32 token ids
    n_out: int  # output tokens the request asks for (eos is off)
    phase: str  # "preroll" | "window"


def lognormal_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths, lognormal with the given median and sigma,
    rounded and clipped to [min, max]."""
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def phase(mix: dict, seconds: float, stream: int):
    """(gaps, prompt lengths, output lengths) of one phase."""
    n = max(1, math.ceil(mix["rate_per_s"] * seconds))
    fixed = np.random.default_rng([FIXED_STREAM, stream])
    gaps = fixed.exponential(1.0 / mix["rate_per_s"], n)
    gaps *= seconds / gaps.sum()
    plen, olen = lognormal_lengths(mix["prompt"], n, fixed), lognormal_lengths(mix["output"], n, fixed)
    return gaps, plen, olen


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list[Arrival]:
    """Every request of a run: the pre-roll's, due in [0, preroll_s), then
    the window's, due in [preroll_s, preroll_s + seconds)."""
    out: list[Arrival] = []
    tok = np.random.default_rng([int(seed), 3])
    start = 0.0
    for stream, (name, length) in enumerate(
        (("preroll", mix["preroll_s"]), ("window", seconds)), start=1
    ):
        gaps, plen, olen = phase(mix, length, stream)
        # the first request of a phase is due at its start; each gap
        # separates it from the next, and the last gap runs to the end
        due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        for d, p, o in zip(due, plen, olen):
            prompt = tok.integers(0, vocab, int(p), dtype=np.int64).astype(np.int32)
            out.append(Arrival(len(out), float(d), prompt, int(o), name))
        start += length
    return out
