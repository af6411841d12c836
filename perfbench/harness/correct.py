"""The comparison that decides ``correct``.

Once the window has closed and the engine is freed, a sample of the
requests the engine finished, drawn from the seed and always holding the
longest one, is run through the float32 reference teacher-forced over its
prompt and served tokens.  Per served token, the gap is how far its
reference logit lies below the reference's best at its position
(``reference.token_readings``).  The number compared is the share of
the sample's tokens whose gap passes ``gap_over_spread`` times the
standard deviation of the reference's logits at their position: the
sound W4A4 path picks the runner-up at near-ties, with gaps under that
threshold, while a path one bit coarser picks tokens far below the best.
The mean gap and the widest gap are printed beside it but not compared:
a few requests whose served tokens sit on near-ties move the mean, and
the widest gaps of the two paths overlap (see ``PERF.md``, Findings).
Every finished request must also hold exactly the tokens it asked for.
"""
from __future__ import annotations

import numpy as np

from harness import reference


def sample(run, seed: int, tokens: int) -> list:
    finished = [t for t in run.tracked if len(t.req.out) >= t.arrival.n_out]
    if not finished:
        return []
    longest = max(finished, key=lambda t: (t.arrival.n_out, -t.arrival.rid))
    # each request's place in the draw comes from the seed and its own id,
    # so a request that finishes late does not reorder the others
    key = {t.arrival.rid: np.random.default_rng([int(seed), 11, t.arrival.rid]).random() for t in finished}
    order = [longest] + sorted((t for t in finished if t is not longest), key=lambda t: key[t.arrival.rid])
    out, n = [], 0
    for t in order:
        if n >= tokens:
            break
        out.append(t)
        n += t.arrival.n_out
    return out


def gap_share(gaps: np.ndarray, spread: np.ndarray, over: float) -> float:
    """Share of tokens whose gap passes ``over`` times the spread of the
    reference's logits at their position."""
    return float(np.mean(gaps > over * spread))


def summary(gaps: np.ndarray, spread: np.ndarray, over: float) -> dict:
    """The share compared, and the mean and widest gap beside it."""
    return {"share": gap_share(gaps, spread, over), "mean": float(gaps.mean()), "max": float(gaps.max())}


def check(run, params, cfg: dict, fmt: dict, seed: int, control: bool = False):
    """(correct, the numbers compared with their limits, what else the
    comparison saw).  With ``control`` the number compared is the
    control's: the reference at 3-bit put in the program's place, read at
    the same positions."""
    lim = cfg["correct"]
    picked = sample(run, seed, lim["sample_tokens"])
    reads = [reference.token_readings(params, cfg, fmt, t.arrival.prompt, t.req.out, control)
             for t in picked]
    if not reads:  # nothing finished
        reads = [{"gap": np.zeros(0), "spread": np.zeros(0), "control_gap": np.zeros(0)}]
    flat = {k: np.concatenate([r[k] for r in reads]) for k in reads[0]}
    gaps, spread = flat["control_gap" if control else "gap"], flat["spread"]
    share = gap_share(gaps, spread, lim["gap_over_spread"]) if len(gaps) else None
    wrong = sum(1 for t in run.tracked if len(t.req.out) > t.arrival.n_out)
    compared = {
        "gap_share": {"value": share, "limit": lim["limit_gap_share"]},
        "tokens_compared": {"value": int(len(gaps)), "limit": lim["sample_tokens"]},
        "overlong_requests": {"value": wrong, "limit": 0},
    }
    ok = len(gaps) >= lim["sample_tokens"] and share <= lim["limit_gap_share"] and wrong == 0
    seen = {"requests_compared": len(picked)}
    if len(gaps):
        seen["served"] = summary(flat["gap"], spread, lim["gap_over_spread"])
        if control:
            seen["control"] = summary(gaps, spread, lim["gap_over_spread"])
    return ok, compared, seen
