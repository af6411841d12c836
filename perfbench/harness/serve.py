"""The open-loop client: submits each request when it is due, ticks the
engine, and records when every output token becomes visible.

A token is visible when the client sees it in ``req.out`` after
``engine.step()`` returns, so the host tick is part of every latency.
Latency is timed from when a request was due, not from its submit, so a
late client cannot hide queueing; the client's lateness is reported.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque

import numpy as np


@dataclasses.dataclass
class Tracked:
    arrival: object  # traffic.Arrival
    due: float  # absolute perf_counter time
    req: object = None  # the engine Request
    t_submit: float = 0.0
    times: list = dataclasses.field(default_factory=list)  # visibility time per token


@dataclasses.dataclass
class Run:
    tracked: list
    t_start: float
    t_open: float
    t_close: float
    steps: int = 0
    unsent: list = dataclasses.field(default_factory=list)  # due before close, never submitted


def drive(engine, arrivals, t_start: float, preroll_s: float, seconds: float,
          on_open=None, on_close=None, span=None) -> Run:
    """Serve ``arrivals`` (due times relative to ``t_start``) until the
    window [t_start + preroll_s, + seconds) closes.  ``on_open`` and
    ``on_close`` are called at the window's edges; ``span(name)`` returns
    a context manager wrapping each call into the engine (trace spans)."""
    from repro.serving.generate import Request

    span = span or (lambda name: contextlib.nullcontext())
    run = Run([], t_start, t_start + preroll_s, t_start + preroll_s + seconds)
    pending = deque(Tracked(a, t_start + a.due) for a in arrivals)
    live: list[Tracked] = []
    opened = False
    while True:
        now = time.perf_counter()
        if not opened and now >= run.t_open:
            opened = True
            if on_open is not None:
                on_open()
        if now >= run.t_close:
            break
        with span("client.submit"):
            while pending and pending[0].due <= now:
                t = pending.popleft()
                a = t.arrival
                t.req = Request(rid=a.rid, prompt=a.prompt, max_new=a.n_out - 1)
                t.t_submit = time.perf_counter()
                engine.submit(t.req)
                live.append(t)
                run.tracked.append(t)
        if not live:
            nxt = min(pending[0].due if pending else run.t_close, run.t_close)
            if not opened:
                nxt = min(nxt, run.t_open)
            with span("client.wait"):
                time.sleep(max(0.0, nxt - time.perf_counter()))
            continue
        with span("engine.step"):
            engine.step()
        run.steps += 1
        seen = time.perf_counter()
        with span("client.poll"):
            still = []
            for t in live:
                n = len(t.req.out)
                if n > len(t.times):
                    t.times.extend([seen] * (n - len(t.times)))
                if t.req.error is not None:
                    raise RuntimeError(f"request {t.arrival.rid} failed: {t.req.error!r}")
                if n < t.arrival.n_out:
                    still.append(t)
            live = still
    if on_close is not None:
        on_close()
    run.unsent = [t for t in pending if t.due < run.t_close]
    return run


def p95(values, empty: float) -> float:
    """95th percentile; ``empty`` where there are no samples."""
    return float(np.percentile(values, 95)) if len(values) else empty


def median(values, empty: float) -> float:
    return float(np.median(values)) if len(values) else empty


def due_in_window(run: Run) -> list:
    """Every request due in the window, submitted or not (a client that a
    long step held up may not have sent the last ones before close)."""
    return [t for t in run.tracked + run.unsent if run.t_open <= t.due < run.t_close]


def backlog(run: Run, t: float) -> int:
    """Requests due by ``t`` that had shown no token by ``t``."""
    return sum(1 for r in run.tracked + run.unsent if r.due <= t and not (r.times and r.times[0] <= t))


def end_to_end(run: Run) -> tuple[dict, dict]:
    """The window's end-to-end metrics, and the detail printed beside them
    (medians, sample counts, the client's lateness, and whether the
    backlog of requests waiting for their first token grew through the
    window: its mean over each third, and the TTFT median of the requests
    due in each half)."""
    o, c = run.t_open, run.t_close
    due = due_in_window(run)
    toks = sum(1 for t in run.tracked for s in t.times if o <= s < c)
    ttft = [min(t.times[0] if t.times else c, c) - t.due for t in due]
    itl = [
        b - a
        for t in run.tracked
        for a, b in zip(t.times, t.times[1:])
        if a >= o and b < c
    ]
    late = [(t.t_submit if t.req is not None else c) - t.due for t in due]
    grid = np.linspace(o, c, 301)[:-1]
    thirds = [float(np.mean([backlog(run, t) for t in part])) for part in np.array_split(grid, 3)]
    mid = 0.5 * (o + c)
    halves = [
        median([x for x, t in zip(ttft, due) if (t.due < mid) == first], c - o) for first in (True, False)
    ]
    # no two tokens of one request inside the window: every gap spans it
    metrics = {
        "output_tok_s": toks / (c - o),
        "ttft_p95_s": p95(ttft, c - o),
        "itl_p95_ms": 1e3 * p95(itl, c - o),
    }
    detail = {
        "requests_due": len(due),
        "requests_first_token": sum(1 for t in due if t.times and t.times[0] < c),
        "tokens_in_window": toks,
        "output_tok_s": metrics["output_tok_s"],
        "ttft_median_s": median(ttft, c - o),
        "itl_median_ms": 1e3 * median(itl, c - o),
        "itl_p95_ms": metrics["itl_p95_ms"],
        "itl_samples": len(itl),
        "lateness_p95_ms": 1e3 * p95(late, 0.0),
        "lateness_max_ms": 1e3 * max(late, default=0.0),
        "engine_steps": run.steps,
        "unsent_at_close": len([t for t in due if t.req is None]),
        "backlog_thirds": thirds,
        "ttft_median_halves_s": halves,
    }
    return metrics, detail
