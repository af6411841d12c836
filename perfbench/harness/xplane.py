"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
kernel time and idle gaps.

The traced window is the host span ``perfbench.window`` that the harness
opens and closes around the measured window.  Device time comes from the
op line (``XLA Ops``) of each TPU plane: busy time is the union of the op
intervals inside the window, a kernel's time the sum of the durations of
the ops whose instruction name contains the kernel's name (Pallas names
its custom call after the kernel: ``%bcq_linear.67 = ...``; ops that only
contain others, such as the scanned layers' ``while``, are left out of
the sums but not of the union), and each idle gap (a span
of the window with no op running) is charged to the innermost host span
of the client's thread that covers the gap's midpoint.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "perfbench.window"
OPS_LINE = "XLA Ops"


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> tuple[list, list]:
    """(device op lines, host thread lines) as plain lists: each line a
    list of (name, start_ns, end_ns) events."""
    from jax.profiler import ProfileData

    dev, host = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.append([(e.name, e.start_ns, e.end_ns) for e in line.events])
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.append([(e.name, e.start_ns, e.end_ns) for e in line.events])
    return dev, host


def window_of(host: list) -> tuple[float, float]:
    for line in host:
        for name, s, e in line:
            if name == WINDOW_SPAN:
                return s, e
    raise ValueError(f"no {WINDOW_SPAN} span in the trace")


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


# ops that only contain others (a scanned layer stack is one ``while``)
CONTAINERS = ("while", "conditional", "call")


def op_name(text: str) -> str:
    """The instruction name of an op event, whose name on the chip is the
    HLO text ``%bcq_linear.67 = f32[512,3072]{...} custom-call(...)``."""
    return text.split(" = ", 1)[0].lstrip("%")


def base_name(name: str) -> str:
    """``fusion.123`` -> ``fusion``: one entry per kind of op."""
    return re.sub(r"(\.\d+)+$", "", name)


def op_kind(text: str) -> str:
    """Breakdown key: the op's kind and, where the event carries the HLO
    text, its result type (so a copy of the page pool stands apart)."""
    kind = base_name(op_name(text))
    if " = " in text:
        result = text.split(" = ", 1)[1].split("{", 1)[0].split(" ", 1)[0]
        kind = f"{kind} {result}"[:96]
    return kind


class HostSpans:
    """The client thread's spans, for finding the innermost one at a time
    (properly nested spans: the covering span that started last)."""

    def __init__(self, host: list, w0: float, w1: float):
        line = next((ln for ln in host if any(n == WINDOW_SPAN for n, _, _ in ln)), [])
        ev = sorted((s, e, n) for n, s, e in line if n != WINDOW_SPAN and e > w0 and s < w1)
        self.starts = [s for s, _, _ in ev]
        self.ev = ev

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0:
            s, e, n = self.ev[i]
            if e > t:
                return n
            i -= 1
        return "host idle"


def reduce(dev: list, host: list, kernels=()) -> dict:
    """Busy, kernel and idle figures of the traced window, averaged over
    the device op lines (one per chip used)."""
    w0, w1 = window_of(host)
    spans = HostSpans(host, w0, w1)
    n = max(len(dev), 1)
    busy = 0.0
    kernel_ns = {k: 0.0 for k in kernels}
    by_op: dict[str, float] = defaultdict(float)
    gaps_by_host: dict[str, float] = defaultdict(float)
    for line in dev:
        clipped = [(nm, max(s, w0), min(e, w1)) for nm, s, e in line if e > w0 and s < w1]
        merged = union((s, e) for _, s, e in clipped)
        busy += sum(e - s for s, e in merged)
        for nm, s, e in clipped:
            name = op_name(nm)
            if base_name(name) in CONTAINERS:
                continue
            by_op[op_kind(nm)] += e - s
            for k in kernels:
                if k in name:
                    kernel_ns[k] += e - s
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps_by_host[spans.at((a + b) / 2)] += b - a
    top = lambda d: [[k, v / n * 1e-9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]  # noqa: E731
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy / n * 1e-9,
        "kernel_s": {k: v / n * 1e-9 for k, v in kernel_ns.items()},
        "device_ops": top(by_op),
        "idle_gaps": top(gaps_by_host),
        "n_device_lines": len(dev),
    }
