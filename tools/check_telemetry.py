#!/usr/bin/env python
"""Validate serving telemetry artifacts (CI gate).

  python tools/check_telemetry.py METRICS.json [TRACE.json]

Checks the --metrics-json dump (schema version, required counters /
gauges / histograms with the pinned bucket edges, timeline sanity) and
the --trace-out Chrome trace (loadable, monotonic timestamps, every
duration Begin closed by the End of the same span with spans nested
per thread, thread-name metadata).  Exits
nonzero with a message on the first violation so CI fails loudly.

Only stdlib — runnable on artifacts downloaded from a CI run without
the repo's python path set up.
"""
from __future__ import annotations

import json
import sys

SCHEMA = 1

REQUIRED_COUNTERS = (
    "prefix_hits", "prefix_misses", "preemptions", "prefix_evictions",
    "decode_ticks", "prefill_chunks", "prefill_tokens", "prefill_launches",
    "forks", "cow_copies", "shared_pages", "device_syncs",
    # robustness layer (docs/ROBUSTNESS.md)
    "quarantined", "shed", "expired", "cancelled",
    "audit_failures", "degraded_ticks",
    # host-RAM swap tier (zeros when the tier is disabled)
    "swap_outs", "swap_ins", "verified_swapins", "corrupt_swapins",
    "swap_bytes",
)
REQUIRED_GAUGES = (
    "pool_pages_used", "pool_pages_free", "pool_peak_pages",
    "prefix_reclaimable_pages", "prefix_registered_pages",
    "watermark_headroom", "queue_depth", "active_slots",
    # per-kind pool occupancy: one page budget shared across
    # heterogeneous kinds (kv block-table pages, state checkpoints,
    # read-only shared encoder pages)
    "pool_pages_kv", "pool_pages_state", "pool_pages_shared_ro",
    # host-RAM swap tier occupancy (zeros when disabled)
    "host_pages_used", "host_pages_capacity",
)
# name → exact bucket edges (mirrors repro.serving.telemetry — kept
# literal here so the checker stands alone)
REQUIRED_HISTOGRAMS = {
    "ttft_s": [0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
               1.0, 2.5, 5.0, 10.0],
    "itl_s": [0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
              0.5, 1.0],
    "queue_time_s": [0.0001, 0.001, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0],
    "prefill_launch_s": None,  # = itl_s edges
    "decode_tick_s": None,
}
REQUIRED_HISTOGRAMS["prefill_launch_s"] = REQUIRED_HISTOGRAMS["itl_s"]
REQUIRED_HISTOGRAMS["decode_tick_s"] = REQUIRED_HISTOGRAMS["itl_s"]


def fail(msg: str) -> None:
    print(f"check_telemetry: FAIL: {msg}", file=sys.stderr)
    raise SystemExit(1)


def check_metrics(path: str) -> None:
    with open(path) as f:
        snap = json.load(f)
    if snap.get("schema") != SCHEMA:
        fail(f"{path}: schema {snap.get('schema')!r} != {SCHEMA}")
    if snap.get("level") not in ("counters", "default"):
        fail(f"{path}: unknown level {snap.get('level')!r}")
    for section in ("counters", "gauges", "histograms", "journal", "timelines"):
        if section not in snap:
            fail(f"{path}: missing section {section!r}")
    for name in REQUIRED_COUNTERS:
        if not isinstance(snap["counters"].get(name), int):
            fail(f"{path}: counter {name!r} missing or non-integer")
    for name in REQUIRED_GAUGES:
        if name not in snap["gauges"]:
            fail(f"{path}: gauge {name!r} missing")
    kinds = {k: snap["gauges"][f"pool_pages_{k}"]
             for k in ("kv", "state", "shared_ro")}
    if any(v < 0 for v in kinds.values()):
        fail(f"{path}: negative per-kind page gauge {kinds}")
    if sum(kinds.values()) != snap["gauges"]["pool_pages_used"]:
        fail(f"{path}: per-kind pages {kinds} do not sum to "
             f"pool_pages_used={snap['gauges']['pool_pages_used']}")
    # host-tier swap accounting: every swap-in either verified or
    # quarantined, and occupancy never exceeds the configured bound
    c = snap["counters"]
    if c["swap_ins"] != c["verified_swapins"] + c["corrupt_swapins"]:
        fail(f"{path}: swap_ins={c['swap_ins']} != verified "
             f"{c['verified_swapins']} + corrupt {c['corrupt_swapins']}")
    g = snap["gauges"]
    if g["host_pages_used"] > g["host_pages_capacity"]:
        fail(f"{path}: host_pages_used={g['host_pages_used']} exceeds "
             f"host_pages_capacity={g['host_pages_capacity']}")
    for name, edges in REQUIRED_HISTOGRAMS.items():
        h = snap["histograms"].get(name)
        if h is None:
            fail(f"{path}: histogram {name!r} missing")
        if h["buckets"] != edges:
            fail(f"{path}: histogram {name!r} buckets {h['buckets']} != {edges}")
        if len(h["counts"]) != len(edges) + 1:  # implicit +inf bucket
            fail(f"{path}: histogram {name!r} has {len(h['counts'])} counts "
                 f"for {len(edges)} edges")
        if sum(h["counts"]) != h["count"]:
            fail(f"{path}: histogram {name!r} bucket counts do not sum "
                 f"to count={h['count']}")
    for tl in snap["timelines"]["requests"]:
        if tl["ttft_s"] is not None and tl["ttft_s"] < 0:
            fail(f"{path}: rid {tl['rid']} negative ttft {tl['ttft_s']}")
        if tl["n_tokens"] < 0 or tl["preemptions"] < 0:
            fail(f"{path}: rid {tl['rid']} negative token/preempt counts")
    if "quant_probes" in snap:
        qp = snap["quant_probes"]
        for site, layers in qp["sites"].items():
            for layer, agg in layers.items():
                if agg["nmse_mean"] < 0 or agg["nmse_max"] < 0:
                    fail(f"{path}: probe {site}/L{layer} negative nmse")
                if any(c < 0 for c in agg["cluster_occupancy"]):
                    fail(f"{path}: probe {site}/L{layer} negative occupancy")
    print(f"check_telemetry: {path} OK "
          f"(level={snap['level']}, {len(snap['counters'])} counters, "
          f"{snap['timelines']['count']} timelines"
          + (", quant probes present" if "quant_probes" in snap else "")
          + ")")


def check_trace(path: str) -> None:
    with open(path) as f:
        doc = json.load(f)
    evs = doc.get("traceEvents")
    if not isinstance(evs, list):
        fail(f"{path}: no traceEvents list")
    if doc.get("otherData", {}).get("schema") != SCHEMA:
        fail(f"{path}: otherData.schema != {SCHEMA}")
    meta_threads = {
        e["args"]["name"] for e in evs
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    if not {"host scheduling", "device launches"} <= meta_threads:
        fail(f"{path}: thread-name metadata missing ({meta_threads})")
    real = [e for e in evs if e["ph"] != "M"]
    last_ts = -1.0
    open_spans: dict = {}  # tid -> stack of open span names
    for e in real:
        for key in ("name", "ph", "ts", "pid", "tid"):
            if key not in e:
                fail(f"{path}: event missing {key!r}: {e}")
        if e["ts"] < last_ts:
            fail(f"{path}: timestamps not monotonic at {e}")
        last_ts = e["ts"]
        stack = open_spans.setdefault(e["tid"], [])
        if e["ph"] == "B":
            stack.append(e["name"])
        elif e["ph"] == "E":
            if not stack:
                fail(f"{path}: End without Begin on tid {e['tid']}")
            if stack.pop() != e["name"]:
                fail(f"{path}: span {e['name']!r} ends inside another "
                     f"on tid {e['tid']} (spans must nest)")
        elif e["ph"] != "i":
            fail(f"{path}: unexpected phase {e['ph']!r}")
    if any(open_spans.values()):
        fail(f"{path}: unbalanced spans at end of trace: "
             f"{ {t: len(s) for t, s in open_spans.items()} }")
    spans = sum(1 for e in real if e["ph"] == "B")
    print(f"check_telemetry: {path} OK ({spans} spans, "
          f"{sum(1 for e in real if e['ph'] == 'i')} instants, "
          f"{doc['otherData']['dropped']} dropped)")


def main(argv: list[str]) -> None:
    if not 1 <= len(argv) <= 2:
        fail("usage: check_telemetry.py METRICS.json [TRACE.json]")
    check_metrics(argv[0])
    if len(argv) == 2:
        check_trace(argv[1])


if __name__ == "__main__":
    main(sys.argv[1:])
