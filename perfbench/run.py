#!/usr/bin/env python3
"""Runs one benchmark cell once, on the chips of the machine it starts on.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell (``BENCHMARK.json`` ``workloads``)
names a configuration and a traffic mix; both, and the readers of the
per-layer metrics, are found by name under ``perfbench/``.  One run:

1. builds the engine of the configuration with weights made on the
   device from ``--seed``, and warms up every program it can launch;
2. pre-rolls the traffic mix for ``preroll_s`` seconds, then measures
   for ``--seconds`` seconds (with ``--trace 1`` under the profiler);
3. frees the engine and checks what it served against the float32
   reference (``harness/correct.py``);
4. prints the numbers compared beside their limits as the last lines of
   standard error, and one JSON result as the last line of standard
   output: the end-to-end metrics with ``--trace 0``, the per-layer
   metrics with ``--trace 1``.

It refuses to run (exit code 3, no result) where JAX finds no TPU or
fewer chips than the cell asks for.

Options the benchmark's own runs never pass, for measuring the cell:
``--seed`` may list several seeds (``1,2,3``), served one after another
in one process that builds and warms the programs once, each printing
its own result line; ``--rate`` overrides the mix's arrival rate, one
rate or one per seed (the capacity sweep); ``--control 1`` judges, in
the served tokens' place, the tokens that the float32 reference at
3-bit LO-BCQ puts first at the same positions (the control, which has
to come out not correct), printing the served tokens' reading beside
it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

from harness import cells  # noqa: E402

KERNELS = ("bcq_linear", "page_gather_attention")
TRACE_DIR = os.path.join(cells.ROOT, ".perfbench_trace")


def peaks(kind: str) -> dict:
    table = cells.load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in perfbench/peaks.json")
    return table[kind]


def registry_view(engine) -> dict:
    """What the readers take from the engine's telemetry at a window edge."""
    gap = engine.telemetry.h_host_gap
    return {"host_gap_sum": gap.sum, "host_gap_count": gap.count, "queue": len(engine.queue)}


def run_cell(c: dict, seed: int, seconds: float, trace: bool, dev, rate=None,
             t_start: float = T_START, api=None, warm: bool = True,
             control: bool = False) -> dict:
    """One run of cell ``c``, on ``api`` where given (the model API, which
    holds the compiled step programs); ``warm=False`` where an earlier run
    in this process already warmed them up."""
    import jax

    from harness import correct, model, serve, traffic, work, xplane
    from repro.serving.telemetry import Telemetry

    cfg, mix = c["config"], dict(c["traffic"])
    if rate:
        mix["rate_per_s"] = rate
    api = api or model.build_api(cfg)
    params = jax.block_until_ready(model.make_params(api, cfg, seed))
    engine = model.build_engine(
        api, params, cfg, telemetry=Telemetry(trace_capacity=1 << 18, max_timelines=1 << 16)
    )
    if warm:
        model.warm_up(engine, work.vocab_padded(cfg), mix["warm_prefill_batches"], log=cells.log)
    traces_before = engine.trace_counts(since_init=False)
    arrivals = traffic.schedule(mix, seed, seconds, cfg["vocab"])

    state: dict = {}
    span = (lambda name: jax.profiler.TraceAnnotation(name)) if trace else None

    def on_open():
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(TRACE_DIR)
            state["window_span"] = jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)
            state["window_span"].__enter__()
        state["open"] = (time.perf_counter(), registry_view(engine))

    def on_close():
        state["close"] = (time.perf_counter(), registry_view(engine))
        if trace:
            state["window_span"].__exit__(None, None, None)

    run = serve.drive(engine, arrivals, time.perf_counter(), mix["preroll_s"], seconds,
                      on_open=on_open, on_close=on_close, span=span)
    stats = dev.memory_stats() or {}
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": c["workload"]["chips"],
        "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
    }
    traces = {k: v - traces_before.get(k, 0) for k, v in engine.trace_counts(since_init=False).items()}
    metrics, detail = serve.end_to_end(run)
    metrics["setup_s"] = run.t_open - t_start
    detail["programs_traced_in_window"] = traces
    detail["queue_at_open"] = state["open"][1]["queue"]
    detail["queue_at_close"] = state["close"][1]["queue"]
    detail["peak_pages"] = engine.stats["peak_pages"]
    for k, v in detail.items():
        cells.log(f"{k} {v}")

    units = {m["name"]: m["unit"] for m in c["end_to_end"] + c["per_layer"]}
    breakdown = None
    if trace:
        jax.block_until_ready(engine.pool)
        jax.profiler.stop_trace()
        red = xplane.reduce(*xplane.load(xplane.find(TRACE_DIR)), kernels=KERNELS)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
        cells.log(f"trace {json.dumps(red)}")
        ctx = {
            "cfg": cfg, "peaks": peaks(dev.device_kind), "trace": red, "run": run,
            "open": state["open"], "close": state["close"],
            "journal": list(engine.telemetry.journal._buf),
            "timelines": list(engine.telemetry.timelines),
        }
        values = {}
        for m in c["per_layer"]:
            v = cells.reader(m["name"])(ctx)
            if v is not None:
                values[m["name"]] = v
        out_metrics = values
    else:
        out_metrics = {m["name"]: metrics[m["name"]] for m in c["end_to_end"]}

    engine.drain()
    del engine
    gc.collect()
    fmt = cells.load_json(os.path.join(HERE, "codebooks.json"))["format"]
    t0 = time.perf_counter()
    ok, compared, seen = correct.check(run, params, cfg, fmt, seed, control=control)
    cells.log(f"reference check took {time.perf_counter() - t0:.3f} s; {json.dumps(seen)}")
    due = serve.due_in_window(run)
    result = {
        "correct": bool(ok),
        "attempted": len(due),
        # a request the client had not sent by close is late, not failed:
        # it counts in the TTFT tail as close - due
        "failed": sum(1 for t in due if t.req is not None and t.req.error is not None),
        "metrics": {k: {"value": v, "unit": units[k]} for k, v in out_metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    for k, v in compared.items():
        rel = ">=" if k == "tokens_compared" else "<="
        cells.log(f"compared {k} {v['value']} {rel} limit {v['limit']}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True, help="a seed, or several: 1,2,3")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", default=None, help="one rate, or one per seed")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    seeds = [int(x) for x in a.seed.split(",")]
    rates = [float(x) for x in a.rate.split(",")] if a.rate else [None]
    if len(rates) == 1:
        rates = rates * len(seeds)
    if len(rates) != len(seeds):
        ap.error("--rate takes one rate or one per seed")
    c = cells.cell(a.workload)
    cells.setup_jax()
    try:
        dev = cells.require_chips(c["workload"]["chips"])
    except cells.NoChip as e:
        cells.log(f"refusing to run: {e}")
        return 3
    from harness import model

    api = model.build_api(c["config"])
    for i, (seed, rate) in enumerate(zip(seeds, rates)):
        result = run_cell(c, seed, a.seconds, bool(a.trace), dev, rate=rate,
                          t_start=T_START if i == 0 else time.perf_counter(), api=api,
                          warm=i == 0, control=bool(a.control))
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
