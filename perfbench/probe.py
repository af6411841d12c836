#!/usr/bin/env python3
"""Sizing probe for one configuration, on the chip.

    python3 perfbench/probe.py <config> [--pages N] [--context T] [--batches 1,8] [--trace-dir DIR]

Builds the configuration's engine with a small page pool, compiles its
decode tick and its full-chunk tick at a few prefill batches, and prints
per program: compile seconds, ``compiled.memory_analysis()`` (argument,
output and temporary bytes), and the median device time of a launch with
every decode row (or every chunk row) live at a stated context length.
From the argument bytes of two pool sizes it prints the physical bytes
of one page, the figure behind each configuration's ``n_pages``.  A
program the chip's compiler refuses is reported with its error.  With
``--trace-dir`` it traces a few launches and prints the trace's planes,
its reduction and the most frequent op names.  Prints one JSON line last.
Refuses to run off a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def timed(fn, args, reps=7):
    import jax

    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--trace-dir")
    ap.add_argument("--context", type=int, default=1024)
    ap.add_argument("--pages", type=int, default=1024)
    ap.add_argument("--batches", default="1,8,32,128")
    a = ap.parse_args()
    from harness import cells

    cells.setup_jax()
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = cells.require_chips(1)
    from harness import model

    cfg = model.load_json(os.path.join(HERE, "configs", f"{a.config}.json"))
    out = {"config": a.config, "device": dev.device_kind}
    api = model.build_api(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(model.make_params(api, cfg, 1))
    out["params_s"] = time.perf_counter() - t0
    out["params_bytes"] = int(sum(x.nbytes for x in jax.tree.leaves(params)))
    cfg["engine"]["n_pages"] = a.pages
    eng = model.build_engine(api, params, cfg)
    w = eng.tables.shape[1]
    ps, c = eng.ps, eng.prefill_chunk
    n_cp = -(-c // ps)
    live = -(-a.context // ps)
    table = (np.arange(w) % (a.pages - 1) + 1).astype(np.int32)

    # each row reads the same live pages (bytes read are as for distinct
    # pages) but writes its token into a page of its own, as when serving
    dec = np.zeros((eng.n_slots, 3 + w), np.int32)
    dec[:, 1] = 1
    dec[:, 2] = a.context
    dec[:, 3:] = np.where(np.arange(w) < live + 1, table, 0)
    dec[:, 3 + a.context // ps] = 1 + np.arange(eng.n_slots) % (a.pages - 1)
    progs = [("decode", eng._decode, (params, eng.pool, jnp.asarray(dec), eng._chain_tok))]
    for bb in (int(b) for b in a.batches.split(",")):
        pk = np.zeros((bb, c + 2 + n_cp + w), np.int32)
        pk[:, c] = a.context - c
        pk[:, c + 1 : c + 1 + n_cp] = 1 + np.arange(bb * n_cp).reshape(bb, n_cp) % (a.pages - 1)
        pk[:, c + 1 + n_cp] = c
        pk[:, c + 2 + n_cp :] = np.where(np.arange(w) < live, table, 0)
        progs.append((f"chunk b={bb}", eng._chunk_fn(c, n_cp), (params, eng.pool, jnp.asarray(pk))))
    rows, ok = [], []
    for name, fn, args in progs:
        t0 = time.perf_counter()
        try:
            comp = fn.lower(*args).compile()
        except Exception as e:  # a program the chip's compiler refuses
            r = {"program": name, "error": str(e)[:400]}
            print("probe", json.dumps(r), flush=True)
            rows.append(r)
            continue
        ok.append((name, fn, args))
        ct = time.perf_counter() - t0
        ma = comp.memory_analysis()
        r = {
            "program": name, "compile_s": ct,
            "argument_bytes": ma.argument_size_in_bytes, "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes, "alias_bytes": ma.alias_size_in_bytes,
            "device_s": timed(fn, args),
        }
        print("probe", json.dumps(r), flush=True)
        rows.append(r)
    out["programs"] = rows
    pool_a = sum(x.nbytes for x in jax.tree.leaves(eng.pool))
    out["pool_logical_bytes_per_page"] = pool_a / a.pages
    # physical bytes: the decode program's argument bytes at two pool sizes
    small = api.pool_init(a.pages // 2, ps)
    ma2 = eng._decode.lower(params, small, jnp.asarray(dec), eng._chain_tok).compile().memory_analysis()
    out["pool_physical_bytes_per_page"] = (rows[0]["argument_bytes"] - ma2.argument_size_in_bytes) / (a.pages - a.pages // 2)
    del small
    if a.trace_dir:
        jax.profiler.start_trace(a.trace_dir)
        with jax.profiler.TraceAnnotation("perfbench.window"):
            for name, fn, args in ok[:3]:
                for _ in range(3):
                    with jax.profiler.TraceAnnotation(f"probe.{name}"):
                        jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        from harness import xplane
        from jax.profiler import ProfileData

        path = xplane.find(a.trace_dir)
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
            print("plane", plane.name, lines[:12], flush=True)
        dev_lines, host = xplane.load(path)
        red = xplane.reduce(dev_lines, host, ("bcq_linear", "page_gather_attention"))
        print("reduction", json.dumps(red), flush=True)
        from collections import Counter

        names = Counter(xplane.op_name(n) for line in dev_lines for n, _, _ in line)
        print("op names", names.most_common(40), flush=True)
    stats = dev.memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    out["bytes_limit"] = stats.get("bytes_limit")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
