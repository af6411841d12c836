"""Finds a cell's pieces by the names in ``BENCHMARK.json``: the
configuration ``configs/<config>.json``, the traffic mix
``traffic/<traffic>.json`` and one reader ``metrics/<metric>.py`` per
per-layer metric.  A later cell is added with files and entries alone.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(name: str, root: str = ROOT, bench_dir: str = BENCH_DIR) -> dict:
    """Everything one workload needs, found by name."""
    b = benchmark(root)
    w = next((w for w in b["workloads"] if w["name"] == name), None)
    if w is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in b["configs"] if c["name"] == w["config"])
    e2e = [m for m in b["end_to_end"] if name in m.get("workloads", [name])]
    per_layer = [m for m in b["per_layer"] if name in m.get("workloads", [name])]
    return {
        "workload": w,
        "config": load_json(os.path.join(root, conf["file"])),
        "traffic": load_json(os.path.join(bench_dir, "traffic", f"{w['traffic']}.json")),
        "end_to_end": e2e,
        "per_layer": per_layer,
    }


def reader(metric: str, bench_dir: str = BENCH_DIR):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    path = os.path.join(bench_dir, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"perfbench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def setup_jax() -> str:
    """Point JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    when set, else at the fixed ``<checkout>/.jax_cache``; cache every
    program, however quick its compile.  Returns the directory."""
    import jax

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


def require_chips(n: int):
    """The first device, if JAX finds at least ``n`` TPU chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"needs {n} TPU chip(s); JAX found {len(devs)} {devs[0].platform} device(s)")
    return devs[0]


def log(msg: str) -> None:
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)
