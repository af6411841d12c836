"""The run command off the chip, and a cell added as files alone."""
import json
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness import cells  # noqa: E402


def test_refuses_to_run_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    bench = cells.benchmark()
    for w in bench["workloads"]:
        p = subprocess.run(
            [sys.executable, os.path.join(BENCH, "run.py"), "--workload", w["name"],
             "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
        )
        assert p.returncode != 0
        assert '"metrics"' not in p.stdout and not p.stdout.strip()
        assert "refusing to run" in p.stderr


def test_benchmark_entries_have_their_files():
    b = cells.benchmark()
    for w in b["workloads"]:
        c = cells.cell(w["name"])
        assert c["config"]["engine"]["n_slots"] >= 1 and c["traffic"]["rate_per_s"] > 0
        assert c["end_to_end"] and c["per_layer"]
    for m in b["per_layer"]:
        assert callable(cells.reader(m["name"]))


def test_a_cell_added_as_files_is_found(tmp_path):
    """Copy the benchmark, add a configuration, a mix and a metric as new
    files plus BENCHMARK.json entries, and find the new cell without
    touching any file that was there."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "perfbench", ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    before = {p: open(p, "rb").read() for p in map(str, (root / "perfbench").rglob("*")) if os.path.isfile(p)}

    conf = json.load(open(os.path.join(BENCH, "configs", "gpt3_126m.json")))
    conf["engine"]["n_slots"] = 64
    (root / "perfbench" / "configs" / "gpt3_126m_half.json").write_text(json.dumps(conf))
    mix = json.load(open(os.path.join(BENCH, "traffic", "doc_qa.json")))
    mix["rate_per_s"] = 1.5
    (root / "perfbench" / "traffic" / "doc_qa_slow.json").write_text(json.dumps(mix))
    (root / "perfbench" / "metrics" / "tokens_seen.py").write_text(
        "def read(ctx):\n    return float(len(ctx['run']))\n"
    )
    bench["configs"].append({**bench["configs"][0], "name": "gpt3_126m_half",
                             "file": "perfbench/configs/gpt3_126m_half.json"})
    bench["workloads"].append({"name": "gpt3_126m_half.doc_qa_slow", "config": "gpt3_126m_half",
                               "traffic": "doc_qa_slow", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "tokens_seen", "unit": "tokens", "better": "higher",
                               "source": "program_counter", "layer": "client", "moves": "ttft_p95_s",
                               "workloads": ["gpt3_126m_half.doc_qa_slow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    c = cells.cell("gpt3_126m_half.doc_qa_slow", root=str(root), bench_dir=str(root / "perfbench"))
    assert c["config"]["engine"]["n_slots"] == 64
    assert c["traffic"]["rate_per_s"] == 1.5
    names = [m["name"] for m in c["per_layer"]]
    assert "tokens_seen" in names
    assert cells.reader("tokens_seen", bench_dir=str(root / "perfbench"))({"run": [1, 2]}) == 2.0
    # the old cells do not report the new metric, and nothing old changed
    assert "tokens_seen" not in [m["name"] for m in cells.cell(
        "gpt3_126m.doc_qa", root=str(root), bench_dir=str(root / "perfbench"))["per_layer"]]
    assert all(open(p, "rb").read() == b for p, b in before.items())
