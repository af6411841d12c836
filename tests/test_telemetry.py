"""Telemetry subsystem: metric registry semantics, pinned histogram
bucket layouts, Chrome-trace journal schema, request timelines under
preemption and forking, quant-probe attribution, and the overhead
guards (default-level telemetry adds zero traces and zero device
syncs to the serving hot path)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke
from repro.core.bcq import BCQConfig
from repro.core.calibrate import default_universal_codebooks
from repro.models import zoo
from repro.models.layers import Runtime
from repro.serving.engine import PagedEngine
from repro.serving.events import TID_DEVICE, TID_HOST, TraceJournal
from repro.serving.generate import Request, SamplingParams
from repro.serving.telemetry import (
    ENGINE_STAT_KEYS,
    ITL_BUCKETS,
    LAUNCH_BUCKETS,
    NMSE_BUCKETS,
    QUEUE_BUCKETS,
    TTFT_BUCKETS,
    Histogram,
    MetricsRegistry,
    QuantProbeSink,
    Telemetry,
)

CFG = get_smoke("gpt3_126m")
CB = default_universal_codebooks(BCQConfig()).as_jnp()
MAX_LEN, PS = 32, 8


@pytest.fixture(scope="module")
def api_params():
    rt = Runtime(
        quant_mode="none", compute_dtype=jnp.float32, param_dtype=jnp.float32,
        cache_kind="bf16",
    )
    api = zoo.build(CFG, rt)
    params = api.init(jax.random.PRNGKey(0))
    params["codebooks"] = CB
    return api, params


def _prompts(lengths=(5, 9, 7)):
    rng = np.random.default_rng(0)
    return [rng.integers(0, CFG.vocab, size=n).astype(np.int32) for n in lengths]


def _run(engine, prompts, n_new):
    for i, p in enumerate(prompts):
        engine.submit(Request(rid=i, prompt=p, max_new=n_new))
    finished, _ = engine.run_to_completion()
    return {r.rid: r for r in finished}


# ----------------------------------------------------------- registry units
def test_histogram_bucket_edges_pinned():
    """Dashboards key on these exact edges — changing them is a schema
    break, not a tweak."""
    assert TTFT_BUCKETS == (
        0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
        2.5, 5.0, 10.0,
    )
    assert ITL_BUCKETS == (
        0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
    )
    assert QUEUE_BUCKETS == (
        0.0001, 0.001, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0,
    )
    assert LAUNCH_BUCKETS == ITL_BUCKETS
    assert NMSE_BUCKETS == (
        1e-5, 3e-5, 1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 1e-1, 3e-1, 1.0,
    )


def test_histogram_observe_and_snapshot():
    h = Histogram("x", (1.0, 2.0, 4.0), unit="s")
    for v in (0.5, 1.0, 1.5, 3.0, 100.0):
        h.observe(v)
    # edges are EXCLUSIVE upper bounds (bisect_right): a value equal to
    # an edge lands in the next bucket — [-inf,1) [1,2) [2,4) [4,+inf)
    assert h.counts == [1, 2, 1, 1]
    assert h.count == 5 and h.sum == pytest.approx(106.0)
    assert h.mean() == pytest.approx(21.2)
    assert (h.min, h.max) == (0.5, 100.0)
    s = h.snapshot()
    assert s["buckets"] == [1.0, 2.0, 4.0] and s["counts"] == [1, 2, 1, 1]
    assert s["unit"] == "s" and s["count"] == 5
    assert Histogram("y", (1.0,)).mean() == 0.0  # empty: no div-by-zero


def test_registry_get_or_create_and_snapshot():
    reg = MetricsRegistry()
    c = reg.counter("hits")
    c.inc()
    c.inc(3)
    assert reg.counter("hits") is c and c.value == 4
    reg.gauge("depth").set(7)
    h = reg.histogram("lat", (0.1, 1.0), "s")
    assert reg.histogram("lat", (0.1, 1.0), "s") is h
    with pytest.raises(AssertionError):  # silently changing edges is a bug
        reg.histogram("lat", (0.5, 1.0))
    snap = reg.snapshot()
    assert snap["counters"]["hits"] == 4
    assert snap["gauges"]["depth"] == 7
    assert snap["histograms"]["lat"]["buckets"] == [0.1, 1.0]


# ------------------------------------------------------------ trace journal
def test_journal_chrome_trace_schema_and_ring():
    j = TraceJournal(capacity=4)
    j.span("tick", 1.0, 1.5, args={"n": 1})
    j.instant("evt", 1.2)
    for k in range(4):  # overflow the ring: the two oldest records drop
        j.span("tick", 2.0 + k, 2.4 + k)
    assert len(j) == 4 and j.total == 6 and j.dropped == 2
    # the first span and the instant fell off the ring: only the 4
    # youngest tick spans remain
    assert j.counts() == {"tick": 4}

    doc = j.to_chrome_trace()
    json.loads(json.dumps(doc))  # chrome://tracing requires plain JSON
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert {m["args"]["name"] for m in meta if m["name"] == "thread_name"} \
        == {"host scheduling", "device launches"}
    real = [e for e in evs if e["ph"] != "M"]
    # ts is µs relative to the earliest retained event and monotonic
    assert all(e["ts"] >= 0 for e in real)
    assert [e["ts"] for e in real] == sorted(e["ts"] for e in real)
    # every B has its E: per-thread begin/end depth balances and never
    # goes negative in the sorted stream (Perfetto's own invariant)
    depth: dict = {}
    for e in real:
        if e["ph"] == "B":
            depth[e["tid"]] = depth.get(e["tid"], 0) + 1
        elif e["ph"] == "E":
            depth[e["tid"]] = depth.get(e["tid"], 0) - 1
            assert depth[e["tid"]] >= 0
    assert all(d == 0 for d in depth.values())
    assert sum(1 for e in real if e["ph"] == "B") == 4
    assert doc["otherData"]["dropped"] == 2


def test_journal_disabled_records_nothing():
    j = TraceJournal(capacity=4, enabled=False)
    j.span("tick", 1.0, 2.0)
    j.instant("evt")
    assert len(j) == 0 and j.total == 0
    # only the process/thread-name metadata preamble remains
    assert all(e["ph"] == "M" for e in j.to_chrome_trace()["traceEvents"])


def test_counters_level_hooks_are_noops():
    tel = Telemetry(level="counters")
    req = Request(rid=0, prompt=np.zeros(4, np.int32), max_new=2)
    tel.on_submit(req, 1.0)
    assert req.timeline is None and len(tel.timelines) == 0
    with tel.span("prefill_launch", tokens=4) as args:
        args["synced"] = True
    with tel.span("decode_tick", n_active=1):
        pass
    assert tel.span("engine_step") is tel.span("admit")  # one shared no-op
    assert tel.h_prefill.count == 0 and tel.h_decode.count == 0
    assert len(tel.journal) == 0


# -------------------------------------------------------------- quant probe
def test_quant_probe_layer_attribution():
    """Ordered emissions: layer = arrival count mod n_layers per site."""
    sink = QuantProbeSink(n_layers=2)
    occ = np.array([3, 1], np.int32)
    for nmse in (1.0, 2.0, 3.0, 4.0):  # two launches × two layers
        sink("mlp_in", nmse, occ)
    rep = sink.report()
    per = rep["sites"]["mlp_in"]
    assert per["0"]["count"] == 2 and per["0"]["nmse_mean"] == pytest.approx(2.0)
    assert per["1"]["count"] == 2 and per["1"]["nmse_max"] == 4.0
    assert per["0"]["cluster_occupancy"] == [6, 2]
    assert rep["emissions"] == 4 and sink.total_emissions == 4
    assert rep["nmse_histogram"]["count"] == 4


def test_quant_probe_sampling_decimates_launches():
    sink = QuantProbeSink(n_layers=2, sample_every=2)
    for k in range(6):  # launches 0,1,2 — launch 1 decimated
        sink("s", float(k), np.array([1], np.int32))
    rep = sink.report()["sites"]["s"]
    assert rep["0"]["count"] == 2 and rep["1"]["count"] == 2
    assert sink.total_emissions == 6  # decimation bounds aggregation, not seen


# ----------------------------------------------------------- engine wiring
def test_stats_view_and_snapshot_schema(api_params):
    api, params = api_params
    eng = PagedEngine(api, params, n_slots=2, max_len=MAX_LEN, page_size=PS)
    fin = _run(eng, _prompts(), 4)
    assert len(fin) == 3

    # legacy stats surface: Mapping over exactly the historical keys
    assert set(dict(eng.stats)) == set(ENGINE_STAT_KEYS)
    assert eng.stats["peak_pages"] == eng.pool_mgr.peak > 0
    assert eng.stats["decode_ticks"] > 0
    with pytest.raises(KeyError):
        eng.stats["no_such_stat"]

    snap = eng.snapshot()
    assert snap["schema"] == 1 and snap["level"] == "default"
    for key in ("counters", "gauges", "histograms", "trace_counts",
                "journal", "timelines"):
        assert key in snap, key
    assert snap["gauges"]["pool_peak_pages"] == eng.pool_mgr.peak
    assert snap["counters"]["device_syncs"] > 0
    json.dumps(snap)  # the --metrics-json payload must be JSON-able

    # per-request timelines: every request one timeline, sane latencies
    tls = {tl.rid: tl for tl in eng.telemetry.timelines}
    assert set(tls) == set(fin)
    for rid, r in fin.items():
        tl = tls[rid]
        assert tl.n_tokens == len(r.out)
        assert len(tl.admits) == 1 and tl.preemptions == 0
        assert tl.ttft() is not None and tl.ttft() >= 0
        assert tl.tpot() is not None and tl.tpot() >= 0
        assert tl.t_finish >= tl.t_first >= tl.t_submit
    hist = snap["histograms"]
    assert hist["ttft_s"]["count"] == 3
    assert hist["decode_tick_s"]["count"] == eng.stats["decode_ticks"]

    # the journal replays the run as paired spans
    doc = eng.telemetry.journal.to_chrome_trace()
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "B"}
    assert "decode_tick" in names


def test_default_level_adds_no_traces_or_syncs(api_params):
    """Same warm workload, default vs counters telemetry: identical jit
    trace counts (zero) and identical device-sync counts — the detailed
    level reuses the engine's existing measurement points."""
    api, params = api_params
    # warm every shape bucket (throwaway engine; jitted fns shared per api)
    _run(PagedEngine(api, params, n_slots=2, max_len=MAX_LEN, page_size=PS),
         _prompts(), 4)

    syncs = {}
    for level in ("default", "counters"):
        eng = PagedEngine(api, params, n_slots=2, max_len=MAX_LEN,
                          page_size=PS, telemetry=Telemetry(level=level))
        _run(eng, _prompts(), 4)
        assert sum(eng.trace_counts().values()) == 0, level
        syncs[level] = eng.telemetry.registry.counter("device_syncs").value
    assert syncs["default"] == syncs["counters"] > 0

    # counters level keeps the stats surface but skips the detail
    assert len(eng.telemetry.timelines) == 0
    assert len(eng.telemetry.journal) == 0
    assert eng.stats["decode_ticks"] > 0


@pytest.mark.parametrize("depth,profile", [(2, False), (1, True)], ids=["depth2", "profile_sync"])
def test_launch_histograms_count_engine_launches(api_params, depth, profile):
    """One ``prefill_launch_s`` observation per batched prefill launch and
    one ``decode_tick_s`` observation per decode tick, whether the chunked
    engine runs pipelined (depth 2: launch spans) or synchronous
    (``profile_sync``: full device spans)."""
    api, params = api_params
    eng = PagedEngine(api, params, n_slots=2, max_len=MAX_LEN, page_size=PS,
                      chunked_prefill=True, prefill_chunk=PS,
                      pipeline_depth=depth, profile_sync=profile)
    _run(eng, _prompts(), 4)
    assert eng.stats["prefill_launches"] > 0 and eng.stats["decode_ticks"] > 0
    assert eng.telemetry.h_prefill.count == eng.stats["prefill_launches"]
    assert eng.telemetry.h_decode.count == eng.stats["decode_ticks"]


def test_guards_add_no_traces_or_syncs(api_params):
    """The robustness guards (NaN guard + an invariant audit every 4 ticks)
    against both off, on the same warm workload: identical tokens,
    identical device-sync counts, zero retraces, and every periodic audit
    clean."""
    api, params = api_params

    def guarded(on):
        return PagedEngine(api, params, n_slots=2, max_len=MAX_LEN, page_size=PS,
                           nan_guard=on, audit_every=4 if on else 0)

    for on in (True, False):  # warm each variant's shape buckets
        _run(guarded(on), _prompts(), 4)
    outs, syncs = {}, {}
    for on in (True, False):
        eng = guarded(on)
        outs[on] = {rid: r.out for rid, r in _run(eng, _prompts(), 4).items()}
        assert sum(eng.trace_counts().values()) == 0, on
        syncs[on] = eng.telemetry.registry.counter("device_syncs").value
        if on:
            assert eng._last_audit is not None and eng._last_audit.ok
            assert eng.health()["counters"]["audit_failures"] == 0
    assert outs[True] == outs[False]
    assert syncs[True] == syncs[False] > 0


def test_preemption_timeline_single_submit_two_admits(api_params):
    """A preempted-and-resumed request keeps ONE timeline: one submit,
    an admit per (re)admission, TTFT measured from the original submit."""
    api, params = api_params
    eng = PagedEngine(api, params, n_slots=2, max_len=MAX_LEN, page_size=PS,
                      n_pages=6, watermark=1)
    fin = _run(eng, _prompts((9, 7)), 10)
    assert eng.stats["preemptions"] >= 1
    assert len(fin) == 2

    tls = [tl for tl in eng.telemetry.timelines]
    assert len(tls) == 2  # resubmission reuses the timeline — no duplicate
    assert len({tl.rid for tl in tls}) == 2
    for tl in tls:  # every emitted token counted, preempted or not
        assert tl.n_tokens == len(fin[tl.rid].out)
    pre = [tl for tl in tls if tl.preemptions > 0]
    assert pre, "forced preemption left no preempted timeline"
    for tl in pre:
        assert len(tl.admits) == 1 + tl.preemptions
        assert tl.admits == sorted(tl.admits)
        # TTFT spans the preemption: anchored at the ORIGINAL submission
        assert tl.ttft() == pytest.approx(tl.t_first - tl.t_submit)
        assert tl.t_submit <= tl.admits[0] <= tl.t_first
    # queue time observed once per admission, preempted or not
    total_admits = sum(len(tl.admits) for tl in tls)
    assert eng.telemetry.h_queue.count == total_admits


def test_fork_timelines_independent_with_shared_prefill(api_params):
    """Forked siblings: independent timelines (own tokens/TTFT) that start
    from the parent's chunks — one prefill served every sibling."""
    api, params = api_params
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, CFG.vocab, size=PS + 3).astype(np.int32)
    eng = PagedEngine(api, params, n_slots=3, max_len=MAX_LEN, page_size=PS)
    eng.submit(Request(rid=0, prompt=prompt, max_new=4, n_samples=3,
                       sampling=SamplingParams(temperature=0.8, seed=11)))
    finished, _ = eng.run_to_completion()
    assert len(finished) == 3 and all(r.error is None for r in finished)

    tls = list(eng.telemetry.timelines)
    assert len(tls) == 3
    parent = next(tl for tl in tls if tl.sample_idx == 0)
    children = [tl for tl in tls if tl.sample_idx != 0]
    assert len(children) == 2
    for ch in children:
        assert ch is not parent
        assert ch.chunks == parent.chunks and ch.chunks  # one prefill
        assert ch.t_submit == parent.t_submit  # sibling existed at submit
        assert ch.ttft() is not None
    # each sibling decodes its own tokens on its own timeline
    out_by_sample = {r.sample_idx: r.out for r in finished}
    for tl in tls:
        assert tl.n_tokens == len(out_by_sample[tl.sample_idx])
    assert eng.telemetry.h_ttft.count == 3


# ------------------------------------------------------------------ spans
def _spans(journal, name=None):
    return [r for r in journal._buf
            if r[0] == "span" and (name is None or r[1] == name)]


@pytest.mark.parametrize("name, hist", [
    ("prefill_launch", "prefill_launch_s"),
    ("decode_tick", "decode_tick_s"),
    ("decode_sync", "decode_sync_s"),
    ("admit", None),
])
def test_span_records_args_and_its_histogram(name, hist):
    tel = Telemetry()
    with tel.span(name, tick=3) as args:
        args["late"] = 7  # an argument the region learns inside
    (rec,) = _spans(tel.journal)
    kind, rname, _cat, _tid, t0, t1, rargs, _seq = rec
    assert rname == name and t1 >= t0
    assert rargs == {"tick": 3, "late": 7}
    counts = {n: h.count for n, h in tel.registry.histograms.items()}
    for h, n in counts.items():
        assert n == (1 if h == hist else 0), h
    if hist is not None:
        assert tel.registry.histograms[hist].sum == pytest.approx(t1 - t0)


def test_span_nesting_on_one_tid():
    """The tick and admission spans share the host-scheduling track and
    export as properly nested B/E pairs."""
    tel = Telemetry()
    with tel.span("engine_step", tick=1):
        with tel.span("admit") as a:
            a["admitted"] = 0
        with tel.span("decode_tick", n_active=1):
            pass
    recs = {r[1]: r for r in _spans(tel.journal)}
    assert recs["engine_step"][3] == recs["admit"][3] == TID_HOST
    assert recs["decode_tick"][3] == TID_DEVICE
    outer, inner = recs["engine_step"], recs["admit"]
    assert outer[4] <= inner[4] <= inner[5] <= outer[5]
    host = [(e["ph"], e["name"])
            for e in tel.journal.to_chrome_trace()["traceEvents"]
            if e["ph"] in "BE" and e["tid"] == TID_HOST]
    assert host == [("B", "engine_step"), ("B", "admit"),
                    ("E", "admit"), ("E", "engine_step")]


def test_span_leaves_no_record_when_the_region_raises():
    tel = Telemetry()
    with pytest.raises(RuntimeError):
        with tel.span("prefill_launch", tokens=1):
            raise RuntimeError("launch failed")
    assert len(tel.journal) == 0 and tel.h_prefill.count == 0
    with tel.span("prefill_launch", tokens=1):  # the next span still works
        pass
    assert len(tel.journal) == 1


def _chunked_engine(api, params, **kw):
    return PagedEngine(api, params, n_slots=4, max_len=MAX_LEN, page_size=PS,
                       chunked_prefill=True, prefill_chunk=PS,
                       pipeline_depth=2, **kw)


def test_engine_span_args_count_launched_rows(api_params):
    """Chunked, depth 2: every decode launch packs all n_slots rows, its
    ``tick`` matches one ``decode_sync``, and each chunk launch reports
    its pow2 batch bucket, token bucket and whether it blocked."""
    api, params = api_params
    eng = _chunked_engine(api, params)
    _run(eng, _prompts((5, 19, 12)), 5)
    j = eng.telemetry.journal
    ticks = eng.stats["decode_ticks"]
    dec = [r[6] for r in _spans(j, "decode_tick")]
    assert len(dec) == ticks > 0
    assert sum(a["rows_launched"] for a in dec) == eng.n_slots * ticks
    assert all(1 <= a["n_active"] <= a["rows_launched"] for a in dec)
    sync_ticks = [r[6]["tick"] for r in _spans(j, "decode_sync")]
    assert sorted(sync_ticks) == sorted(a["tick"] for a in dec)
    assert len(set(sync_ticks)) == len(sync_ticks)

    pre = [r[6] for r in _spans(j, "prefill_launch")]
    assert len(pre) == eng.stats["prefill_launches"]
    assert sum(a["tokens"] for a in pre) == eng.stats["prefill_tokens"]
    for a in pre:
        rows, bucket = a["rows_launched"], a["chunk_bucket"]
        assert rows & (rows - 1) == 0 and a["slots"] <= rows
        assert a["tokens"] <= rows * bucket and bucket <= PS
    # a prompt finishes on 3 launches at least (one per request)
    assert sum(a["synced"] for a in pre) >= 3

    steps = [r[6]["tick"] for r in _spans(j, "engine_step")]
    assert steps == list(range(1, len(steps) + 1))
    assert len(_spans(j, "admit")) == len(steps)
    assert sum(r[6]["admitted"] for r in _spans(j, "admit")) == 3
    # each launch and sync nests inside the engine_step of its tick
    step_span = {r[6]["tick"]: r for r in _spans(j, "engine_step")}
    for r in _spans(j, "decode_tick") + _spans(j, "prefill_launch"):
        outer = step_span[r[6]["tick"]]
        assert outer[4] <= r[4] <= r[5] <= outer[5]


def test_engine_span_grid_steps_match_the_launched_lengths(api_params):
    """``grid_steps`` on ``decode_tick`` and ``prefill_launch`` is the
    page-gather grid a layer of that launch runs, Σ clip(ceil(kv/ps), 1,
    MAXP) over the launched rows: counted here from the packed arrays the
    launches carry (decode: kv = length + 1; chunk: kv = n_past + bucket,
    padding rows included)."""
    from repro.kernels.common import page_counts

    api, params = api_params
    eng = _chunked_engine(api, params)
    want = {"decode_tick": [], "prefill_launch": []}
    decode, chunk = eng._decode, eng._chunk_step

    def counted(packed, kv_col, extra):
        p = np.asarray(packed)
        return int(page_counts(p[:, kv_col] + extra, PS, eng.tables.shape[1]).sum())

    def spy_decode(params_, pool, packed, chain):
        want["decode_tick"].append(counted(packed, 2, 1))
        return decode(params_, pool, packed, chain)

    def spy_chunk(params_, packed, c, n_cp):
        want["prefill_launch"].append(counted(packed, c, c))
        return chunk(params_, packed, c, n_cp)

    eng._decode, eng._chunk_step = spy_decode, spy_chunk
    _run(eng, _prompts((5, 19, 12)), 5)
    for name, steps in want.items():
        got = [r[6]["grid_steps"] for r in _spans(eng.telemetry.journal, name)]
        assert got == steps and len(steps) > 0, name
    dec = [r[6] for r in _spans(eng.telemetry.journal, "decode_tick")]
    # idle rows take one step each; live rows their pages
    assert all(a["rows_launched"] < a["grid_steps"] for a in dec if a["n_active"] > 1)


def test_engine_spans_reach_the_profiler(api_params, tmp_path):
    """At the default level the engine's spans are TraceAnnotations: a
    profile taken around a few steps holds them on the host plane."""
    from jax.profiler import ProfileData

    api, params = api_params
    eng = _chunked_engine(api, params)
    _run(eng, _prompts((9,)), 2)  # warm the programs outside the trace
    for i, p in enumerate(_prompts((13, 6))):
        eng.submit(Request(rid=10 + i, prompt=p, max_new=4))
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(4):
            eng.step()
    eng.run_to_completion()
    (path,) = list(tmp_path.rglob("*.xplane.pb"))
    names = {
        e.name
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
    }
    for span in ("engine_step", "admit", "prefill_launch", "decode_tick",
                 "decode_sync"):
        assert span in names, span


def test_step_programs_carry_their_names(api_params):
    """The lowered decode and chunk steps are modules named after their
    jit keys, so a device trace tells the two programs apart."""
    api, params = api_params
    eng = _chunked_engine(api, params)
    low = eng.lower_steps()
    assert low["decode"].as_text().startswith("module @jit_paged_decode_fused")
    assert low["chunk"].as_text().startswith("module @jit_chunk_step")


def test_trace_checker_requires_nested_spans(api_params, tmp_path):
    """tools/check_telemetry.py passes the engine's export (spans nested
    per tid) and fails a trace whose spans cross."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).parents[1] / "tools" / "check_telemetry.py"
    spec = importlib.util.spec_from_file_location("check_telemetry", path)
    check = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(check)

    api, params = api_params
    eng = _chunked_engine(api, params)
    _run(eng, _prompts((11,)), 3)
    good = tmp_path / "good.json"
    eng.telemetry.dump_trace(str(good))
    check.check_trace(str(good))

    j = TraceJournal()
    j.span("outer", 1.0, 3.0, tid=TID_HOST)
    j.span("crossing", 2.0, 4.0, tid=TID_HOST)
    bad = tmp_path / "bad.json"
    j.dump(str(bad))
    with pytest.raises(SystemExit):
        check.check_trace(str(bad))
