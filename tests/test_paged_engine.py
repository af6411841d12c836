"""Paged serving engine: greedy-token equivalence with the contiguous
continuous-batching engine (all cache kinds), prefix-cache sharing /
refcount / eviction, preemption-by-eviction, and allocator unit tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke
from repro.core.bcq import BCQConfig
from repro.core.calibrate import default_universal_codebooks
from repro.launch.batching import ContinuousBatcher
from repro.models import zoo
from repro.models.layers import Runtime
from repro.serving.engine import (
    PagedEngine,
    PagePoolExhaustedError,
    PromptTooLongError,
)
from repro.serving.generate import Request, greedy_generate
from repro.serving.pages import PagePool
from repro.serving.prefix import PrefixCache, chunk_hashes

CFG = get_smoke("gpt3_126m")
BCQ = BCQConfig()
CB = default_universal_codebooks(BCQ).as_jnp()
MAX_LEN, PS = 32, 8


def _api_params(kind):
    rt = Runtime(
        quant_mode="none", compute_dtype=jnp.float32, param_dtype=jnp.float32,
        cache_kind=kind,
    )
    api = zoo.build(CFG, rt)
    params = api.init(jax.random.PRNGKey(0))
    params["codebooks"] = CB  # cache quantization path needs the codebooks
    return api, params


def _prompts(lengths=(5, 9, 7)):
    rng = np.random.default_rng(0)
    return [rng.integers(0, CFG.vocab, size=n).astype(np.int32) for n in lengths]


def _run(engine, prompts, n_new):
    for i, p in enumerate(prompts):
        engine.submit(Request(rid=i, prompt=p, max_new=n_new))
    finished, ticks = engine.run_to_completion()
    return {r.rid: r.out for r in finished}, ticks


# --------------------------------------------------------- token equivalence
@pytest.mark.parametrize("kind", ("bf16", "int8", "bcq4"))
def test_paged_matches_contiguous_engine(kind):
    """Token-for-token identical greedy outputs, every cache kind."""
    api, params = _api_params(kind)
    prompts, n_new = _prompts(), 4
    ref, _ = _run(ContinuousBatcher(api, params, n_slots=2, max_len=MAX_LEN), prompts, n_new)
    got, ticks = _run(
        PagedEngine(api, params, n_slots=2, max_len=MAX_LEN, page_size=PS), prompts, n_new
    )
    assert set(got) == set(ref)
    for rid in ref:
        assert got[rid] == ref[rid], (kind, rid, got[rid], ref[rid])
    # mixed-depth slots decode in ONE fused tick each — never more ticks
    # than the position-grouped contiguous engine
    assert ticks <= sum(n_new + 1 for _ in prompts)


def test_prefix_sharing_and_reuse():
    """Identical full-page prompt prefixes share pages (refcounted), turn
    reclaimable on completion, and are revived by later requests."""
    api, params = _api_params("bf16")
    rng = np.random.default_rng(1)
    shared = rng.integers(0, CFG.vocab, size=2 * PS).astype(np.int32)  # 2 full pages
    p1 = np.concatenate([shared, rng.integers(0, CFG.vocab, size=3).astype(np.int32)])
    p2 = np.concatenate([shared, rng.integers(0, CFG.vocab, size=5).astype(np.int32)])

    eng = PagedEngine(api, params, n_slots=2, max_len=MAX_LEN, page_size=PS)
    eng.submit(Request(rid=0, prompt=p1, max_new=3))
    eng.submit(Request(rid=1, prompt=p2, max_new=3))
    eng._admit()
    assert eng.stats["prefix_hits"] == 2  # both shared pages hit by rid 1
    shared_pages = [int(x) for x in eng.tables[0][:2]]
    assert [int(x) for x in eng.tables[1][:2]] == shared_pages
    assert all(eng.pool_mgr.refcount[p] == 2 for p in shared_pages)

    eng.run_to_completion()
    # sequences done: shared pages at refcount 0 but parked reclaimable
    assert all(eng.pool_mgr.refcount[p] == 0 for p in shared_pages)
    assert eng.prefix.reclaimable_count() >= 2

    # a third request with the same prefix revives them without rewriting
    hits_before = eng.stats["prefix_hits"]
    eng.submit(Request(rid=2, prompt=p1, max_new=3))
    eng._admit()
    assert eng.stats["prefix_hits"] == hits_before + 2
    assert [int(x) for x in eng.tables[0][:2]] == shared_pages or \
           [int(x) for x in eng.tables[1][:2]] == shared_pages
    eng.run_to_completion()


def test_prefix_sharing_outputs_exact():
    """Sharing pages across prefix-identical requests does not change a
    single output token (sharing is bit-exact)."""
    api, params = _api_params("bcq4")
    rng = np.random.default_rng(2)
    shared = rng.integers(0, CFG.vocab, size=PS).astype(np.int32)
    prompts = [
        np.concatenate([shared, rng.integers(0, CFG.vocab, size=n).astype(np.int32)])
        for n in (2, 4)
    ]
    ref, _ = _run(
        PagedEngine(api, params, n_slots=2, max_len=MAX_LEN, page_size=PS,
                    prefix_caching=False),
        prompts, 3,
    )
    got, _ = _run(
        PagedEngine(api, params, n_slots=2, max_len=MAX_LEN, page_size=PS),
        prompts, 3,
    )
    assert got == ref


def test_preemption_by_eviction_is_greedy_exact():
    """With a pool too small for both sequences, the youngest is preempted
    (pages evicted, recompute-requeued) and still finishes with exactly the
    reference tokens."""
    api, params = _api_params("bf16")
    prompts = _prompts((9, 7))
    n_new = 10
    ref, _ = _run(
        PagedEngine(api, params, n_slots=2, max_len=MAX_LEN, page_size=PS), prompts, n_new
    )
    # 1 null + 4 real pages: both sequences admit (2+1 prompt pages) but
    # together need 6 pages by the end of decode, so the pool must run dry
    # mid-decode and evict the younger sequence
    eng = PagedEngine(
        api, params, n_slots=2, max_len=MAX_LEN, page_size=PS,
        n_pages=5, watermark=1, prefix_caching=False,
    )
    got, _ = _run(eng, prompts, n_new)
    assert eng.stats["preemptions"] >= 1
    assert got == ref


def test_admission_control_watermark():
    """Admission blocks while the pool lacks prompt pages + watermark."""
    api, params = _api_params("bf16")
    eng = PagedEngine(
        api, params, n_slots=2, max_len=MAX_LEN, page_size=PS, n_pages=4, watermark=2
    )
    # 3 free pages, need 1 prompt page + 2 watermark → admits
    assert eng._try_admit(Request(rid=0, prompt=_prompts((5,))[0], max_new=2), 0)
    # 2 free pages left, next needs 2 + 2 → must be refused
    assert not eng._try_admit(Request(rid=1, prompt=_prompts((9,))[0], max_new=2), 1)


def test_refused_admission_does_not_orphan_reclaimable_pages():
    """A refused admission must leave reclaimable prefix pages parked (and
    stats untouched) — a rejected head-of-line request is re-scanned every
    tick and must not strand evictable memory at refcount 0."""
    api, params = _api_params("bf16")
    rng = np.random.default_rng(4)
    shared = rng.integers(0, CFG.vocab, size=2 * PS).astype(np.int32)
    eng = PagedEngine(api, params, n_slots=2, max_len=MAX_LEN, page_size=PS, n_pages=6)
    _run(eng, [np.concatenate([shared, shared[:3]])], 2)  # park 2 prefix pages
    assert eng.prefix.reclaimable_count() == 2
    hits_before = eng.stats["prefix_hits"]

    eng.watermark = 10  # force every admission to be refused
    big = Request(rid=9, prompt=np.concatenate([shared, shared[:5]]), max_new=2)
    for _ in range(3):  # re-scanned repeatedly, like a waiting head-of-line
        assert not eng._try_admit(big, 0)
    assert eng.prefix.reclaimable_count() == 2  # still parked, still evictable
    assert eng.stats["prefix_hits"] == hits_before  # no stat inflation
    assert all(eng.pool_mgr.refcount[p] == 0 for p in eng.prefix.reclaimable)

    eng.watermark = 1  # and the pages are still claimable afterwards
    assert eng._try_admit(big, 0)
    assert eng.stats["prefix_hits"] == hits_before + 2


@pytest.mark.parametrize("chunked", (False, True))
def test_refused_admission_is_side_effect_free(chunked):
    """The full non-mutating-peek contract: a refused _try_admit must not
    unpark reclaimable pages, reorder the prefix LRU, bump
    prefix_hits/prefix_misses (or any stat), touch refcounts, or leave
    anything in the slot/table state."""
    api, params = _api_params("bf16")
    rng = np.random.default_rng(7)
    a = rng.integers(0, CFG.vocab, size=2 * PS).astype(np.int32)
    b = rng.integers(0, CFG.vocab, size=2 * PS).astype(np.int32)
    eng = PagedEngine(
        api, params, n_slots=2, max_len=MAX_LEN, page_size=PS, n_pages=10,
        chunked_prefill=chunked, prefill_chunk=PS,
    )
    # park two distinct 2-page prefixes with a known LRU order (a older)
    _run(eng, [np.concatenate([a, a[:3]])], 2)
    _run(eng, [np.concatenate([b, b[:3]])], 2)
    assert eng.prefix.reclaimable_count() == 4

    lru_before = list(eng.prefix.reclaimable)
    stats_before = dict(eng.stats)
    refcounts_before = eng.pool_mgr.refcount.copy()
    free_before = list(eng.pool_mgr.free)
    tables_before = eng.tables.copy()

    eng.watermark = 10  # force refusal
    big = Request(rid=9, prompt=np.concatenate([a, a[:5]]), max_new=2)
    for _ in range(3):  # re-scanned repeatedly, like a waiting head-of-line
        assert not eng._try_admit(big, 0)

    assert list(eng.prefix.reclaimable) == lru_before  # order untouched
    assert dict(eng.stats) == stats_before  # incl. prefix_hits/misses
    np.testing.assert_array_equal(eng.pool_mgr.refcount, refcounts_before)
    assert list(eng.pool_mgr.free) == free_before
    np.testing.assert_array_equal(eng.tables, tables_before)
    assert all(s.req is None for s in eng.slots)


# ------------------------------------------------------------ typed errors
def test_prompt_too_long_error_non_chunked_only():
    """plen >= max_len: typed error from the non-chunked slab path; the
    chunked path has no such limit (its block tables grow)."""
    api, params = _api_params("bf16")
    long_prompt = _prompts((MAX_LEN,))[0]
    eng = PagedEngine(api, params, n_slots=1, max_len=MAX_LEN, page_size=PS)
    with pytest.raises(PromptTooLongError, match="chunked_prefill"):
        eng._try_admit(Request(rid=0, prompt=long_prompt, max_new=2), 0)

    eng_ck = PagedEngine(
        api, params, n_slots=1, max_len=MAX_LEN, page_size=PS, n_pages=12,
        chunked_prefill=True, prefill_chunk=PS,
    )
    got, _ = _run(eng_ck, [long_prompt], 2)
    assert len(got[0]) == 3  # served fine: first token + 2 decode tokens


def test_pool_exhausted_error_names_watermark():
    """An unserveable head-of-line request surfaces as a typed allocator
    error whose message names the watermark (shed_stuck=False opts back
    into the old fail-stop raise for capacity-planning tests)."""
    api, params = _api_params("bf16")
    eng = PagedEngine(
        api, params, n_slots=1, max_len=MAX_LEN, page_size=PS, n_pages=3,
        watermark=2, shed_stuck=False,
    )
    eng.submit(Request(rid=0, prompt=_prompts((9,))[0], max_new=2))
    with pytest.raises(PagePoolExhaustedError, match="watermark=2"):
        eng.run_to_completion()


def test_stuck_head_of_line_request_is_shed_not_fatal():
    """Default policy: an impossible head-of-line request is shed with a
    typed error and the loop keeps serving the requests behind it."""
    api, params = _api_params("bf16")
    eng = PagedEngine(
        api, params, n_slots=2, max_len=MAX_LEN, page_size=PS, n_pages=3,
        watermark=1,
    )
    big = Request(rid=0, prompt=_prompts((9,))[0], max_new=2)
    small = Request(rid=1, prompt=_prompts((4,))[0], max_new=2)
    eng.submit(big)
    eng.submit(small)
    finished, _ = eng.run_to_completion()
    assert big.error is not None and big.error.kind == "shed"
    assert "watermark=1" in big.error
    assert small.done and small.error is None and len(small.out) == 3
    assert eng.telemetry.registry.counter("shed").value == 1
    # nothing left referenced by the shed path
    assert int((eng.pool_mgr.refcount > 0).sum()) == 0


def test_stats_accounting_after_forced_preemption():
    """prefix_evictions / preemptions / peak_pages after a run that forces
    both a reclaimable-page eviction and a preemption."""
    api, params = _api_params("bf16")
    rng = np.random.default_rng(11)
    parked = rng.integers(0, CFG.vocab, size=2 * PS).astype(np.int32)
    eng = PagedEngine(
        api, params, n_slots=2, max_len=MAX_LEN, page_size=PS,
        n_pages=6, watermark=1,
    )
    # park 2 registered prefix pages (refcount 0, kept for reuse)
    _run(eng, [np.concatenate([parked, parked[:3]])], 2)
    assert eng.prefix.reclaimable_count() == 2
    assert eng.stats["preemptions"] == 0 and eng.stats["prefix_evictions"] == 0

    # two fresh long-decode sequences: admitting + decoding them must first
    # evict the parked pages (allocator dry) and then preempt the youngest
    prompts = _prompts((9, 7))
    ref, _ = _run(
        PagedEngine(api, params, n_slots=2, max_len=MAX_LEN, page_size=PS), prompts, 10
    )
    got, _ = _run(eng, prompts, 10)
    assert got == ref  # eviction + preemption stay greedy-exact
    assert eng.stats["prefix_evictions"] == 2  # both parked pages reclaimed
    assert eng.stats["preemptions"] >= 1
    assert eng.stats["peak_pages"] == 5  # ran the 5-real-page pool dry


# ------------------------------------------------------------- unit pieces
def test_page_pool_alloc_ref_release():
    pool = PagePool(4)
    a, b_ = pool.alloc(), pool.alloc()
    assert {a, b_} <= {1, 2, 3} and pool.available() == 1
    pool.ref(a)
    assert not pool.deref(a) and pool.refcount[a] == 1
    assert pool.deref(a)
    pool.release(a)
    assert pool.available() == 2
    assert pool.used() == 1  # only b_ held
    assert pool.alloc() is not None and pool.alloc() is not None
    assert pool.alloc() is None  # dry


def test_prefix_cache_lru_eviction():
    pc = PrefixCache()
    hashes = chunk_hashes(list(range(24)), 8)  # 3 full chunks, chained
    assert len(hashes) == 3 and len(set(hashes)) == 3
    for h, pid in zip(hashes, (1, 2, 3)):
        pc.register(h, pid)
        pc.mark_reclaimable(pid)
    assert pc.lookup(hashes[0]) == 1  # revived → no longer reclaimable
    assert pc.reclaimable_count() == 2
    assert pc.evict_one() == 2  # LRU order
    assert pc.lookup(hashes[1]) is None  # evicted registration is gone
    pc.mark_reclaimable(1)
    assert pc.evict_one() == 3 and pc.evict_one() == 1 and pc.evict_one() is None


def test_chunk_hash_is_prefix_conditioned():
    """Identical chunk content under different prefixes must NOT collide."""
    a = chunk_hashes([1, 2, 3, 4, 9, 9, 9, 9], 4)
    b = chunk_hashes([5, 6, 7, 8, 9, 9, 9, 9], 4)
    assert a[1] != b[1]


def test_chunk_hash_is_process_stable():
    """Prefix keys must be reproducible across processes: a blake2b chain
    over token bytes, NOT the builtin hash() (which PYTHONHASHSEED salts
    per process, breaking warm-bench comparisons and any cross-process
    sharing).  Pinned digests = the cross-process contract."""
    got = chunk_hashes(np.arange(8, dtype=np.int64), 4)
    assert [h.hex() for h in got] == [
        "61abbbbadcb5a29f38974c1405255595",
        "ceb7796f6f9059e045e6ec8c7df2e484",
    ]
    # int dtype of the prompt must not change the key (engine uses int64,
    # requests arrive int32)
    assert chunk_hashes(np.arange(8, dtype=np.int32), 4) == got
    assert chunk_hashes(list(range(8)), 4) == got


def test_prefix_hit_rate_counts_cacheable_pages_only():
    """Regression: a 100%-warm resubmission of a 17-token prompt at
    page_size=16 must report a 100% hit rate — the trailing partial page
    (never cacheable by design) used to be charged as a miss, reporting
    50%."""
    api, params = _api_params("bf16")
    eng = PagedEngine(api, params, n_slots=1, max_len=32, page_size=16, n_pages=8)
    prompt = _prompts((17,))[0]
    _run(eng, [prompt], 2)  # cold: the one full page is a genuine miss
    assert (eng.stats["prefix_hits"], eng.stats["prefix_misses"]) == (0, 1)
    eng.submit(Request(rid=1, prompt=prompt, max_new=2))
    eng.run_to_completion()  # warm: full page hits, partial page uncounted
    assert (eng.stats["prefix_hits"], eng.stats["prefix_misses"]) == (1, 1)


def test_prefix_hit_rate_chunked_trimmed_hit_not_a_miss():
    """Chunked mode trims the final full-page hit of a page-aligned
    prompt (to keep last-position logits) — that deliberate trim must not
    count as a miss on a warm resubmission."""
    api, params = _api_params("bf16")
    eng = PagedEngine(
        api, params, n_slots=1, max_len=MAX_LEN, page_size=PS,
        chunked_prefill=True, prefill_chunk=PS,
    )
    prompt = _prompts((2 * PS,))[0]  # exactly 2 full pages
    _run(eng, [prompt], 2)
    # cacheable = (plen-1)//ps = 1 (the final page is the trimmed one)
    assert (eng.stats["prefix_hits"], eng.stats["prefix_misses"]) == (0, 1)
    eng.submit(Request(rid=1, prompt=prompt, max_new=2))
    eng.run_to_completion()
    assert (eng.stats["prefix_hits"], eng.stats["prefix_misses"]) == (1, 1)


# ------------------------------------------------------ submit-time validation
def test_oversized_prompt_rejected_at_submit_cannot_dos_the_batch():
    """Regression: PromptTooLongError used to escape step() mid-flight,
    abandoning every other in-flight request.  submit() now rejects the
    bad request into ``finished`` with an error marker and the rest of
    the batch completes token-exactly."""
    api, params = _api_params("bf16")
    good = _prompts((5, 9))
    ref, _ = _run(
        PagedEngine(api, params, n_slots=2, max_len=MAX_LEN, page_size=PS), good, 3
    )

    eng = PagedEngine(api, params, n_slots=2, max_len=MAX_LEN, page_size=PS)
    bad = Request(rid=99, prompt=_prompts((MAX_LEN,))[0], max_new=3)
    eng.submit(Request(rid=0, prompt=good[0], max_new=3))
    eng.submit(bad)  # rejected immediately — never enters the queue
    eng.submit(Request(rid=1, prompt=good[1], max_new=3))
    finished, _ = eng.run_to_completion()

    assert bad in finished and bad.error is not None and bad.out == []
    assert "chunked_prefill" in bad.error  # actionable message
    got = {r.rid: r.out for r in finished if r.error is None}
    assert got == ref  # surrounding requests unharmed, token-exact


# ------------------------------------------------- bucketed contiguous reads
def test_kv_bucketed_decode_matches_full_read():
    """greedy_generate(kv_bucket=8) — bounded cache dequantization — is
    token-identical to full-cache reads."""
    api, params = _api_params("int8")
    rng = np.random.default_rng(3)
    prompts = jnp.asarray(rng.integers(0, CFG.vocab, size=(2, 6)), jnp.int32)
    full = greedy_generate(api, params, prompts, 6, MAX_LEN)
    bucketed = greedy_generate(api, params, prompts, 6, MAX_LEN, kv_bucket=8)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(bucketed))


@pytest.mark.parametrize("chunked", (False, True))
def test_prompt_logits_and_lowered_steps(chunked):
    """``keep_prompt_logits`` hands back the logits the first token was
    picked from (requests without it keep none), and ``lower_steps``
    lowers the engine's own decode (and chunk) step."""
    api, params = _api_params("bf16")
    eng = PagedEngine(
        api, params, n_slots=2, max_len=MAX_LEN, page_size=PS,
        chunked_prefill=chunked, prefill_chunk=PS,
    )
    p1, p2 = _prompts((11, 6))
    kept = Request(rid=0, prompt=p1, max_new=2, keep_prompt_logits=True)
    plain = Request(rid=1, prompt=p2, max_new=2)
    eng.submit(kept)
    eng.submit(plain)
    eng.run_to_completion()
    want = api.prefill_fn(params, {"tokens": jnp.asarray(p1)[None]}, MAX_LEN)[0][0, -1]
    assert kept.prompt_logits.shape == (CFG.vocab,)
    assert kept.prompt_logits.dtype == np.float32
    assert int(kept.prompt_logits.argmax()) == kept.out[0]
    np.testing.assert_allclose(kept.prompt_logits, np.asarray(want), rtol=1e-5, atol=1e-5)
    assert plain.prompt_logits is None
    lowered = eng.lower_steps()
    assert set(lowered) == ({"decode", "chunk"} if chunked else {"decode"})
    for step in lowered.values():
        assert step.compile().as_text()
