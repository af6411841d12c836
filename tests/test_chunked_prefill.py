"""Chunked prefill: Pallas kernel == oracle (interpret mode) across page
kinds / chunk sizes / ragged prefix lengths, and PagedEngine chunked
admission token-for-token identical to full-prompt prefill for every cache
kind and prefix-hit fraction (0%, partial, 100%), including mixed
prefill/decode ticks and prompts longer than max_len."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke
from repro.core.bcq import BCQConfig
from repro.core.calibrate import default_universal_codebooks
from repro.kernels import ref as kref
from repro.kernels.chunked_prefill import chunked_prefill
from repro.models import layers, zoo
from repro.models.layers import Runtime
from repro.serving.engine import PagedEngine
from repro.serving.generate import Request

CFG = get_smoke("gpt3_126m")
BCQ = BCQConfig()
CB = default_universal_codebooks(BCQ).as_jnp()
MAX_LEN, PS = 32, 8
P, HKV, D = 8, 2, 32  # kernel-test pool shape


# ------------------------------------------------------------ kernel == ref
def _pool(kind, key=0):
    pool = layers.cache_init(P, PS, HKV, D, kind, BCQ)
    k = jax.random.normal(jax.random.PRNGKey(key), (P, PS, HKV, D))
    v = jax.random.normal(jax.random.PRNGKey(key + 1), (P, PS, HKV, D))
    return layers.cache_write(pool, k, v, 0, kind, BCQ, CB)


@pytest.mark.parametrize("kind", ("bf16", "int8", "bcq4"))
@pytest.mark.parametrize("h", (2, 4))  # MHA and 2× GQA replication
def test_kernel_matches_reference(kind, h):
    """Ragged hit-chain lengths (n_past 0 / mid-page-multiple / deep) and
    several chunk sizes, one pool per kind."""
    pool = _pool(kind)
    rng = np.random.default_rng(0)
    for c in (1, 5, 8):  # decode-like, ragged tail, full-page chunk
        b, maxp = 3, 4
        bt = jnp.asarray(rng.integers(1, P, (b, maxp)), jnp.int32)
        # chunk starts page-aligned in the engine, but the kernel only
        # needs n_past + C to fit the gathered pages — exercise both
        n_past = jnp.asarray([0, PS, (maxp - 1) * PS - c], jnp.int32)
        q = jax.random.normal(jax.random.PRNGKey(7 + c), (b, c, h, D))
        ref = kref.chunked_prefill_ref(q, pool, bt, n_past, kind, BCQ, CB)
        got = chunked_prefill(q, pool, bt, n_past, kind, BCQ, CB, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=2e-5, rtol=2e-5
        )


def test_kernel_causal_within_chunk():
    """Chunk token c must not see chunk tokens > c: corrupting the page
    region holding later chunk tokens leaves earlier rows unchanged."""
    pool = _pool("bf16")
    bt = jnp.asarray([[1, 2, 0]], jnp.int32)
    n_past = jnp.asarray([PS], jnp.int32)  # chunk occupies page 2 onward
    q = jax.random.normal(jax.random.PRNGKey(3), (1, 4, HKV, D))
    out_a = chunked_prefill(q, pool, bt, n_past, "bf16", BCQ, interpret=True)
    pool2 = dict(pool)
    pool2["k"] = pool["k"].at[2, 2:].set(777.0)  # tokens at positions >= n_past+2
    pool2["v"] = pool["v"].at[2, 2:].set(777.0)
    out_b = chunked_prefill(q, pool2, bt, n_past, "bf16", BCQ, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_a[:, :2]), np.asarray(out_b[:, :2]))
    assert not np.array_equal(np.asarray(out_a[:, 2:]), np.asarray(out_b[:, 2:]))


def test_kernel_prefix_pages_visible_to_whole_chunk():
    """All prefix tokens (positions < n_past) influence every chunk row."""
    pool = _pool("bf16")
    bt = jnp.asarray([[3, 1, 0]], jnp.int32)
    n_past = jnp.asarray([PS], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(4), (1, 3, HKV, D))
    out_a = chunked_prefill(q, pool, bt, n_past, "bf16", BCQ, interpret=True)
    pool2 = dict(pool)
    pool2["k"] = pool["k"].at[3, PS - 1].set(9.0)  # last prefix token
    out_b = chunked_prefill(q, pool2, bt, n_past, "bf16", BCQ, interpret=True)
    assert not np.array_equal(np.asarray(out_a), np.asarray(out_b))


# --------------------------------------------------- model chunk attention
def test_model_kernel_path_matches_jnp_path():
    """prefill_from_pages with Runtime.paged_kernel (Pallas chunked-prefill
    kernel, interpret on CPU) agrees with the jnp gather path."""
    outs = {}
    for paged_kernel in (False, True):
        rt = Runtime(
            quant_mode="none", compute_dtype=jnp.float32, param_dtype=jnp.float32,
            cache_kind="bcq4", paged_kernel=paged_kernel,
        )
        api = zoo.build(CFG, rt)
        params = api.init(jax.random.PRNGKey(0))
        params["codebooks"] = CB
        pool = api.pool_init(6, PS)
        tokens = jnp.asarray(np.arange(1, 6)[None, :], jnp.int32)
        bt = jnp.asarray([[1, 0, 0, 0]], jnp.int32)
        logits, _ = api.prefill_from_pages_fn(
            params, tokens, pool, bt, jnp.asarray([0], jnp.int32),
            jnp.asarray([[1]], jnp.int32),
        )
        outs[paged_kernel] = np.asarray(logits)
    np.testing.assert_allclose(outs[False], outs[True], atol=3e-5, rtol=3e-5)


# ------------------------------------------------------ engine equivalence
def _api_params(kind):
    rt = Runtime(
        quant_mode="none", compute_dtype=jnp.float32, param_dtype=jnp.float32,
        cache_kind=kind,
    )
    api = zoo.build(CFG, rt)
    params = api.init(jax.random.PRNGKey(0))
    params["codebooks"] = CB
    return api, params


def _run(engine, reqs):
    for r in reqs:
        engine.submit(r)
    finished, ticks = engine.run_to_completion()
    return {r.rid: list(r.out) for r in finished}, ticks


def _mix(rng):
    """0% / partial / would-be-100% prefix-hit prompts in one batch."""
    shared = rng.integers(0, CFG.vocab, size=PS).astype(np.int32)
    return [
        np.concatenate([shared, rng.integers(0, CFG.vocab, size=3).astype(np.int32)]),
        np.concatenate([shared, rng.integers(0, CFG.vocab, size=5).astype(np.int32)]),
        rng.integers(0, CFG.vocab, size=17).astype(np.int32),
    ]


@pytest.mark.parametrize("kind", ("bf16", "int8", "bcq4"))
def test_chunked_engine_matches_full_prefill(kind):
    """Cold pass (0% and partial hits) AND a warm 100%-hit resubmission are
    token-for-token identical to the full-prompt-prefill engine."""
    api, params = _api_params(kind)
    prompts = _mix(np.random.default_rng(0))

    ref_eng = PagedEngine(api, params, n_slots=2, max_len=MAX_LEN, page_size=PS)
    ref, _ = _run(ref_eng, [Request(rid=i, prompt=p, max_new=4) for i, p in enumerate(prompts)])
    ref_eng.submit(Request(rid=9, prompt=prompts[0].copy(), max_new=4))
    ref_eng.run_to_completion()
    ref[9] = list(next(r.out for r in ref_eng.finished if r.rid == 9))

    eng = PagedEngine(
        api, params, n_slots=2, max_len=MAX_LEN, page_size=PS,
        chunked_prefill=True, prefill_chunk=PS,
    )
    got, _ = _run(eng, [Request(rid=i, prompt=p, max_new=4) for i, p in enumerate(prompts)])
    cold_tokens = eng.stats["prefill_tokens"]

    # warm resubmission: every full page of prompts[0] is now cached — the
    # engine must run prefill over ONLY the final partial page (zero
    # attention FLOPs over the cached pages) and still match exactly
    eng.submit(Request(rid=9, prompt=prompts[0].copy(), max_new=4))
    eng.run_to_completion()
    got[9] = list(next(r.out for r in eng.finished if r.rid == 9))
    plen = len(prompts[0])
    suffix = plen - (plen - 1) // PS * PS
    assert eng.stats["prefix_hits"] >= (plen - 1) // PS
    assert eng.stats["prefill_tokens"] - cold_tokens == suffix
    assert got == ref, (kind, got, ref)


def test_chunked_engine_chunk_size_invariance():
    """Greedy outputs are identical for any page-multiple chunk size."""
    api, params = _api_params("bf16")
    prompts = _mix(np.random.default_rng(1))
    outs = []
    for chunk in (PS, 2 * PS, 3 * PS):
        eng = PagedEngine(
            api, params, n_slots=2, max_len=MAX_LEN, page_size=PS,
            chunked_prefill=True, prefill_chunk=chunk,
        )
        got, _ = _run(eng, [Request(rid=i, prompt=p, max_new=4) for i, p in enumerate(prompts)])
        outs.append(got)
    assert outs[0] == outs[1] == outs[2]


def test_mixed_prefill_decode_ticks():
    """While one slot prefills chunk-by-chunk, another keeps decoding — and
    outputs still match the non-chunked engine exactly."""
    api, params = _api_params("bf16")
    rng = np.random.default_rng(2)
    short = rng.integers(0, CFG.vocab, size=4).astype(np.int32)
    long = rng.integers(0, CFG.vocab, size=24).astype(np.int32)

    ref, _ = _run(
        PagedEngine(api, params, n_slots=2, max_len=MAX_LEN, page_size=PS),
        [Request(rid=0, prompt=short, max_new=6), Request(rid=1, prompt=long, max_new=3)],
    )
    eng = PagedEngine(
        api, params, n_slots=2, max_len=MAX_LEN, page_size=PS,
        chunked_prefill=True, prefill_chunk=PS,
    )
    got, _ = _run(
        eng,
        [Request(rid=0, prompt=short, max_new=6), Request(rid=1, prompt=long, max_new=3)],
    )
    # the long prompt needed 3 chunks; decode ticks for the short request
    # ran in the same window (interleaved, not serialized behind prefill)
    assert eng.stats["prefill_chunks"] >= 3 + 1
    assert eng.stats["decode_ticks"] > 0
    assert got == ref


def test_one_chunk_launch_per_engine_tick():
    """ALL prefilling slots ride ONE prefill_from_pages launch per tick —
    the launch count equals the number of prefill ticks, never the number
    of (slot, chunk) pairs — with token-for-token equivalence to the
    non-chunked reference engine preserved."""
    api, params = _api_params("bf16")
    rng = np.random.default_rng(5)
    prompts = [
        rng.integers(0, CFG.vocab, size=n).astype(np.int32) for n in (24, 20, 17)
    ]
    ref, _ = _run(
        PagedEngine(api, params, n_slots=3, max_len=MAX_LEN, page_size=PS),
        [Request(rid=i, prompt=p, max_new=4) for i, p in enumerate(prompts)],
    )

    eng = PagedEngine(
        api, params, n_slots=3, max_len=MAX_LEN, page_size=PS,
        chunked_prefill=True, prefill_chunk=PS,
    )
    calls = [0]
    inner = eng._chunk_step

    def counting(*args):
        calls[0] += 1
        return inner(*args)

    eng._chunk_step = counting
    got, _ = _run(eng, [Request(rid=i, prompt=p, max_new=4) for i, p in enumerate(prompts)])
    assert got == ref
    # 3 prompts × 3 chunks each = 9 chunks, but 3 slots prefill together:
    # one launch per tick, so far fewer launches than chunks
    assert calls[0] == eng.stats["prefill_launches"]
    assert eng.stats["prefill_chunks"] == 9
    assert calls[0] <= 4, (calls[0], eng.stats)


def test_retrace_count_bounded_by_buckets_not_requests():
    """Shape-bucketing regression: a mixed-length serving run traces each
    device step a BOUNDED (bucket-count) number of times — and a second
    wave of fresh lengths through the warmed engine adds ZERO traces
    (steady state stops retracing).  Before bucketing, every distinct
    tail-chunk length and every admission mix recompiled the chunk step:
    traces grew O(requests)."""
    api, params = _api_params("bf16")
    rng = np.random.default_rng(6)
    eng = PagedEngine(
        api, params, n_slots=2, max_len=MAX_LEN, page_size=PS, n_pages=40,
        chunked_prefill=True, prefill_chunk=2 * PS,
    )
    lengths_cold = (3, 5, 7, 9, 11, 14, 17, 19, 22, 25)
    reqs = [
        Request(rid=i, prompt=rng.integers(0, CFG.vocab, size=n).astype(np.int32),
                max_new=3)
        for i, n in enumerate(lengths_cold)
    ]
    _run(eng, reqs)
    cold = eng.trace_counts()
    # buckets: tail chunks round to pow2 (≤ log2(prefill_chunk)+1 token
    # shapes), prefill batch pads to pow2 (≤ log2(n_slots)+1), decode is
    # one fixed shape — an order of magnitude under one-per-request
    assert 0 < cold["chunk"] <= 8, cold
    assert cold["decode"] == 1, cold

    # second wave: same length mix, FRESH tokens (zero prefix hits, so the
    # prefill really runs again) — all shapes land in warmed buckets
    wave2 = [
        Request(rid=100 + i, prompt=rng.integers(0, CFG.vocab, size=n).astype(np.int32),
                max_new=3)
        for i, n in enumerate(lengths_cold)
    ]
    _run(eng, wave2)
    warm = eng.trace_counts()
    assert warm == cold, (cold, warm)  # steady state: zero new compilations

    # a second engine over the same api starts fully warm (shared jit
    # cache): the whole workload replays without a single compilation
    eng2 = PagedEngine(
        api, params, n_slots=2, max_len=MAX_LEN, page_size=PS, n_pages=40,
        chunked_prefill=True, prefill_chunk=2 * PS,
    )
    wave3 = [
        Request(rid=200 + i, prompt=rng.integers(0, CFG.vocab, size=n).astype(np.int32),
                max_new=3)
        for i, n in enumerate(lengths_cold)
    ]
    _run(eng2, wave3)
    assert sum(eng2.trace_counts().values()) == 0, eng2.trace_counts()


def test_chunked_lifts_prompt_length_limit():
    """A prompt LONGER than max_len serves through chunked admission (block
    tables grow page-by-page) and matches a big-slab reference engine."""
    api, params = _api_params("int8")
    rng = np.random.default_rng(3)
    long = rng.integers(0, CFG.vocab, size=MAX_LEN + 9).astype(np.int32)

    eng = PagedEngine(
        api, params, n_slots=1, max_len=MAX_LEN, page_size=PS, n_pages=16,
        chunked_prefill=True, prefill_chunk=2 * PS,
    )
    got, _ = _run(eng, [Request(rid=0, prompt=long, max_new=3)])
    assert eng.tables.shape[1] * PS > MAX_LEN  # tables actually grew

    big = PagedEngine(api, params, n_slots=1, max_len=2 * MAX_LEN, page_size=PS, n_pages=16)
    ref, _ = _run(big, [Request(rid=0, prompt=long, max_new=3)])
    assert got == ref
