"""Generic quantized-state page store (PR 9): StatePagedEngine serving
SSM / hybrid / enc-dec families — greedy-token equivalence with the
contiguous decode path, bounded-replay preemption-resume exactness,
fork sharing, shared read-only encoder pages (zero encoder FLOPs on a
hit), chaos containment, and typed rejection of unservable families."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke
from repro.models import zoo
from repro.models.layers import Runtime
from repro.serving.engine import PagedEngine
from repro.serving.faults import FaultInjector
from repro.serving.generate import (
    Request,
    SamplingParams,
    greedy_generate,
    next_greedy_tokens,
)
from repro.serving.state_engine import StatePagedEngine

RT = Runtime(quant_mode="none", compute_dtype=jnp.float32, param_dtype=jnp.float32)
B, S, G, ML, PS = 3, 12, 8, 64, 8
STATE_ARCHS = ("mamba2_130m", "recurrentgemma_9b", "whisper_base")


@functools.lru_cache(maxsize=None)
def _built(arch):
    cfg = get_smoke(arch)
    api = zoo.build(cfg, RT)
    params = api.init(jax.random.PRNGKey(0))
    return cfg, api, params


def _frames(cfg):
    if cfg.family != "encdec":
        return None
    return np.asarray(
        jax.random.normal(jax.random.PRNGKey(7), (cfg.encoder_len, cfg.d_model))
        * 0.02,
        np.float32,
    )


def _contiguous_ref(api, cfg, params, prompts, frames, gen_len, max_len):
    """Greedy reference on the plain contiguous prefill/decode path."""
    if cfg.family != "encdec":
        return np.asarray(
            greedy_generate(api, params, jnp.asarray(prompts), gen_len, max_len)
        )
    b, s = prompts.shape
    batch = {
        "tokens": jnp.asarray(prompts),
        "frames": jnp.broadcast_to(
            jnp.asarray(frames)[None], (b, cfg.encoder_len, cfg.d_model)
        ),
    }
    lg, caches = api.prefill_fn(params, batch, max_len)
    out = [next_greedy_tokens(lg)]
    for t in range(gen_len - 1):
        lg, caches = api.decode_fn(params, caches, out[-1][:, None], jnp.int32(s + t))
        out.append(next_greedy_tokens(lg))
    return np.asarray(jnp.stack(out, 1))


# --------------------------------------------------------- token equivalence
@pytest.mark.parametrize("depth", (1, 2))
@pytest.mark.parametrize("arch", STATE_ARCHS)
def test_state_paged_matches_contiguous(arch, depth):
    """Paged decode with state checkpointing (and, for enc-dec, shared
    read-only encoder pages) is token-for-token identical to the
    contiguous path — at pipeline depth 1 and 2."""
    cfg, api, params = _built(arch)
    prompts = np.asarray(
        jax.random.randint(jax.random.PRNGKey(5), (B, S), 0, cfg.vocab)
    )
    frames = _frames(cfg)
    ref = _contiguous_ref(api, cfg, params, prompts, frames, G, 32)
    eng = StatePagedEngine(
        api, params, n_slots=4, max_len=ML, page_size=PS, pipeline_depth=depth
    )
    reqs = [
        Request(rid=i, prompt=prompts[i], max_new=G - 1, frames=frames)
        for i in range(B)
    ]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    for i, r in enumerate(reqs):
        assert r.done and r.error is None, (arch, i, r.error)
        assert list(map(int, r.out)) == list(map(int, ref[i])), (arch, i)
    eng.audit(strict=True)
    kinds = eng.pool_mgr.used_by_kind()
    assert kinds["kv"] == 0, "state layout must hold no kv pages"
    if cfg.family == "encdec":
        # one distinct audio input → exactly one encoder launch, the
        # other B-1 requests hit the shared_ro page
        assert eng._cs["encoder_launches"].value == 1
        assert eng.stats["prefix_hits"] == B - 1


# ----------------------------------------------- bounded-replay preemption
@pytest.mark.parametrize("depth", (1, 2))
@pytest.mark.parametrize("arch", STATE_ARCHS)
def test_preempt_resume_bounded_replay(arch, depth):
    """Preempt an in-flight request mid-generation, resume it, and the
    output stays bit-identical to the never-preempted run — with at most
    page_size tokens replayed from the last checkpoint (vs a full
    prompt+output recompute without checkpoints)."""
    cfg, api, params = _built(arch)
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(5), (12,), 0, cfg.vocab)
    )
    frames = _frames(cfg)

    def fresh(rid):
        return Request(rid=rid, prompt=prompt, max_new=19, frames=frames)

    e0 = StatePagedEngine(
        api, params, n_slots=2, max_len=ML, page_size=PS, pipeline_depth=depth
    )
    r0 = fresh(0)
    e0.submit(r0)
    e0.run_to_completion()
    assert r0.done and r0.error is None, r0.error

    e1 = StatePagedEngine(
        api, params, n_slots=2, max_len=ML, page_size=PS, pipeline_depth=depth
    )
    r1 = fresh(1)
    e1.submit(r1)
    for _ in range(9):
        e1.step()
    e1.drain()
    n_before = len(r1.out)
    assert 0 < n_before < 20, "must preempt MID-generation"
    assert e1._preempt_one(None) is not None
    e1.audit(strict=True)  # carried checkpoint/encoder refs stay accounted
    e1.run_to_completion()
    assert list(map(int, r1.out)) == list(map(int, r0.out)), (arch, depth)
    replayed = e1._cs["replay_tokens"].value
    assert e1._cs["state_restores"].value == 1, "resume must restore a checkpoint"
    assert 0 < replayed <= PS, (arch, replayed)
    # the checkpoint saved recomputing everything before it
    assert replayed < len(prompt) + n_before
    if cfg.family == "encdec":
        assert e1._cs["encoder_launches"].value == 1, "resume must NOT re-encode"
    e1.audit(strict=True)


# ------------------------------------------- host-tier zero-replay resume
@pytest.mark.parametrize("arch", ("mamba2_130m", "whisper_base"))
def test_preempt_resume_from_host_zero_replay(arch):
    """With the host tier on, a preempted request's LIVE recurrent state
    snapshots to a pinned host page and resume restores it verified —
    bit-identical output with ZERO replayed tokens (the tierless path
    above replays up to page_size from the last checkpoint)."""
    cfg, api, params = _built(arch)
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(5), (12,), 0, cfg.vocab)
    )
    frames = _frames(cfg)

    def fresh(rid):
        return Request(rid=rid, prompt=prompt, max_new=19, frames=frames)

    e0 = StatePagedEngine(api, params, n_slots=2, max_len=ML, page_size=PS)
    r0 = fresh(0)
    e0.submit(r0)
    e0.run_to_completion()
    assert r0.done and r0.error is None, r0.error

    e1 = StatePagedEngine(
        api, params, n_slots=2, max_len=ML, page_size=PS, host_pages=8
    )
    r1 = fresh(1)
    e1.submit(r1)
    for _ in range(9):
        e1.step()
    e1.drain()
    assert 0 < len(r1.out) < 20, "must preempt MID-generation"
    assert e1._preempt_one(None) is not None
    sw = e1.health()["swap"]
    assert sw["swap_outs"] == 1, sw  # one state page carried, pinned
    assert e1.health()["host_tier"]["pinned"] == 1
    e1.audit(strict=True)  # the pinned carry is audit-clean mid-queue
    e1.run_to_completion()
    assert list(map(int, r1.out)) == list(map(int, r0.out)), arch
    assert e1._cs["replay_tokens"].value == 0, "host resume must not replay"
    assert e1._cs["state_restores"].value == 1
    sw = e1.health()["swap"]
    assert sw["swap_ins"] == 1 and sw["verified_swapins"] == 1, sw
    assert sw["swap_ins"] == sw["verified_swapins"] + sw["corrupt_swapins"]
    assert e1.health()["host_tier"] == {
        "used": 0, "capacity": 8, "bytes_resident": 0, "pinned": 0
    }
    if cfg.family == "encdec":
        assert e1._cs["encoder_launches"].value == 1, "resume must NOT re-encode"
    e1.audit(strict=True)


def test_host_swap_in_fault_falls_back_to_checkpoint_replay():
    """A refused swap-in drops only the host carry: the legacy HBM
    checkpoint reference is still held, so resume degrades to the
    bounded-replay path — exact output, ≤ page_size tokens replayed."""
    cfg, api, params = _built("mamba2_130m")
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(5), (12,), 0, cfg.vocab)
    )
    e0 = StatePagedEngine(api, params, n_slots=2, max_len=ML, page_size=PS)
    r0 = Request(rid=0, prompt=prompt, max_new=19)
    e0.submit(r0)
    e0.run_to_completion()

    e1 = StatePagedEngine(
        api, params, n_slots=2, max_len=ML, page_size=PS, host_pages=8,
        fault_injector=FaultInjector(seed=1, rates={"swap_in": 1.0}),
    )
    r1 = Request(rid=1, prompt=prompt, max_new=19)
    e1.submit(r1)
    for _ in range(9):
        e1.step()
    e1.drain()
    assert e1._preempt_one(None) is not None
    fin, _ = e1.run_to_completion()
    done = [r for r in fin if r.error is None]
    assert done and list(map(int, done[0].out)) == list(map(int, r0.out))
    assert 0 < e1._cs["replay_tokens"].value <= PS
    assert e1.health()["host_tier"]["used"] == 0  # refused carry dropped
    e1.audit(strict=True)


def test_host_swap_corrupt_quarantines_owner_state_layout():
    """A corrupted state-page swap-in quarantines exactly the owning
    request with a typed integrity error; pages stay fully accounted."""
    cfg, api, params = _built("mamba2_130m")
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(5), (12,), 0, cfg.vocab)
    )
    eng = StatePagedEngine(
        api, params, n_slots=2, max_len=ML, page_size=PS, host_pages=8,
        fault_injector=FaultInjector(seed=1, rates={"swap_corrupt": 1.0}),
    )
    req = Request(rid=0, prompt=prompt, max_new=19)
    eng.submit(req)
    for _ in range(9):
        eng.step()
    eng.drain()
    assert eng._preempt_one(None) is not None
    fin, _ = eng.run_to_completion()
    bad = [r for r in fin if r.error is not None]
    assert len(bad) == 1 and bad[0].error.kind == "quarantined"
    assert "integrity" in str(bad[0].error)
    sw = eng.health()["swap"]
    assert sw["corrupt_swapins"] == 1, sw
    assert sw["swap_ins"] == sw["verified_swapins"] + sw["corrupt_swapins"]
    assert eng.health()["host_tier"]["used"] == 0
    eng.audit(strict=True)
    assert int((eng.pool_mgr.refcount > 0).sum()) == 0


# ------------------------------------------------------------------- forks
def test_greedy_fork_identical():
    """n_samples=2 greedy forks share the live row + checkpoint page and
    both siblings reproduce the single-sequence output."""
    cfg, api, params = _built("mamba2_130m")
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(5), (12,), 0, cfg.vocab)
    )
    e0 = StatePagedEngine(api, params, n_slots=4, max_len=ML, page_size=PS)
    r0 = Request(rid=0, prompt=prompt, max_new=9)
    e0.submit(r0)
    e0.run_to_completion()

    e1 = StatePagedEngine(api, params, n_slots=4, max_len=ML, page_size=PS)
    e1.submit(Request(rid=1, prompt=prompt, max_new=9, n_samples=2))
    fin, _ = e1.run_to_completion()
    assert len(fin) == 2 and all(r.done and r.error is None for r in fin)
    for r in fin:
        assert list(map(int, r.out)) == list(map(int, r0.out)), r.sample_idx
    e1.audit(strict=True)
    assert e1.stats["forks"] == 1


def test_sampled_fork_deterministic_and_divergent():
    """Sampled siblings are deterministic across runs (seeded per-sample
    key chain) and actually diverge from each other after the fork."""
    cfg, api, params = _built("mamba2_130m")
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(5), (12,), 0, cfg.vocab)
    )
    sp = SamplingParams(temperature=0.9, top_k=20, seed=7)

    def outs():
        e = StatePagedEngine(api, params, n_slots=4, max_len=ML, page_size=PS)
        e.submit(Request(rid=2, prompt=prompt, max_new=9, n_samples=3, sampling=sp))
        fin, _ = e.run_to_completion()
        assert all(x.done and x.error is None for x in fin), [x.error for x in fin]
        return {x.sample_idx: list(map(int, x.out)) for x in fin}

    a, b = outs(), outs()
    assert a == b, "sampled forks must be deterministic"
    assert len({tuple(v) for v in a.values()}) > 1, "siblings should diverge"


# ----------------------------------------------- shared encoder page reuse
def test_shared_encoder_page_zero_encode_on_hit():
    """Two requests over the SAME audio: the second claims the registered
    shared_ro page — one encoder launch total, identical outputs."""
    cfg, api, params = _built("whisper_base")
    frames = _frames(cfg)
    prompts = np.asarray(
        jax.random.randint(jax.random.PRNGKey(5), (2, S), 0, cfg.vocab)
    )
    eng = StatePagedEngine(api, params, n_slots=2, max_len=ML, page_size=PS)
    reqs = [
        Request(rid=i, prompt=prompts[i], max_new=G - 1, frames=frames)
        for i in range(2)
    ]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    assert all(r.done and r.error is None for r in reqs)
    assert eng._cs["encoder_launches"].value == 1, "hit must run ZERO encoder FLOPs"
    assert eng.stats["prefix_hits"] == 1
    ref = _contiguous_ref(api, cfg, params, prompts, frames, G, 32)
    for i, r in enumerate(reqs):
        assert list(map(int, r.out)) == list(map(int, ref[i])), i
    eng.audit(strict=True)
    # the finished shared_ro page stays parked (reclaimable), kind-tagged
    assert eng.pool_mgr.used_by_kind()["shared_ro"] == 1


# ------------------------------------------------------------------ chaos
def test_chaos_contained_state_layout():
    """Injected alloc failures + poisoned logits: the engine loop
    survives, audits stay clean with heterogeneous kinds, untouched
    requests still match the clean run, checkpoint-alloc failures
    degrade the replay bound instead of correctness."""
    cfg, api, params = _built("mamba2_130m")
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(5), (12,), 0, cfg.vocab)
    )
    e0 = StatePagedEngine(api, params, n_slots=3, max_len=ML, page_size=PS)
    r0 = Request(rid=0, prompt=prompt, max_new=9)
    e0.submit(r0)
    e0.run_to_completion()

    faults = FaultInjector(
        seed=3,
        schedule=[(2, "alloc"), (3, "alloc"), (4, "alloc"), (5, "alloc"),
                  (4, "logits", 1)],
    )
    eng = StatePagedEngine(
        api, params, n_slots=3, max_len=ML, page_size=PS,
        fault_injector=faults, audit_every=1,
    )
    reqs = [Request(rid=10 + i, prompt=prompt, max_new=9) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    eng.audit(strict=True)
    assert eng.health()["counters"]["audit_failures"] == 0
    ok = [r for r in reqs if r.done and r.error is None]
    assert ok, "at least one request must survive the fault schedule"
    for r in ok:
        assert list(map(int, r.out)) == list(map(int, r0.out))
    bad = [r for r in reqs if r.error is not None]
    for r in bad:
        assert r.error.kind == "quarantined", r.error


# ------------------------------------------------- typed family rejection
def test_unsupported_family_raises_typed():
    """Wrong engine for the layout — and families with page_spec=None —
    raise UnsupportedModelError naming the family and the servable list."""
    cfg_kv, api_kv, params_kv = _built("gpt3_126m")
    with pytest.raises(zoo.UnsupportedModelError) as ei:
        StatePagedEngine(api_kv, params_kv, n_slots=2, max_len=ML, page_size=PS)
    msg = str(ei.value)
    assert ei.value.family == "dense"
    assert "state_checkpoint" in msg and "paged-servable families" in msg

    cfg_st, api_st, params_st = _built("mamba2_130m")
    with pytest.raises(zoo.UnsupportedModelError):
        PagedEngine(api_st, params_st, n_slots=2, max_len=ML, page_size=PS)

    # vlm is not paged-servable at all
    assert zoo.build(get_smoke("pixtral_12b"), RT).page_spec is None


@pytest.mark.parametrize("depth", [1, 2])
def test_state_engine_spans(depth):
    """The state engine's tick goes through the same span API: one
    ``engine_step`` / ``admit`` per step, one ``decode_tick`` per launch
    packing all ``n_slots`` rows, and (deeper than 1) one ``decode_sync``
    per launch; at depth 1 the sync merges into ``decode_tick``."""
    cfg, api, params = _built("mamba2_130m")
    eng = StatePagedEngine(
        api, params, n_slots=4, max_len=ML, page_size=PS, pipeline_depth=depth
    )
    rng = np.random.default_rng(3)
    for i in range(2):
        eng.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab, size=S).astype(np.int32),
            max_new=4,
        ))
    _, ticks = eng.run_to_completion()
    spans = [r for r in eng.telemetry.journal._buf if r[0] == "span"]
    by = {}
    for r in spans:
        by.setdefault(r[1], []).append(r[6])
    assert len(by["engine_step"]) == len(by["admit"]) == ticks
    dec = by["decode_tick"]
    assert len(dec) == eng.stats["decode_ticks"] > 0
    assert all(a["rows_launched"] == 4 for a in dec)
    assert len(by.get("decode_sync", [])) == (0 if depth == 1 else len(dec))
    pre = by["prefill_launch"]
    assert [a["tokens"] for a in pre] == [S, S]
    assert all(a["rows_launched"] == 1 and a["chunk_bucket"] == S for a in pre)
