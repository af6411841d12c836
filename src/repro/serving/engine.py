"""PagedEngine: continuous batching over a paged, quantized KV-cache.

Replaces the slot-contiguous cache of ``launch.batching.ContinuousBatcher``
with a global page pool + per-sequence block tables:

* **memory**: a sequence holds ceil(len/page_size) pages instead of a
  max-length slot; identical prompt prefixes share full pages through the
  prefix cache (refcounted, copy-on-write);
* **bandwidth**: decode attention gathers only the referenced pages
  (dequantizing int8/bcq4 pages on the fly — in-kernel with
  Runtime.paged_kernel), never the max-length buffer;
* **scheduling**: positions are per-sequence, so ONE fused decode step
  serves all active slots regardless of depth (the contiguous engine had
  to tick per unique position);
* **admission control** by free-page watermark, and **preemption by
  eviction** when the pool runs dry: the youngest sequence loses its pages
  and is requeued in recompute mode (prompt := prompt + generated), which
  is greedy-exact.

**Chunked-prefill tick model** (``chunked_prefill=True``): admission no
longer runs a full-prompt prefill over a max_len slab.  Instead it only
*plans* — claims the longest chain of prefix-hit pages and marks the slot
``prefill`` — and every ``step()`` then advances each prefilling slot by
ONE ``prefill_chunk``-token chunk (``models.transformer.prefill_from_pages``:
the chunk attends causally to itself and, through its block table, to the
already-written pages; with Runtime.paged_kernel the gather + dequant runs
in the Pallas chunked-prefill kernel) before the fused decode tick serves
the decoding slots.  ALL prefilling slots ride ONE launch per tick
(stacked tables / chunk starts / scatter ids, per-slot ``chunk_len``
masks), and serving shapes are **bucketed** so steady state stops
retracing: ragged tail chunks round up to power-of-two token buckets, the
prefill batch pads to a power of two, and block tables grow by doubling —
``trace_counts()`` reports the (bounded) compilation count.  Prefill
compute is therefore spread across ticks and
interleaved with decode (mixed prefill/decode scheduling), new pages are
written as each chunk completes, and a prefix hit saves *compute*, not
just page memory: the engine runs zero transformer work — zero attention
FLOPs — over prefix-hit tokens (only the uncached suffix runs; on a 100%
hit that is just the prompt's final partial page, kept so the last
position's logits exist).  Chunked mode also lifts the contiguous-slab
prompt-length limit: block tables grow on demand (in whole pages, one
decode retrace per growth), so a prompt longer than ``max_len`` serves
fine as long as the pool has pages — ``PromptTooLongError`` can only come
out of the non-chunked path, whose prefill materializes a max_len slab.

**Sequence forking / best-of-n** (``Request(n_samples=n)``): after a
request's prefill completes (either admission path), the engine forks the
slot into n sibling slots that share EVERY prompt page by refcount — one
``PagePool.ref`` per sibling per page, zero page copies, zero recompute.
Each sibling owns its block-table row, position, output list, and
``sample_idx`` (which seeds its token stream, see
``generate.SamplingParams``).  Siblings share the prompt's partial tail
page until their first token write, which triggers the copy-on-write
branch of ``_ensure_tail_page``: the tail page is duplicated bit-exactly
(``pages.copy_page`` moves every quant leaf, per-page scale/selector
metadata included) into a private page and the source loses one ref —
n-1 copies for n siblings (the last writer inherits the original).
Admission reserves the sibling slots (chunked mode holds them across
prefill ticks via ``_PagedSlot.reserved_by``), preemption requeues a
sibling as its OWN prompt+output (``n_samples`` already 1 post-fork, so
it never re-forks) dropping only its refs, and ``_free_slot`` releases a
not-yet-forked parent's reservations.  With temperature 0 the fork is
degenerate — every sibling replays the greedy stream bit-exactly
(tests/test_forking.py).

Greedy outputs are token-for-token identical to the contiguous engine:
the pool reuses cache_write's quantization layouts page by page, gathered
decode attention sees the same dequantized values with the same shapes
(max_len == MAXP·page_size), and masked tail positions contribute exact
zeros either way.  Chunked prefill writes byte-identical pages (per-token
quantization) and computes the same masked attention rows as the
full-prompt prefill, so its greedy tokens match the non-chunked engine
for every cache kind and prefix-hit fraction.  Verified in
tests/test_paged_engine.py and tests/test_chunked_prefill.py.

**Pipelined tick loop** (``pipeline_depth=2`` — the production
default in launch/serve.py and the benches; docs/OBSERVABILITY.md
"Pipelined tick attribution"): ``step()`` enqueues tick t+1's decode
launch BEFORE blocking on tick t's tokens, so host scheduling,
admission, and prefill planning overlap device compute.  The machinery
that keeps depth 2 bit-identical to the legacy synchronous loop
(depth 1, or ``profile_sync=True`` which forces it):

* the consumed token chains launch-to-launch ON DEVICE
  (``_make_fused_decode``: each launch computes its own argmax — and
  NaN-guard finite mask — in the same launch, and the next launch
  selects per-slot between that device token and a host-written one
  via the ``use_host`` column), so no host round-trip sits between
  decode ticks;
* everything else the launch needs — host tokens, source flags, kv
  lengths, block tables — rides ONE consolidated ``(B, 3+W)`` int32
  host→device transfer per tick (``_launch_decode`` packs it; the
  buffer is copied before ``jnp.asarray`` because the CPU backend may
  alias host memory zero-copy while the launch is still in flight);
* syncing a launch (``_sync_one``) books tokens per recorded row,
  discarding rows whose slot was since retired or re-assigned
  (speculative EOS launches), and only hands token authority back to
  the host when no NEWER in-flight launch still chains that slot;
* page-pool dataflow orders device work; host-side page reuse is safe
  because a stale launch's writes land beyond every reader's
  ``length`` (masked) or are overwritten by the new owner's prefill
  before its first decode read;
* preemption, teardown, and ``run_to_completion``'s exit drain the
  in-flight queue first (public ``drain()``), so recompute snapshots
  and final outputs always include every launched token;
* the NaN-quarantine and sampler fault seams consume row stats one
  tick late at depth 2 but key on the LAUNCH tick, so chaos runs
  demote identical requests at every depth (docs/ROBUSTNESS.md,
  "Quarantine under the pipelined tick loop").

Each tick's regions are ``Telemetry.span``s — ``engine_step`` around
``step()``, ``admit``, ``prefill_launch`` (the chunk launch),
``decode_tick`` (the decode launch) and ``decode_sync`` — so they land
on the profiler's host timeline while a trace runs.  Telemetry splits
attribution at depth 2: ``decode_tick_s`` holds the
dispatch-only launch span, ``decode_sync_s`` the blocking fetch, and
``decode_host_gap_s`` the between-launch host gap on quiet ticks —
the pipeline's figure of merit (the benchmark's ``host_gap_ms`` reads
it on the chip, ``perfbench/metrics/host_gap_ms.py``).  Sampled
requests merge their token on device too (``_SET_TOK`` overlay after
the launch) — the per-tick padded-logits host fetch is gone.
Depth-2 ≡ depth-1 ≡ profile_sync bit-identity across cache kinds ×
sampling × forking/preemption/chaos is pinned by
tests/test_pipelined_engine.py.

**Fault containment** (docs/ROBUSTNESS.md): the tick loop is built so
one poisoned request cannot take the batch down or leak pages:

* *lifecycle guard* — ``Request.deadline_s`` / ``max_output_stall_ticks``
  / ``cancel()`` are enforced at every tick boundary, tearing the request
  down (pages, fork reservations, queue entry) wherever it lives and
  finishing it with a typed ``RequestError``;
* *per-request quarantine* — non-finite logits, sampler exceptions, and
  per-slot state-transition failures demote only the offending slot to
  ``finished``-with-``error.kind == "quarantined"`` while the tick
  completes for everyone else; admission exceptions are contained the
  same way (with a transient-failure retry budget first).
  ``strict=True`` re-raises instead, for debugging;
* *invariant auditing* — ``engine.audit()`` (serving/audit.py) checks
  refcount ≡ table references, the free/referenced/parked partition, and
  prefix-chain consistency; ``audit_every=N`` rides production ticks;
* *graceful degradation* — a bounded admission queue (``max_queue``)
  sheds deadline-hopeless requests first; sustained watermark pressure
  enters a degraded mode (forks rejected at submit, prefix LRU shrunk to
  ``degraded_prefix_target``) with hysteresis on recovery;
  ``engine.health()`` summarizes all of it;
* *deterministic fault injection* — a ``serving.faults.FaultInjector``
  wired behind the allocator / prefix-claim / launch / logits-fetch /
  sampler seams reproduces every failure mode above at seeded
  (tick, site) points (the CI chaos smoke, tools/check_chaos.py).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import page_counts
from repro.serving import pages as pages_lib
from repro.serving.audit import AuditReport, audit_engine
from repro.serving.generate import (
    Request,
    RequestError,
    _sample_row,
    api_jit,
    next_greedy_tokens,
    pick_token,
    sampling_key,
    sequence_finished,
)
from repro.serving.pages import NULL_PAGE, PagePool, live_pages, pages_needed
from repro.serving.prefix import PrefixCache, chunk_hashes
from repro.serving.telemetry import (
    ENGINE_STAT_KEYS,
    ROBUSTNESS_STAT_KEYS,
    SWAP_STAT_KEYS,
    StatsView,
    Telemetry,
)


class PromptTooLongError(ValueError):
    """Prompt cannot fit the non-chunked prefill slab (plen >= max_len).

    Only the non-chunked admission path raises this: full-prompt prefill
    materializes a max_len cache slab.  Chunked admission has no such
    limit — its block tables grow page-by-page with the prompt."""


class PagePoolExhaustedError(RuntimeError):
    """The page pool cannot serve the pending request even with every
    reclaimable prefix page evicted and every other sequence preempted."""


class NonFiniteLogitsError(RuntimeError):
    """A request's last-position logits came back NaN/Inf — a poisoned
    forward pass (over/underflowed W4A4 activation, corrupted page).  The
    engine's nan_guard quarantines the offending request; ``strict=True``
    re-raises."""


# -------------------------------------------------- shared jit plumbing
# Per-ModelAPI jit caching lives in serving.generate.api_jit (shared with
# ContinuousBatcher); the page ops are api-independent, so one module-level
# jit each is enough for every engine instance.
_SCATTER = jax.jit(pages_lib.scatter_prefill_pages)
_COPY_PAGE = jax.jit(pages_lib.copy_page)
# Greedy argmax + finiteness of the last-position logits in ONE fused
# launch: the finite mask rides the same device→host fetch the argmax
# already paid (the tick loop consumes both right after its existing
# block_until_ready), so the NaN guard adds zero device syncs.
_ROW_STATS = jax.jit(
    lambda lg: (
        jnp.argmax(lg[:, -1, :], axis=-1).astype(jnp.int32),
        jnp.all(jnp.isfinite(lg[:, -1, :]), axis=-1),
    )
)
# Jitted greedy row fetch for the nan_guard=False path: one launch per
# tick, as on the guards-on path (run eagerly, ``next_greedy_tokens`` is
# a separate un-jitted dispatch every tick).
_GREEDY_ROW = jax.jit(next_greedy_tokens)
# Device-side merge of one sampled token into the launch's token vector
# (the index rides as a traced scalar, so every slot shares one trace).
_SET_TOK = jax.jit(lambda nxt, i, tok: nxt.at[i].set(tok.astype(nxt.dtype)))


def _make_fused_decode(fn, guard: bool):
    """The per-api decode step with everything the tick needs fused into
    ONE launch and ONE host→device transfer:

    * ``packed`` (B, 3+W) int32 carries next_tok / token-source flag /
      kv lengths / the block table — one consolidated ``jnp.asarray``
      per tick where the loop used to issue three;
    * the consumed token comes from the host column OR from
      ``chain_tok`` — the previous launch's on-device token choice — so
      a pipelined tick chains launch-to-launch with no host round-trip;
    * the greedy argmax (and, with the nan guard, the finite mask) of
      the last-position row is computed in the same launch, replacing
      the separate ``_ROW_STATS`` dispatch per tick."""

    def fused(params, pool, packed, chain_tok):
        tok = jnp.where(packed[:, 1] == 1, packed[:, 0], chain_tok)
        logits, pool = fn(params, pool, tok[:, None], packed[:, 3:], packed[:, 2])
        row = logits[:, -1, :]
        nxt = jnp.argmax(row, axis=-1).astype(jnp.int32)
        fin = jnp.all(jnp.isfinite(row), axis=-1) if guard else None
        return logits, nxt, fin, pool

    return fused


def _make_packed_chunk(fn, c: int, n_cp: int):
    """The chunk-tick step with its five per-array transfers (tokens /
    n_past / scatter ids / chunk_len / block tables) consolidated into
    ONE packed int32 array, split on device (the slices are free — XLA
    fuses them into the consumers)."""

    def fused(params, pool, packed):
        tok = packed[:, :c]
        npast = packed[:, c]
        ids = packed[:, c + 1 : c + 1 + n_cp]
        clen = packed[:, c + 1 + n_cp]
        bt = packed[:, c + 2 + n_cp :]
        return fn(params, tok, pool, bt, npast, ids, clen)

    return fused


def _pow2_bucket(n: int, cap: int) -> int:
    """Smallest power of two ≥ n, capped (shape-bucketing: bounded trace
    count instead of one compilation per distinct size)."""
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


@dataclasses.dataclass
class _PagedSlot:
    req: Optional[Request] = None
    pos: int = 0  # tokens currently in cache (next write position)
    admit_seq: int = 0  # admission order — preemption victims are youngest-first
    mode: str = "decode"  # 'decode' | 'prefill' (chunked admission in flight)
    pending: Optional[np.ndarray] = None  # full prompt while mode == 'prefill'
    hashes: Optional[list] = None  # full-page chain hashes of ``pending``
    # free slot held for a forking request's sibling (parent slot index):
    # chunked admission claims sibling slots up front so the fork at
    # prefill completion — many ticks later — cannot find them taken
    reserved_by: Optional[int] = None


@dataclasses.dataclass
class _InFlight:
    """One enqueued-but-unsynced decode launch (pipeline_depth > 1 keeps
    up to depth-1 of these between ticks).  ``rows`` snapshots
    (slot, request, post-launch position) at launch time: by sync time a
    row's slot may have been retired/preempted/re-admitted, in which case
    the row was speculative and is skipped (the identity check is the
    Request object itself — a freed slot always gets a NEW Request)."""

    tick: int  # engine tick that launched it (fault seams key on this)
    rows: list  # (slot_idx, req, pos_after_launch) triples
    nxt: object  # (n_slots,) device int32 — merged greedy/sampled tokens
    fin: object  # (n_slots,) device bool finite mask; None with guard off


class PagedEngine:
    """Fixed-slot continuous batching over a shared paged KV pool."""

    # page layout this engine serves (audit/telemetry dispatch on it);
    # StatePagedEngine overrides with "state"
    PAGE_LAYOUT = "kv"

    def __init__(
        self,
        api,
        params,
        n_slots: int,
        max_len: int,
        page_size: int = 16,
        n_pages: Optional[int] = None,
        eos_id: int = -1,
        prefix_caching: bool = True,
        watermark: Optional[int] = None,
        chunked_prefill: bool = False,
        prefill_chunk: int = 16,
        profile_sync: bool = False,
        pipeline_depth: int = 1,
        telemetry: Optional[Telemetry] = None,
        fault_injector=None,
        strict: bool = False,
        nan_guard: bool = True,
        audit_every: int = 0,
        max_queue: Optional[int] = None,
        shed_stuck: bool = True,
        degrade_after: Optional[int] = None,
        recover_after: int = 16,
        degraded_prefix_target: int = 0,
        host_pages: int = 0,
        recompress_after: int = 0,
    ):
        if api.paged_decode_fn is None:
            # typed and actionable instead of an assert: names the family
            # and the servable list (models.zoo.UnsupportedModelError)
            from repro.models.zoo import UnsupportedModelError

            cfg = getattr(api, "cfg", None)
            raise UnsupportedModelError(
                getattr(cfg, "name", "?"), getattr(cfg, "family", "?"),
                reason="This engine serves kv_paged layouts; state-checkpoint "
                "families serve through serving.state_engine.StatePagedEngine.",
            )
        assert max_len % page_size == 0, "page_size must divide max_len"
        self._init_shared(
            api, params, n_slots, max_len, page_size, eos_id, prefix_caching,
            profile_sync, pipeline_depth, telemetry, fault_injector, strict,
            nan_guard, audit_every, max_queue, shed_stuck, degrade_after,
            recover_after, degraded_prefix_target, host_pages,
        )
        self.recompress_after = recompress_after
        self.chunked = chunked_prefill
        self.prefill_chunk = prefill_chunk
        self.maxp = max_len // page_size
        if chunked_prefill:
            assert api.prefill_from_pages_fn is not None, (
                "family has no chunked-prefill path"
            )
            assert prefill_chunk % page_size == 0, (
                "prefill_chunk must be a page multiple (only a prompt's last "
                "chunk may end mid-page)"
            )
        # watermark: decode headroom kept free at admission — every active
        # slot may need one fresh page on any upcoming tick
        self.watermark = n_slots if watermark is None else watermark
        if n_pages is None:
            n_pages = 1 + n_slots * self.maxp  # null page + worst case
        self.pool_mgr = PagePool(n_pages)
        self.prefix = PrefixCache()
        self.pool = api.pool_init(n_pages, page_size)

        self.slots = [_PagedSlot() for _ in range(n_slots)]
        self.tables = np.full((n_slots, self.maxp), NULL_PAGE, np.int32)
        self._prefill, c_pre = api_jit(
            api, ("prefill", max_len),
            lambda p, t, _a=api, _ml=max_len: _a.prefill_fn(p, {"tokens": t}, _ml),
        )
        self._scatter = _SCATTER
        # decode rides the fused wrapper (argmax/finite in-launch, packed
        # single-transfer inputs, device token chaining) — keyed on the
        # guard flag so nan_guard=False skips the finite reduce entirely
        self._decode, c_dec = api_jit(
            api, ("paged_decode_fused", bool(nan_guard)),
            _make_fused_decode(api.paged_decode_fn, bool(nan_guard)),
        )
        self._copy_page = _COPY_PAGE
        if chunked_prefill:
            # ONE launch per tick for every prefilling slot; shapes bucket
            # to powers of two (chunk length, prefill batch) and tables
            # grow by doubling, so steady-state serving retraces a bounded
            # (bucket-count) number of times — never O(requests).  The
            # callable is per-(chunk bucket, pages-per-chunk) under the
            # hood (the packed-array split is a static layout), which is
            # exactly the pre-existing retrace cadence — trace_counts()
            # sums the per-bucket counters.
            self._chunk_step = self._chunk_step_packed
        self._trace_counters = {"prefill": c_pre, "decode": c_dec}
        self._trace_base = {k: v["traces"] for k, v in self._trace_counters.items()}
        self._trace_base["chunk"] = self._chunk_traces_total()
        self._packed = np.zeros((n_slots, 3 + self.tables.shape[1]), np.int32)

    def _init_shared(
        self, api, params, n_slots, max_len, page_size, eos_id,
        prefix_caching, profile_sync, pipeline_depth, telemetry,
        fault_injector, strict, nan_guard, audit_every, max_queue,
        shed_stuck, degrade_after, recover_after, degraded_prefix_target,
        host_pages=0,
    ):
        """Layout-independent engine state: the request lifecycle (queue /
        finished / lifecycle guard anchors), telemetry counters, fault
        containment config, and the pipelined tick machinery.  Shared by
        PagedEngine (kv_paged layout) and StatePagedEngine
        (state_checkpoint layout) — everything page-layout-specific (pool
        trees, block tables / slot records, the jitted steps) stays in the
        concrete engine's __init__."""
        self.api = api
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.ps = page_size
        self.eos = eos_id
        self.prefix_caching = prefix_caching
        # defaults a state-layout engine keeps; PagedEngine overwrites
        self.chunked = False
        self.prefill_chunk = 0
        # profile_sync: block on every prefill launch so the per-tick
        # latency split (stats t_prefill_s / t_decode_s) attributes device
        # time exactly — otherwise a mid-prompt launch's device work drains
        # inside the decode tick's sync and skews the split.  Off by
        # default: production keeps host/device overlap (benches opt in).
        self.profile_sync = profile_sync
        # pipeline_depth: dispatch queue depth of the tick loop.  1 (the
        # default) syncs each decode launch inside its own step() — the
        # legacy synchronous loop, and what profile_sync needs for exact
        # per-tick attribution (profile_sync therefore forces depth 1).
        # Depth 2 enqueues tick t+1's launch BEFORE syncing tick t's
        # tokens, so host scheduling/bookkeeping overlaps device compute:
        # the consumed token chains launch-to-launch on device (see
        # _make_fused_decode), dataflow on the page pool keeps device
        # ordering, and the NaN-quarantine / sampler fault seams consume
        # tick t's row stats one tick late WITHOUT changing which request
        # gets demoted (they key on the launch tick).  Tokens are
        # bit-identical across depths; callers reading ``req.out`` between
        # manual step() calls on a deep engine should ``drain()`` first
        # (run_to_completion drains on exit).
        assert pipeline_depth >= 1, "pipeline_depth must be >= 1"
        self.pipeline_depth = 1 if profile_sync else pipeline_depth
        self.queue: deque[Request] = deque()
        self.finished: list[Request] = []
        self._next_tok = np.zeros((n_slots,), np.int32)
        self._admit_counter = 0
        # telemetry: registry counters replace the old hand-maintained
        # stats dict; ``self.stats`` stays readable as a Mapping view with
        # the same keys/values (peak_pages reads the PagePool's own
        # high-water mark).  The t_prefill_s / t_decode_s counters keep
        # the per-tick latency split semantics (wall-clock around each
        # launch, synced on the logits; includes trace time on a cold
        # shape — warm up first for steady-state numbers).
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        _reg = self.telemetry.registry
        self._c = {
            k: _reg.counter(k) for k in ENGINE_STAT_KEYS if k != "peak_pages"
        }
        self._c["t_prefill_s"].unit = "s"
        self._c["t_decode_s"].unit = "s"
        # every block_until_ready on the serving path counts here — the
        # telemetry-overhead guard asserts the default level adds none
        self._c_syncs = _reg.counter("device_syncs")
        self.stats = StatsView(self)
        # --- fault containment (docs/ROBUSTNESS.md) ---
        # fault_injector: a serving.faults.FaultInjector consulted at the
        # allocator / prefix-claim / launch / logits / sampler seams (None
        # in production).  strict=True re-raises contained faults and
        # makes audit() fail-fast (debugging / CI bisection mode).
        # nan_guard validates last-position logits finiteness per request
        # per tick (rides the existing fetch — zero added syncs).
        # audit_every=N runs the serving/audit.py invariant sweep every N
        # ticks.  max_queue bounds the admission queue with deadline-aware
        # shedding; shed_stuck sheds an unserveable head-of-line request
        # in run_to_completion instead of raising.  degrade_after /
        # recover_after / degraded_prefix_target control degraded-mode
        # hysteresis under sustained watermark pressure.  degrade_after
        # defaults to None (disabled): automatic mode switching evicts
        # parked prefix pages, which legitimately perturbs hit/eviction
        # accounting — pools sized for capacity tests sit at the watermark
        # by design, so the policy is an explicit deployment opt-in
        # (launch/serve.py --degrade-after).
        self.faults = fault_injector
        self.strict = strict
        self.nan_guard = nan_guard
        self.audit_every = audit_every
        self.max_queue = max_queue
        self.shed_stuck = shed_stuck
        self.degrade_after = degrade_after
        self.recover_after = recover_after
        self.degraded_prefix_target = degraded_prefix_target
        self.degraded = False
        self._tick = 0
        self._pressure_ticks = 0
        self._relief_ticks = 0
        self._last_audit: Optional[AuditReport] = None
        self._cr = {k: _reg.counter(k) for k in ROBUSTNESS_STAT_KEYS}
        # --- host swap tier (docs/ROBUSTNESS.md "Memory tiers") ---
        # host_pages > 0 bounds a pinned host-RAM pool: evicted parked
        # prefix pages and preemption victims' pages DMA out with a
        # per-page blake2b digest and stream back verified on demand —
        # eviction becomes a recoverable bytes-move instead of data loss.
        # Counters are registry-only like the robustness set (the legacy
        # stats Mapping is pinned) and always registered so the metric
        # catalogue is configuration-independent.
        self.host_tier = (
            pages_lib.HostPageTier(host_pages) if host_pages else None
        )
        self._cs_swap = {k: _reg.counter(k) for k in SWAP_STAT_KEYS}
        self._cs_swap["swap_bytes"].unit = "bytes"
        # opt-in cold-page recompression ladder (KV layout only;
        # PagedEngine.__init__ overwrites recompress_after from its kwarg)
        self.recompress_after = 0
        self._rc_pressure = 0
        self._recompress_stage: dict[int, int] = {}
        # --- pipelined tick state (see pipeline_depth above) ---
        # _inflight: enqueued-but-unsynced decode launches (≤ depth-1).
        # _chain_tok: the LAST launch's on-device merged token choice —
        # what a chained slot consumes next tick without a host round-trip.
        # _chained[i]: slot i's next token lives in _chain_tok (its launch
        # is still in flight), not in the host _next_tok row.
        # _packed: reused host staging buffer for the consolidated
        # per-tick transfer (built by the concrete engine's __init__).
        self._inflight: deque = deque()
        self._chain_tok = jnp.zeros((n_slots,), jnp.int32)
        self._chained = np.zeros((n_slots,), bool)
        # host-gap attribution: launch-to-launch wall clock minus the sync
        # waits in between = pure host scheduling time (the bench's
        # device-bound assertion reads the resulting histogram)
        self._last_launch_end: Optional[float] = None
        self._gap_sync_s = 0.0

    def _chunk_traces_total(self) -> int:
        """Total traces across every (chunk bucket, pages) chunk-step
        entry in the shared per-api jit cache."""
        cache = getattr(self.api, "_engine_jit_cache", None) or {}
        return sum(
            v[1]["traces"] for k, v in cache.items()
            if isinstance(k, tuple) and k and k[0] == "chunk_step"
        )

    def trace_counts(self, since_init: bool = True) -> dict:
        """Traces of the prefill / decode / chunk step functions.  The
        callables are shared per ModelAPI; ``since_init`` subtracts the
        counts observed when THIS engine was built (so a warmed api
        reports ~0 for a steady-state run)."""
        counts = {k: v["traces"] for k, v in self._trace_counters.items()}
        counts["chunk"] = self._chunk_traces_total()
        if since_init:
            counts = {k: v - self._trace_base.get(k, 0) for k, v in counts.items()}
        return counts

    def lower_steps(self) -> dict:
        """The engine's own device steps lowered at its current shapes:
        ``"decode"`` (one tick over every slot) and, with chunked prefill,
        ``"chunk"`` (one full ``prefill_chunk`` tick for one slot).  Each
        value is a ``jax.stages.Lowered``; ``.compile().as_text()`` shows
        the program that serves requests."""
        w = self.tables.shape[1]
        out = {
            "decode": self._decode.lower(
                self.params, self.pool,
                jnp.zeros((self.n_slots, 3 + w), jnp.int32), self._chain_tok,
            )
        }
        if self.chunked:
            c = self.prefill_chunk
            n_cp = pages_needed(c, self.ps)
            out["chunk"] = self._chunk_fn(c, n_cp).lower(
                self.params, self.pool, jnp.zeros((1, c + 2 + n_cp + w), jnp.int32)
            )
        return out

    # ------------------------------------------------------------ intake
    def submit(self, req: Request):
        """Queue a request — after validating it.  An invalid request is
        rejected into ``finished`` with ``req.error`` set instead of
        raising out of ``step()``/``run_to_completion`` mid-flight, which
        would abandon every other in-flight request (the serving loop must
        survive one bad prompt).  Degraded mode rejects forking requests
        at this gate (an n-sibling fork is the most page-hungry admission
        there is), and a full bounded queue (``max_queue``) sheds the
        least-slack request — deadline-aware: the entry closest to (or
        past) its deadline is the one least worth keeping."""
        now = time.perf_counter()
        if req._t_submit is None:
            req._t_submit = now
        req._progress_tick = self._tick
        kind = msg = None
        if not (1 <= req.n_samples <= self.n_slots):
            kind, msg = "invalid", (
                f"n_samples={req.n_samples} outside [1, n_slots={self.n_slots}]"
            )
        elif not self.chunked and len(req.prompt) >= self.max_len:
            kind, msg = "too_long", self._too_long_msg(len(req.prompt))
        elif req.cancelled:
            kind, msg = "cancelled", "cancelled before admission"
        elif self.degraded and req.n_samples > 1:
            kind, msg = "shed", (
                f"degraded mode rejects forking requests (n_samples="
                f"{req.n_samples}); resubmit with n_samples=1 or retry later"
            )
        if kind is not None:
            self._finish_error(req, kind, msg)
            return
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            victim = self._shed_choice(req, now)
            full = f"admission queue full (max_queue={self.max_queue})"
            if victim is req:
                self._finish_error(req, "shed", full)
                return
            self.queue.remove(victim)
            self._finish_error(victim, "shed", f"{full}; least deadline slack")
        self.telemetry.on_submit(req, now)
        self.queue.append(req)

    def _shed_choice(self, newcomer: Request, now: float) -> Request:
        """Queue full: pick what to shed.  The queued request with the
        least remaining deadline slack loses (already-hopeless first);
        unbounded requests never outrank a bounded one, and ties shed the
        newcomer (no queue surgery)."""

        def slack(r: Request) -> float:
            if r.deadline_s is None or r._t_submit is None:
                return float("inf")
            return r.deadline_s - (now - r._t_submit)

        victim = min(self.queue, key=slack)
        return victim if slack(victim) < slack(newcomer) else newcomer

    # ----------------------------------------------------- fault containment
    def _finish_error(self, req: Request, kind: str, msg: str,
                      slot: Optional[int] = None):
        """Terminal-error path shared by every guard: free the slot when
        the request holds one (dropping its page refs and any sibling
        reservations), stamp the typed error, count it, finish."""
        if slot is not None:
            self._free_slot(slot)
        self._release_carried(req)  # page refs a queued resumed req holds
        req.error = RequestError(kind, msg)
        req.done = True
        if kind in self._cr:
            self._cr[kind].inc()
            self.telemetry.instant(kind, rid=int(req.rid))
        self.telemetry.on_finish(req, time.perf_counter())
        self.finished.append(req)

    def _quarantine(self, i: int, exc: BaseException):
        """Contain a per-request fault: demote ONLY slot i's request to
        finished-with-error (releasing every page ref / reservation) and
        let the tick proceed for everyone else."""
        req = self.slots[i].req
        if req is None:
            return
        self._finish_error(
            req, "quarantined", f"{type(exc).__name__}: {exc}", slot=i
        )

    def _lifecycle_violation(self, req: Request, now: float) -> Optional[tuple]:
        """(kind, msg) when the request must be torn down, else None."""
        if req.cancelled:
            return ("cancelled",
                    f"cancelled by caller after {len(req.out)} tokens")
        if (
            req.deadline_s is not None
            and req._t_submit is not None
            and now - req._t_submit > req.deadline_s
        ):
            return ("expired",
                    f"deadline_s={req.deadline_s} exceeded "
                    f"({now - req._t_submit:.3f}s since submit)")
        if (
            req.max_output_stall_ticks is not None
            and self._tick - req._progress_tick > req.max_output_stall_ticks
        ):
            return ("expired",
                    f"no token for {self._tick - req._progress_tick} ticks "
                    f"> max_output_stall_ticks={req.max_output_stall_ticks}")
        return None

    def _enforce_lifecycle(self):
        """Tick-boundary sweep of the lifecycle guard over BOTH the queue
        and the active slots: cancelled / over-deadline / output-stalled
        requests are torn down wherever they live, releasing every page
        reference and fork reservation."""
        now = time.perf_counter()
        if self.queue:
            kept: deque[Request] = deque()
            for req in self.queue:
                why = self._lifecycle_violation(req, now)
                if why is None:
                    kept.append(req)
                else:
                    self._finish_error(req, *why)
            self.queue = kept
        for i, s in enumerate(self.slots):
            if s.req is None:
                continue
            why = self._lifecycle_violation(s.req, now)
            if why is not None:
                self._finish_error(s.req, *why, slot=i)

    def _update_pressure(self):
        """Degraded-mode hysteresis: ``degrade_after`` consecutive ticks
        with free+reclaimable pages at or below the admission watermark
        enter degraded mode; ``recover_after`` consecutive relieved ticks
        leave it (asymmetric on purpose — flapping in and out each tick
        would make shedding decisions incoherent).  While degraded, the
        prefix LRU is shrunk toward ``degraded_prefix_target`` parked
        pages (cached-prefix memory goes back to the live set) and
        forking submissions are rejected (see submit)."""
        self._recompress_tick()
        if self.degrade_after is None:
            return
        pressured = self._available_pages() <= self.watermark
        if pressured:
            self._pressure_ticks += 1
            self._relief_ticks = 0
        else:
            self._relief_ticks += 1
            self._pressure_ticks = 0
        if not self.degraded and self._pressure_ticks >= self.degrade_after:
            self.degraded = True
            self.telemetry.instant("degraded_enter", tick=self._tick)
        elif self.degraded and self._relief_ticks >= self.recover_after:
            self.degraded = False
            self.telemetry.instant("degraded_exit", tick=self._tick)
        if self.degraded:
            self._cr["degraded_ticks"].inc()
            while self.prefix.reclaimable_count() > self.degraded_prefix_target:
                if self._evict_parked_page() is None:
                    break

    def _recompress_tick(self, budget: int = 2):
        """Opt-in accuracy-vs-bits ladder (``recompress_after`` > 0):
        after that many consecutive ticks at/below the admission
        watermark, walk the prefix LRU from its cold tail and requantize
        up to ``budget`` parked pages one ladder stage down
        (native→int8→bcq4, ``pages.kv_page_recompress``) in place —
        trading parked-page fidelity for effective capacity before
        resorting to eviction.  The stage marker sticks to the page's
        contents: it survives revival (downstream equivalence becomes
        tolerance-tier) and travels through the host tier as entry meta;
        swap itself stays bitwise."""
        if not self.recompress_after:
            return
        if self._available_pages() > self.watermark:
            self._rc_pressure = 0
            return
        self._rc_pressure += 1
        if self._rc_pressure < self.recompress_after:
            return
        top = len(pages_lib.RECOMPRESS_STAGES) - 1
        for pid in list(self.prefix.reclaimable):  # LRU order: coldest first
            if budget == 0:
                break
            stage = self._recompress_stage.get(pid, 0)
            if stage >= top:
                continue
            self._recompress_page(pid, pages_lib.RECOMPRESS_STAGES[stage + 1])
            self._recompress_stage[pid] = stage + 1
            self._cs_swap["recompressed_pages"].inc()
            self.telemetry.instant(
                "recompress", page=int(pid),
                stage=pages_lib.RECOMPRESS_STAGES[stage + 1],
            )
            budget -= 1

    def audit(self, strict: Optional[bool] = None) -> AuditReport:
        """Run the serving/audit.py invariant sweep now.  Report mode by
        default; ``strict`` (defaulting to the engine's strict flag)
        raises AuditError on a dirty report.  Called every
        ``audit_every`` ticks by step()."""
        report = audit_engine(self)
        self._last_audit = report
        if not report.ok:
            self._cr["audit_failures"].inc()
            self.telemetry.instant(
                "audit_fail", violations=len(report.violations)
            )
        if self.strict if strict is None else strict:
            report.raise_if_dirty()
        return report

    def health(self) -> dict:
        """One JSON-able liveness/pressure summary (the ops poll surface;
        ``snapshot()`` is the full metrics dump)."""
        return {
            "status": "degraded" if self.degraded else "ok",
            "degraded": self.degraded,
            "tick": self._tick,
            "pipeline_depth": self.pipeline_depth,
            "pipeline_inflight": len(self._inflight),
            "queue_depth": len(self.queue),
            "active_slots": len(self._active()),
            "watermark_headroom": self._available_pages() - self.watermark,
            "pressure_ticks": self._pressure_ticks,
            "relief_ticks": self._relief_ticks,
            "counters": {k: c.value for k, c in self._cr.items()},
            "host_tier": (
                None if self.host_tier is None else self.host_tier.snapshot()
            ),
            "swap": {k: c.value for k, c in self._cs_swap.items()},
            "last_audit": (
                None if self._last_audit is None else self._last_audit.to_dict()
            ),
            "faults_injected": (
                None if self.faults is None else self.faults.counts()
            ),
        }

    def _too_long_msg(self, plen: int) -> str:
        """One source of truth for submit()'s rejection marker and the
        typed PromptTooLongError on the direct _try_admit path."""
        return (
            f"prompt of {plen} tokens does not fit the non-chunked "
            f"prefill slab (max_len={self.max_len}); serve it with "
            f"chunked_prefill=True"
        )

    # ------------------------------------------------------- page plumbing
    def _alloc_page(self, kind: str = pages_lib.KIND_KV) -> Optional[int]:
        """Allocate a page of ``kind``, evicting reclaimable prefix pages
        LRU-first (the freed ids re-alloc as any kind — one budget across
        heterogeneous page kinds)."""
        if self.faults is not None and self.faults.alloc_fails(self._tick):
            return None  # injected transient exhaustion (chaos testing)
        pid = self.pool_mgr.alloc(kind)
        while pid is None:
            if self._evict_parked_page() is None:
                return None
            pid = self.pool_mgr.alloc(kind)
        # (peak tracking lives in PagePool.alloc — see pages.PagePool.peak)
        return pid

    def _evict_parked_page(self) -> Optional[int]:
        """Evict the LRU parked prefix page back to the free list.  With
        the host tier enabled its bytes are demoted to host RAM first
        (the chain hash re-homes onto the host handle), so a future hit
        streams the page back instead of recomputing; without the tier —
        or when the demotion is refused — this is the legacy lossy
        eviction."""
        popped = self.prefix.pop_lru()
        if popped is None:
            return None
        h, victim = popped
        self._c["prefix_evictions"].inc()
        self.telemetry.instant("prefix_evict", page=int(victim))
        self._maybe_swap_out_parked(h, victim)
        self._recompress_stage.pop(victim, None)  # pid returns to free list
        self.pool_mgr.release(victim)
        return victim

    def _maybe_swap_out_parked(self, h, pid: int) -> bool:
        """Demote an evicted parked page's bytes to the host tier under
        its chain hash.  Refusals (tier off, unswappable kind, injected
        swap_out fault, tier full of pinned entries) fall back to plain
        eviction — the caller releases the pid either way."""
        tier = self.host_tier
        if tier is None or h is None:
            return False
        kind = self.pool_mgr.kind_of(pid)
        if kind != self.HOST_SWAP_KIND:
            return False  # e.g. shared_ro encoder pages stay re-encodable
        if self.faults is not None and self.faults.swap_out_fails(
            self._tick, key=int(pid)
        ):
            self._cs_swap["swap_skips"].inc()
            return False
        if tier.full():
            ev = tier.evict_lru()
            if ev is None:
                self._cs_swap["swap_skips"].inc()
                return False  # every host entry pinned: plain eviction
            self.prefix.host_forget(ev[0])
            self.telemetry.instant("host_evict")
        arrays = self._fetch_page_arrays(pid)
        stage = self._recompress_stage.get(pid, 0)
        handle = tier.put(
            arrays, kind, meta=({"stage": stage} if stage else None)
        )
        self.prefix.host_register(h, handle)
        self._cs_swap["swap_outs"].inc()
        self._cs_swap["swap_bytes"].inc(sum(a.nbytes for a in arrays))
        self.telemetry.instant("swap_out", page=int(pid))
        return True

    # ---------------------------------------------- layout-subclass hooks
    # page kind the host tier accepts from this layout (parked-prefix
    # swap-outs of any other kind fall back to plain eviction)
    HOST_SWAP_KIND = pages_lib.KIND_KV

    def _fetch_page_arrays(self, pid: int) -> list:
        """One page's per-page pool slices as host arrays (swap-out)."""
        return pages_lib.kv_page_fetch(self.pool, pid)

    def _insert_page_arrays(self, pid: int, arrays) -> None:
        """Write verified host arrays back into pool page ``pid``."""
        self.pool = pages_lib.kv_page_insert(self.pool, arrays, pid)

    def _recompress_page(self, pid: int, stage: str) -> None:
        self.pool = pages_lib.kv_page_recompress(self.pool, pid, stage)

    def _carry_resume_state(self, slot, resumed: Request) -> None:
        """Preemption hook: move what the resumed request needs across the
        queue round-trip.  Without a host tier the KV layout carries
        nothing — preemption is pure recompute (prefix hits soften the
        replay).  With the tier, a decoding victim's written pages are
        snapshotted to pinned host entries (per-page digests) and the
        resumed request carries their handles: re-admission streams the
        pages back verified and rejoins decode directly — zero prefill
        FLOPs.  Any refusal (tier full of pinned entries, injected
        swap_out fault, mid-prefill victim) keeps the legacy recompute
        path.  The state-checkpoint layout overrides this wholesale."""
        tier = self.host_tier
        if (
            tier is None or slot.mode != "decode" or slot.pos <= 0
            or resumed.n_samples > 1
        ):
            return
        i = self.slots.index(slot)
        pids = live_pages(self.tables[i])
        if not pids:
            return
        if self.faults is not None and self.faults.swap_out_fails(
            self._tick, key=int(resumed.rid)
        ):
            self._cs_swap["swap_skips"].inc()
            return
        while tier.capacity - tier.used() < len(pids):
            ev = tier.evict_lru()
            if ev is None:
                self._cs_swap["swap_skips"].inc()
                return  # cannot fit the carry: recompute preemption
            self.prefix.host_forget(ev[0])
        handles, nbytes = [], 0
        for pid in pids:
            arrays = self._fetch_page_arrays(int(pid))
            handles.append(tier.put(
                arrays, self.HOST_SWAP_KIND, pinned=True,
                meta={"rid": int(resumed.rid)},
            ))
            nbytes += sum(a.nbytes for a in arrays)
        resumed._host_resume = (handles, slot.pos)
        self._cs_swap["swap_outs"].inc(len(pids))
        self._cs_swap["swap_bytes"].inc(nbytes)
        self.telemetry.instant(
            "swap_out_preempt", rid=int(resumed.rid), pages=len(pids)
        )

    def _release_carried(self, req: Request) -> None:
        """Teardown hook: drop what a QUEUED request carries (host-tier
        page snapshots here; the state layout adds HBM checkpoint refs)."""
        hr = getattr(req, "_host_resume", None)
        if hr is not None:
            if self.host_tier is not None:
                for handle in hr[0]:
                    self.host_tier.drop(handle)
            req._host_resume = None

    def _drop_page(self, pid: int):
        if pid == NULL_PAGE:
            return
        if self.pool_mgr.deref(pid):
            if self.prefix.knows(pid):
                self.prefix.mark_reclaimable(pid)  # keep contents for reuse
            else:
                self.pool_mgr.release(pid)

    def _free_slot(self, i: int):
        """Release slot i: drop ONLY this slot's page references (a forked
        sibling shares pages with its siblings — each row carries exactly
        one ref per page, so per-row deref is fork-correct by
        construction) and free any sibling-slot reservations a
        not-yet-forked parent in slot i was holding."""
        for pid in self.tables[i]:
            self._drop_page(int(pid))
        self.tables[i] = NULL_PAGE
        self.slots[i] = _PagedSlot()
        self._chained[i] = False  # any in-flight row for i is now dead
        for s in self.slots:
            if s.reserved_by == i:
                s.reserved_by = None

    def _available_pages(self) -> int:
        return self.pool_mgr.available() + self.prefix.reclaimable_count()

    def _grow_tables(self, n_seq_pages: int):
        """Widen every block table to ≥ n_seq_pages columns (chunked mode
        only — lifts the plen < max_len slab limit).  Growth DOUBLES the
        width instead of padding to the exact need: table width is a jit
        shape for both ticks, so doubling bounds the retrace count at
        log2(longest prompt / max_len) instead of one per distinct
        prompt-page count."""
        if n_seq_pages <= self.tables.shape[1]:
            return
        width = self.tables.shape[1]
        while width < n_seq_pages:
            width *= 2
        pad = width - self.tables.shape[1]
        self.tables = np.pad(
            self.tables, ((0, 0), (0, pad)), constant_values=NULL_PAGE
        )

    def _seq_capacity(self) -> int:
        """Tokens a sequence may hold: the block-table width (chunked mode
        grows it), == max_len for a non-chunked engine."""
        return self.tables.shape[1] * self.ps

    # -------------------------------------------------------- admission
    def _plan_prefix_hits(self, req: Request, prompt: np.ndarray) -> tuple[list, list[int]]:
        """Longest chain of full-page prefix hits (non-mutating peek —
        a refused admission must not unpark reclaimable pages, reorder the
        prefix LRU, or touch stats, since the head-of-line request is
        re-scanned every tick).  The prompt digests are memoized on the
        request so that re-scan costs O(pages) peeks, not O(plen) hashing."""
        if not self.prefix_caching:
            hashes = []
        elif req._hash_cache is not None and req._hash_cache[0] == self.ps:
            hashes = req._hash_cache[1]
        else:
            hashes = chunk_hashes(prompt, self.ps)
            req._hash_cache = (self.ps, hashes)
        hits: list = []
        for h in hashes:
            pid = self.prefix.peek(h)
            if pid is not None:
                hits.append(pid)
                continue
            if self.host_tier is not None:
                handle = self.prefix.host_peek(h)
                if handle is not None:
                    # host-resident chunk: still a hit — claiming it
                    # streams the page back into a FRESH HBM pid
                    hits.append(("host", handle))
                    continue
            break
        if hits and self.faults is not None and self.faults.drop_prefix_claim(
            self._tick, key=int(req.rid)
        ):
            hits = []  # injected racing eviction: force the recompute path
        return hashes, hits

    @staticmethod
    def _n_hbm_hits(hits) -> int:
        """Planned hits already holding an HBM pid (host hits need a
        fresh page each, so they don't reduce the allocation need)."""
        return sum(1 for hit in hits if not isinstance(hit, tuple))

    def _claim_hits(self, hashes, hits, n_cacheable: int,
                    table: np.ndarray) -> int:
        """Commit to the planned hit pages: revive/ref HBM hits, stream
        host hits back in (verified swap-in into a fresh pid).  Returns
        the number of pages actually claimed — a refused host swap-in
        (injected ``swap_in`` fault, tier race, dry allocator) TRUNCATES
        the chain there and the rest of the prompt recomputes; a corrupt
        swap-in raises ``PageCorruptionError`` (the owning request is
        quarantined by ``_admit``, never retried).

        ``n_cacheable`` is the count of prompt pages that COULD have hit:
        full pages only (a prompt's trailing partial page is never
        cacheable by design), and in chunked mode also excluding the
        deliberately-trimmed final hit (the last-chunk page kept to
        produce the prompt's last-position logits).  Counting misses over
        all prompt pages instead used to report a 50% hit rate for a
        100%-warm resubmission of a 17-token prompt at page_size=16."""
        claimed = 0
        for i, (h, hit) in enumerate(zip(hashes, hits)):
            if isinstance(hit, tuple):
                pid = self._swap_in_prefix_page(h)
                if pid is None:
                    break  # refused: the rest of the chain recomputes
            else:
                pid = hit
                got = self.prefix.lookup(h)  # unparks the reclaimable page
                assert got == pid
                if self.pool_mgr.refcount[pid] == 0:
                    self.pool_mgr.revive(pid)
                else:
                    self.pool_mgr.ref(pid)
            table[i] = pid
            claimed += 1
        self._c["prefix_hits"].inc(claimed)
        self._c["prefix_misses"].inc(max(0, n_cacheable - claimed))
        return claimed

    def _swap_in_prefix_page(self, h) -> Optional[int]:
        """Stream one host-resident prefix chunk back into a fresh HBM
        page: claim the handle, allocate, verify-take, insert, re-register
        the hash on the new pid.  Returns the pid, None on a refusal
        (treated as a miss), or raises ``PageCorruptionError`` when the
        integrity check fails (the entry is gone either way — the hash is
        simply no longer cached)."""
        tier = self.host_tier
        handle = self.prefix.host_peek(h)
        if tier is None or handle is None or not tier.has(handle):
            return None  # raced out since planning
        key = int(handle - pages_lib._HANDLE_BASE)
        if self.faults is not None and self.faults.swap_in_fails(
            self._tick, key=key
        ):
            # injected host-pool teardown: the entry is unusable
            self.prefix.host_forget(handle)
            tier.drop(handle)
            self._cs_swap["swap_skips"].inc()
            return None
        self.prefix.host_claim(h)
        tier.pin(handle)  # the alloc below may LRU-evict host entries
        pid = self._alloc_page(self.HOST_SWAP_KIND)
        if pid is None:
            tier.pin(handle, False)
            self.prefix.host_register(h, handle)  # undo the claim
            return None
        if self.faults is not None and self.faults.swap_corrupts(
            self._tick, key=key
        ):
            tier.corrupt(handle)
        self._cs_swap["swap_ins"].inc()
        try:
            entry = tier.take(handle, expect_kind=self.HOST_SWAP_KIND)
        except pages_lib.PageCorruptionError:
            self._cs_swap["corrupt_swapins"].inc()
            self.telemetry.instant("swap_corrupt", handle=key)
            self._drop_page(pid)  # fresh pid, not yet registered
            raise
        self._cs_swap["verified_swapins"].inc()
        self._cs_swap["swap_bytes"].inc(entry.nbytes)
        self._insert_page_arrays(pid, entry.arrays)
        stage = entry.meta.get("stage", 0)
        if stage:
            self._recompress_stage[pid] = stage
        if self.prefix_caching:
            self.prefix.register(h, pid)
        self.telemetry.instant("swap_in", page=int(pid))
        return pid

    def _try_resume_from_host(self, req: Request, slot_idx: int,
                              hr: tuple) -> Optional[bool]:
        """Re-admit a preemption victim from its carried host-tier page
        snapshots: stream every page back into fresh pids (verified), then
        rejoin decode at the carried position — zero prefill FLOPs and
        bit-identical KV.  Returns True (admitted), False (blocked on
        pages; handles stay pinned for the next attempt), or None (fell
        back — handles dropped, caller runs recompute admission)."""
        handles, pos = hr
        tier = self.host_tier

        def _fallback() -> None:
            self._release_carried(req)

        if (
            tier is None
            or any(not tier.has(h) for h in handles)
            # non-chunked recompute would raise the typed too-long error;
            # resuming here would mask that contract
            or (not self.chunked and len(req.prompt) >= self.max_len)
        ):
            _fallback()
            return None
        if self.faults is not None and self.faults.swap_in_fails(
            self._tick, key=int(req.rid)
        ):
            self._cs_swap["swap_skips"].inc()
            _fallback()
            return None
        need = len(handles)
        if self._available_pages() < need + self.watermark:
            return False  # blocked: pinned handles survive for a retry
        if self.chunked:
            self._grow_tables(
                pages_needed(len(req.prompt) + req.max_new + 1, self.ps)
            )
        # allocate every destination page BEFORE consuming any host entry:
        # the watermark check above already held, so a None here is an
        # allocation flake (injected or racing) — roll back and fall all
        # the way back to recompute admission (plan-only in chunked mode,
        # so it cannot itself wedge the stuck-shed heuristic); nothing
        # was consumed, so recompute stays exact
        table = np.full((self.tables.shape[1],), NULL_PAGE, np.int32)
        for k in range(need):
            pid = self._alloc_page(self.HOST_SWAP_KIND)
            if pid is None:
                for p in table:
                    self._drop_page(int(p))
                self._cs_swap["swap_skips"].inc()
                _fallback()
                return None
            table[k] = pid
        try:
            for k, handle in enumerate(handles):
                if self.faults is not None and self.faults.swap_corrupts(
                    self._tick, key=int(req.rid)
                ):
                    tier.corrupt(handle)
                self._cs_swap["swap_ins"].inc()
                entry = tier.take(handle, expect_kind=self.HOST_SWAP_KIND)
                self._cs_swap["verified_swapins"].inc()
                self._cs_swap["swap_bytes"].inc(entry.nbytes)
                self._insert_page_arrays(int(table[k]), entry.arrays)
        except pages_lib.PageCorruptionError:
            for pid in table:
                self._drop_page(int(pid))
            self._cs_swap["corrupt_swapins"].inc()
            self.telemetry.instant("swap_corrupt", rid=int(req.rid))
            # taken handles are gone from the tier; drop the untaken
            # remainder (corruption aborted the loop mid-way) — the raise
            # quarantines this request, nothing else references them
            self._release_carried(req)
            raise
        req._host_resume = None
        self.telemetry.on_admit(req, time.perf_counter())
        self.tables[slot_idx] = table
        self.slots[slot_idx] = _PagedSlot(
            req=req, pos=pos, admit_seq=self._admit_counter
        )
        self._admit_counter += 1
        # rejoin decode exactly where preemption cut it: the cache holds
        # pos tokens and the one token it does NOT yet contain is the last
        # of the resumed prompt (prompt+out concatenation — decode always
        # keeps the cache one token behind the next write), so seed the
        # decode loop with it just like _start_decode would.
        assert pos == len(req.prompt) - 1, (
            "host resume carried a position that disagrees with the "
            "requeued prompt (expected pos == len(prompt) - 1)"
        )
        self._next_tok[slot_idx] = int(np.asarray(req.prompt)[-1])
        self._chained[slot_idx] = False
        req._progress_tick = self._tick
        self.telemetry.instant(
            "swap_resume", rid=int(req.rid), pages=need, pos=int(pos)
        )
        self._finish_if_budget_spent(slot_idx)
        return True

    def _try_admit(self, req: Request, slot_idx: int) -> bool:
        hr = getattr(req, "_host_resume", None)
        if hr is not None:
            res = self._try_resume_from_host(req, slot_idx, hr)
            if res is not None:
                return res
            # fell back (handles dropped): ordinary recompute admission
        prompt = np.asarray(req.prompt, np.int64)
        plen = len(prompt)
        if self.chunked:
            return self._try_admit_chunked(req, prompt, plen, slot_idx)
        if plen >= self.max_len:
            raise PromptTooLongError(self._too_long_msg(plen))
        n_prompt_pages = pages_needed(plen, self.ps)
        n_full = plen // self.ps

        hashes, hits = self._plan_prefix_hits(req, prompt)
        # host hits stream back into FRESH pids, so they don't reduce the
        # allocation need — only already-HBM-resident hits do
        need = n_prompt_pages - self._n_hbm_hits(hits)
        if self._available_pages() < need + self.watermark:
            return False  # admission control: keep decode headroom

        table = np.full((self.tables.shape[1],), NULL_PAGE, np.int32)
        scatter_ids = np.full((self.maxp,), NULL_PAGE, np.int32)
        try:
            n_claimed = self._claim_hits(hashes, hits, n_full, table)
            for i in range(n_claimed, n_prompt_pages):
                pid = self._alloc_page()
                if pid is None:
                    raise PagePoolExhaustedError(
                        f"allocator dry mid-admission (watermark="
                        f"{self.watermark} should have reserved {need} pages)"
                    )
                table[i] = pid
                scatter_ids[i] = pid

            # prefill the prompt (full max_len cache so shapes — and hence
            # reduction order and greedy tokens — match the contiguous
            # engine), then scatter the missed pages; shared pages are
            # never rewritten.
            tokens = jnp.asarray(prompt, jnp.int32)[None, :]
            if self.faults is not None:
                self.faults.delay_launch(self._tick, key=0)
            t0 = time.perf_counter()
            self.telemetry.on_admit(req, t0)
            with self.telemetry.span(
                "prefill_launch", slots=1, tokens=plen, tick=self._tick,
                rows_launched=1, chunk_bucket=plen, synced=True,
            ):
                logits, cache1 = self._prefill(self.params, tokens)
                logits = jax.block_until_ready(logits)
            self._c_syncs.inc()
            t1 = time.perf_counter()
            self._c["t_prefill_s"].inc(t1 - t0)
            self._c["prefill_launches"].inc()
            self.telemetry.on_chunk(req, t1, plen)  # whole prompt, 1 chunk
            self.pool = self._scatter(self.pool, cache1, jnp.asarray(scatter_ids))
            if self.prefix_caching:
                for i in range(n_claimed, n_full):
                    self.prefix.register(hashes[i], int(table[i]))
            self._c["prefill_tokens"].inc(plen)
        except BaseException:
            # roll back before propagating: the claimed hit pages and the
            # fresh allocations live only in the local ``table`` here, so
            # an exception (mid-admission exhaustion, injected flake, a
            # poisoned prefill) would otherwise leak every one of them —
            # _drop_page re-parks registered pages and frees the rest
            for pid in table:
                self._drop_page(int(pid))
            raise

        self.tables[slot_idx] = table
        self.slots[slot_idx] = _PagedSlot(req=req, pos=plen, admit_seq=self._admit_counter)
        self._admit_counter += 1
        try:
            self._start_decode(slot_idx, logits)
        except Exception as exc:
            # the request IS admitted at this point — containment is slot
            # teardown (quarantine), not an admission-failure rollback
            if self.strict:
                raise
            self._quarantine(slot_idx, exc)
        return True

    def _try_admit_chunked(self, req: Request, prompt, plen: int, slot_idx: int) -> bool:
        """Plan-only admission: claim prefix-hit pages, mark the slot
        ``prefill``; ``_prefill_tick`` then runs one chunk per step()."""
        n_prompt_pages = pages_needed(plen, self.ps)
        hashes, hits = self._plan_prefix_hits(req, prompt)
        # keep ≥ 1 suffix token so the prompt's last-position logits (the
        # first generated token) come out of the final chunk
        hits = hits[: min(len(hits), (plen - 1) // self.ps)]
        need = n_prompt_pages - self._n_hbm_hits(hits)
        if self._available_pages() < need + self.watermark:
            return False  # same memory policy; only compute is deferred

        self._grow_tables(pages_needed(plen + req.max_new + 1, self.ps))
        table = np.full((self.tables.shape[1],), NULL_PAGE, np.int32)
        try:
            # cacheable = full pages minus the hit deliberately trimmed above
            n_claimed = self._claim_hits(hashes, hits, (plen - 1) // self.ps,
                                         table)
        except BaseException:
            # a corrupt host swap-in mid-claim: free what was claimed so
            # far (the pages live only in the local ``table`` here)
            for pid in table:
                self._drop_page(int(pid))
            raise
        self._c["prefill_tokens_skipped"].inc(n_claimed * self.ps)
        self.telemetry.on_admit(req, time.perf_counter())

        self.tables[slot_idx] = table
        self.slots[slot_idx] = _PagedSlot(
            req=req, pos=n_claimed * self.ps, admit_seq=self._admit_counter,
            mode="prefill", pending=prompt, hashes=hashes,
        )
        self._admit_counter += 1
        if req.n_samples > 1:
            # hold the sibling slots across the (multi-tick) prefill so the
            # fork at completion cannot find them taken; _free_slot releases
            # the claims if this parent is preempted before it forks
            others = [
                j for j, s in enumerate(self.slots)
                if s.req is None and s.reserved_by is None and j != slot_idx
            ]
            assert len(others) >= req.n_samples - 1, "admission gate broken"
            for j in others[: req.n_samples - 1]:
                self.slots[j].reserved_by = slot_idx
        return True

    def _finish_if_budget_spent(self, i: int) -> bool:
        """Retire a slot whose prefill's first token already exhausted the
        generation budget (a preemption-resumed request whose
        pre-preemption output had reached max_new) — without this,
        re-admission would emit one token beyond the greedy-exact
        reference.  Deliberately does NOT check EOS here: the contiguous
        engine decodes past a first-token EOS too, and engine-vs-engine
        token equivalence is the contract."""
        slot = self.slots[i]
        req = slot.req
        if len(req.out) >= req.max_new + 1:
            req.done = True
            self.telemetry.on_finish(req, time.perf_counter())
            self.finished.append(req)
            self._free_slot(i)
            return True
        return False

    def _admit(self) -> int:
        admitted = 0
        with self.telemetry.span("admit") as args:
            while self.queue:
                free = [
                    i for i, s in enumerate(self.slots)
                    if s.req is None and s.reserved_by is None
                ]
                req = self.queue[0]
                if not free or req.n_samples > len(free):
                    break  # head-of-line waits for a slot (or n sibling slots)
                try:
                    ok = self._try_admit(req, free[0])
                except Exception as exc:
                    if self.strict:
                        raise
                    # containment: admission blew up mid-flight (injected alloc
                    # flake, exhaustion the watermark should have prevented, a
                    # poisoned prefill).  _try_admit already rolled its page
                    # claims back; retry a transient failure a few times from
                    # the head, then fail the REQUEST instead of the loop.
                    self.queue.popleft()
                    if isinstance(exc, pages_lib.PageCorruptionError):
                        # NO retry: a retry would succeed via recompute and
                        # mask the integrity failure — quarantine the owner
                        # (only this request ever referenced the bad bytes)
                        self._finish_error(
                            req, "quarantined",
                            f"swap-in integrity failure: {exc}",
                        )
                        break
                    req._admit_retries += 1
                    if req._admit_retries <= 3:
                        self.queue.appendleft(req)
                        self.telemetry.instant(
                            "admit_retry", rid=int(req.rid),
                            attempt=req._admit_retries,
                        )
                    else:
                        self._finish_error(
                            req, "quarantined",
                            f"admission failed after {req._admit_retries - 1} "
                            f"retries: {type(exc).__name__}: {exc}",
                        )
                    break
                if not ok:
                    break  # admission control: head-of-line blocks until pages free
                self.queue.popleft()
                admitted += 1
            args["admitted"] = admitted
            args["queue"] = len(self.queue)
        return admitted

    def _start_decode(self, i: int, logits) -> None:
        """Prefill for slot i just produced the prompt's last-position
        logits: emit the first token(s) and start decoding.  A request
        with ``n_samples > 1`` FORKS here into n sibling slots sharing
        every prompt page by refcount — one ``PagePool.ref`` per sibling
        per page, zero page copies, zero recompute.  Each sibling is its
        own Request (same rid, distinct sample_idx) with a private output
        list and block-table row; the first write on the shared partial
        tail page COWs it in ``_ensure_tail_page``."""
        slot = self.slots[i]
        parent = slot.req
        now = time.perf_counter()
        nxt, finite = self._row_stats(logits)
        if (
            finite is not None
            and self.faults is not None
            and self.faults.poison_logits(self._tick, i)
        ):
            finite[0] = False
        if finite is not None and not bool(finite[0]):
            # raises to the caller (admission / chunk tick), which
            # quarantines this slot — the request holds its pages here, so
            # teardown is _free_slot, not an admission rollback
            raise NonFiniteLogitsError(
                f"non-finite logits at prefill completion (rid={parent.rid})"
            )
        if parent.keep_prompt_logits:
            parent.prompt_logits = np.asarray(logits[0, -1], np.float32)
        greedy_tok = int(nxt[0])
        row = None if parent.sampling.greedy else logits[0, -1, :]
        if parent.n_samples == 1:
            if self.faults is not None:
                self.faults.sampler_raises(self._tick, i)
            tok = pick_token(row, greedy_tok, parent, slot.pos)
            parent.out.append(tok)
            self._next_tok[i] = tok
            self._chained[i] = False  # host-known token: prefill just set it
            parent._progress_tick = self._tick
            self.telemetry.on_first_token(parent, now)
            self._finish_if_budget_spent(i)
            return
        # sibling slots: the ones chunked admission reserved for this
        # parent first, then any free unreserved slot (non-chunked
        # admission verified the count before prefilling)
        n = parent.n_samples  # captured: sibling 0's demotion resets it
        res = [j for j, s in enumerate(self.slots) if s.req is None and s.reserved_by == i]
        free = [
            j for j, s in enumerate(self.slots)
            if s.req is None and s.reserved_by is None and j != i
        ]
        sibs = [i] + (res + free)[: n - 1]
        assert len(sibs) == n, "fork found too few sibling slots"
        shared = live_pages(self.tables[i])
        children = []
        for s_idx, j in enumerate(sibs):
            if j == i:
                # the submitted Request object itself becomes sibling 0, so
                # the caller's req.done / req.out polling contract holds for
                # forked requests too; demote n_samples so a later
                # preemption requeues it as a single sequence, never
                # re-forking
                child = parent
                child.n_samples = 1
                child.sample_idx = 0
            else:
                child = Request(
                    rid=parent.rid, prompt=parent.prompt, max_new=parent.max_new,
                    sampling=parent.sampling, sample_idx=s_idx,
                )
                self.telemetry.on_fork_child(parent, child, now)
                for pid in shared:
                    self.pool_mgr.ref(pid)  # one ref per sibling per page
                self.tables[j] = self.tables[i]
                self.slots[j] = _PagedSlot(
                    req=child, pos=slot.pos, admit_seq=self._admit_counter
                )
                self._admit_counter += 1
            children.append((j, child))
        self._c["forks"].inc()
        self._c["shared_pages"].inc(len(shared) * (n - 1))
        # emit first tokens only after every sibling holds its refs — a
        # budget-spent sibling retiring here must not free pages that the
        # remaining siblings still share.  A sampler fault on one child
        # quarantines THAT child only (its refs are already taken, so
        # teardown is an ordinary _free_slot); its siblings keep decoding.
        for j, child in children:
            try:
                if self.faults is not None:
                    self.faults.sampler_raises(self._tick, j)
                tok = pick_token(row, greedy_tok, child, self.slots[j].pos)
            except Exception as exc:
                if self.strict:
                    raise
                self._quarantine(j, exc)
                continue
            child.out.append(tok)
            self._next_tok[j] = tok
            self._chained[j] = False  # host-known token: fork just set it
            child._progress_tick = self._tick
            self.telemetry.on_first_token(child, now)
            self._finish_if_budget_spent(j)

    def _row_stats(self, logits):
        """(B,) greedy tokens + finiteness of the last-position logits,
        host-side.  One fused launch, consumed by the same device→host
        fetch the argmax already paid — the NaN guard is sync-free.  The
        finite mask is None with nan_guard off (exact legacy path)."""
        if not self.nan_guard:
            # jitted: the eager argmax dispatch here used to cost ~38% of
            # steady-state throughput (see _GREEDY_ROW)
            return np.asarray(_GREEDY_ROW(logits)), None
        nxt, fin = _ROW_STATS(logits)
        # copy: the mask is mutated by injected logits poisoning
        return np.asarray(nxt), np.array(fin)

    # ------------------------------------------------------- preemption
    def _preempt_one(self, exclude: Optional[int]) -> Optional[int]:
        """Evict the youngest active sequence (≠ exclude if possible) back
        to the queue in recompute mode.  Returns the victim slot index."""
        cands = [i for i, s in enumerate(self.slots) if s.req is not None and i != exclude]
        if not cands:
            cands = [exclude] if exclude is not None and self.slots[exclude].req else []
        if not cands:
            return None
        victim = max(cands, key=lambda i: self.slots[i].admit_seq)
        slot = self.slots[victim]
        req = slot.req
        # recompute mode: prompt grows by everything generated so far; the
        # requeued prefill then reproduces the exact continuation — greedy
        # by argmax, sampled because token keys are (seed, sample_idx,
        # absolute position), which recompute preserves (req.out is
        # shared, so tokens keep accumulating on the same list).
        # A preempted PREFILLING slot requeues its whole prompt — but its
        # already-written full pages stay registered (reclaimable), so the
        # retry's prefix hits resume roughly where the chunks left off.
        # A forked sibling requeues as its OWN prompt+output and dropped
        # only its refs (_free_slot): n_samples is already 1 post-fork, so
        # it never re-forks; a parent preempted BEFORE forking keeps
        # n_samples and forks after its re-prefill.
        # only the output suffix NOT yet folded into the prompt by an
        # earlier preemption is appended — a twice-preempted request must
        # not double-count the tokens its first requeue already folded in
        orig_plen = req._orig_plen if req._orig_plen is not None else len(req.prompt)
        folded = len(req.prompt) - orig_plen
        resumed = Request(
            rid=req.rid,
            prompt=np.concatenate([
                np.asarray(req.prompt, np.int64),
                np.asarray(req.out[folded:], np.int64),
            ]),
            max_new=req.max_new,
            _orig_plen=orig_plen,
            out=req.out,
            frames=req.frames,
            sampling=req.sampling,
            n_samples=req.n_samples,
            sample_idx=req.sample_idx,
            # same timeline object: the resumed request reports ONE submit,
            # another admit on re-entry, TTFT from the original submit
            timeline=req.timeline,
            # lifecycle guard survives preemption: deadlines/stall clocks
            # anchor to the ORIGINAL submit, a cancel mid-preemption still
            # lands, and the admission-retry budget does not reset
            deadline_s=req.deadline_s,
            max_output_stall_ticks=req.max_output_stall_ticks,
            cancelled=req.cancelled,
            _t_submit=req._t_submit,
            _progress_tick=req._progress_tick,
            _admit_retries=req._admit_retries,
        )
        req._resumed_as = resumed  # cancel() on the old handle still lands
        # layout hook: a state-checkpoint engine moves the victim's
        # checkpoint/encoder page refs onto the resumed request BEFORE the
        # slot teardown drops them — bounded replay instead of full
        # recompute (no-op for the KV layout)
        self._carry_resume_state(slot, resumed)
        self._free_slot(victim)
        self.queue.appendleft(resumed)
        self._c["preemptions"].inc()
        now = time.perf_counter()
        self.telemetry.on_preempt(resumed, now)
        self.telemetry.instant("preempt", now, rid=int(req.rid), slot=victim)
        return victim

    def _alloc_page_preempting(self, i: int) -> Optional[int]:
        """_alloc_page with preemption fallback (youngest ≠ i first).
        Returns None iff slot i itself got preempted or nothing is left.

        Pipelined engines drain the in-flight launch before resorting to
        preemption: (a) its bookkeeping may retire slots and free pages,
        making the preemption unnecessary, and (b) preemption snapshots
        ``req.out`` into the recompute prompt, which must include every
        launched token — evicting a victim with an unsynced tick would
        silently drop its newest token (greedy-exactness violation)."""
        pid = self._alloc_page()
        if pid is None and self._inflight:
            self.drain()
            if self.slots[i].req is None:
                return None  # the drain retired/quarantined slot i itself
            pid = self._alloc_page()
        while pid is None:
            if self._preempt_one(exclude=i) is None:
                return None
            if self.slots[i].req is None:
                return None  # we preempted ourselves
            pid = self._alloc_page()
        return pid

    def _ensure_tail_page(self, i: int) -> bool:
        """Make sure slot i's next write position has a private page."""
        slot = self.slots[i]
        if slot.req is None or slot.mode != "decode":
            # slot emptied by a preemption EARLIER in this same sweep (an
            # allocation here would land in a dead table row and leak on
            # the next admission's row overwrite)
            return False
        pi = slot.pos // self.ps
        pid = int(self.tables[i][pi])
        if slot.pos % self.ps == 0 and pid == NULL_PAGE:
            pid = self._alloc_page_preempting(i)
            if pid is None:
                return False
            self.tables[i][pi] = pid
            return True
        if pid != NULL_PAGE and self.pool_mgr.refcount[pid] > 1:
            # copy-on-write: tail page is shared (forked sequence) — give
            # this sequence a private copy before the token write.  The
            # copy moves every quant leaf (per-page scale/selector
            # metadata included), so siblings stay bit-exact; n siblings
            # pay n-1 copies (the last writer finds refcount 1 and keeps
            # the original).
            new = self._alloc_page_preempting(i)
            if new is None:
                return False
            self.pool = self._copy_page(self.pool, pid, new)
            self._c["cow_copies"].inc()
            self.telemetry.instant("cow_copy", src=int(pid), dst=int(new))
            self._drop_page(pid)  # source may have hit refcount 0 meanwhile
            self.tables[i][pi] = new
        return True

    # ------------------------------------------------------ chunked prefill
    def _chunk_bucket(self, c: int) -> int:
        """Chunk-length shape bucket: full chunks keep ``prefill_chunk``
        (page-aligned by construction); a ragged final chunk rounds up to
        the next power of two (≤ prefill_chunk) — ≤ log2(prefill_chunk)+1
        distinct token shapes ever reach the chunk step."""
        if c >= self.prefill_chunk:
            return self.prefill_chunk
        return _pow2_bucket(c, self.prefill_chunk)

    def _chunk_fn(self, c: int, n_cp: int):
        """The jitted chunk step, cached per (chunk bucket, pages-per-chunk)
        in the shared per-api cache — the same retrace cadence the
        shape-bucketed multi-array step already had."""
        fn, _ = api_jit(
            self.api, ("chunk_step", int(c), int(n_cp)),
            _make_packed_chunk(self.api.prefill_from_pages_fn, int(c), int(n_cp)),
        )
        return fn

    def _chunk_step_packed(self, params, packed, c: int, n_cp: int):
        """One chunk-tick launch over the consolidated packed transfer."""
        return self._chunk_fn(c, n_cp)(params, self.pool, packed)

    def _prefill_tick_all(self) -> int:
        """Advance EVERY prefilling slot by one chunk in a SINGLE
        ``prefill_from_pages`` launch (stacked block tables / chunk starts
        / scatter ids, per-slot chunk_len masks) — one kernel launch per
        tick regardless of how many slots are prefilling, where the old
        per-slot loop paid one launch each.  Allocates each slot's chunk
        pages first (slot order, preempting if dry — a slot preempted by a
        later slot's allocation drops out of the batch), pads the batch
        and chunk axes to power-of-two buckets, then registers freshly
        completed full pages and flips finished slots to decode mode.
        Returns the number of slots that advanced."""
        plans: dict[int, tuple[int, int, np.ndarray]] = {}
        for i in range(self.n_slots):
            slot = self.slots[i]
            if slot.req is None or slot.mode != "prefill":
                continue
            start = slot.pos  # page-aligned: chunks are page multiples
            c = min(self.prefill_chunk, len(slot.pending) - start)
            first_page = start // self.ps
            n_cp = pages_needed(c, self.ps)
            ids = np.full((n_cp,), NULL_PAGE, np.int32)
            ok = True
            for k in range(n_cp):
                pid = self._alloc_page_preempting(i)
                if pid is None:
                    ok = False  # slot preempted (requeued) or pool truly dry
                    break
                self.tables[i][first_page + k] = pid
                ids[k] = pid
            if ok:
                plans[i] = (start, c, ids)
        # a later slot's allocation may have preempted an earlier planned
        # slot — keep only slots still prefilling (their pages were freed)
        batch = [
            i for i in plans
            if self.slots[i].req is not None and self.slots[i].mode == "prefill"
        ]
        if not batch:
            return 0

        c_bucket = self._chunk_bucket(max(plans[i][1] for i in batch))
        n_cp_b = pages_needed(c_bucket, self.ps)
        bb = _pow2_bucket(len(batch), self.n_slots)
        w = self.tables.shape[1]
        # a slot finishes its prompt: the logits are consumed on host
        # right below, so syncing them is free — and it makes the timing
        # split exact for exactly the ticks that produce tokens.
        # Mid-prompt ticks skip the sync to keep host/device overlap
        # unless profile_sync asks for an exact split.
        synced = self.profile_sync or any(
            plans[i][0] + plans[i][1] == len(self.slots[i].pending) for i in batch
        )
        # the page-gather grid a layer runs: row r attends over
        # n_past + c_bucket tokens, a padding row (n_past 0) over c_bucket
        kv = np.full((bb,), c_bucket, np.int64)
        kv[: len(batch)] += [plans[i][0] for i in batch]
        if self.faults is not None:
            self.faults.delay_launch(self._tick, key=2)
        with self.telemetry.span(
            "prefill_launch", slots=len(batch),
            tokens=int(sum(plans[i][1] for i in batch)), tick=self._tick,
            rows_launched=bb, chunk_bucket=c_bucket, synced=bool(synced),
            grid_steps=int(page_counts(kv, self.ps, w).sum()),
        ):
            # one packed int32 staging array → ONE host→device transfer
            # per chunk tick (tokens | n_past | scatter ids | chunk_len |
            # table); NULL_PAGE == 0, so zero-init doubles as the id/table
            # padding
            packed = np.zeros((bb, c_bucket + 2 + n_cp_b + w), np.int32)
            for r, i in enumerate(batch):
                start, c, ids = plans[i]
                packed[r, :c] = self.slots[i].pending[start : start + c]
                packed[r, c_bucket] = start
                packed[r, c_bucket + 1 : c_bucket + 1 + len(ids)] = ids
                packed[r, c_bucket + 1 + n_cp_b] = c
                packed[r, c_bucket + 2 + n_cp_b :] = self.tables[i]
            t0 = time.perf_counter()
            logits, self.pool = self._chunk_step(
                self.params, jnp.asarray(packed), c_bucket, n_cp_b
            )
            if synced:
                logits = jax.block_until_ready(logits)
                self._c_syncs.inc()
            t1 = time.perf_counter()
        self._c["t_prefill_s"].inc(t1 - t0)
        self._c["prefill_launches"].inc()

        for r, i in enumerate(batch):
            start, c, _ = plans[i]
            slot = self.slots[i]
            slot.pos = start + c
            self._c["prefill_chunks"].inc()
            self._c["prefill_tokens"].inc(c)
            self.telemetry.on_chunk(slot.req, t1, c)
            if self.prefix_caching:
                first_page = start // self.ps
                for p in range(first_page, min(slot.pos // self.ps, len(slot.hashes))):
                    self.prefix.register(slot.hashes[p], int(self.tables[i][p]))
            if slot.pos == len(slot.pending):  # prompt done — start decoding
                slot.mode = "decode"
                slot.pending = None
                slot.hashes = None
                try:
                    self._start_decode(i, logits[r : r + 1])  # forks if n > 1
                except Exception as exc:
                    if self.strict:
                        raise
                    self._quarantine(i, exc)
        return len(batch)

    # ------------------------------------------------------------- ticks
    def _active(self):
        return [i for i, s in enumerate(self.slots) if s.req is not None]

    def _decoding(self):
        return [i for i, s in enumerate(self.slots) if s.req is not None and s.mode == "decode"]

    def _retire_pending(self, i: int) -> bool:
        """True when slot i's in-flight launch is GUARANTEED to retire it
        at sync regardless of which token comes back: the budget and
        capacity stop rules of ``sequence_finished`` are token-independent
        (only EOS is speculative).  Such a slot must not join the next
        launch — it would generate one token past the budget — and must
        not allocate a tail page it will never write."""
        if not self._chained[i]:
            return False  # no unsynced launch — host state is current
        slot = self.slots[i]
        pending = sum(
            1 for r in self._inflight for (j, rq, _) in r.rows
            if j == i and rq is slot.req
        )
        cap = self._seq_capacity() if self.chunked else self.max_len
        return (
            len(slot.req.out) + pending >= slot.req.max_new + 1
            or slot.pos >= cap - 1
        )

    def _launch_decode(self, active: list, quiet: bool) -> float:
        """Enqueue ONE fused decode launch for ``active`` and push its
        in-flight record — no host/device sync.  Token sources: the host
        ``_next_tok`` row for freshly (re)started slots, the device
        ``_chain_tok`` merge for slots whose previous tick is in flight
        (or just synced) — either way the values are identical, so depth
        1 and depth 2 produce the same tokens by construction.  Sampling
        slots overlay a device-side ``_sample_row`` draw (same jitted
        function, same (seed, sample_idx, position) key as the host
        sampler — bit-identical) so the merged choice never leaves the
        device.  Returns the launch-start timestamp."""
        w = self.tables.shape[1]
        if self._packed.shape[1] != 3 + w:
            self._packed = np.zeros((self.n_slots, 3 + w), np.int32)
        pk = self._packed
        pk[:, 0] = self._next_tok
        pk[:, 1] = (~self._chained).astype(np.int32)
        pk[:, 2] = 0
        # mask non-decoding rows (prefilling slots keep live pages in
        # self.tables) so idle-slot scatters land in the null page
        pk[:, 3:] = NULL_PAGE
        for i in active:
            pk[i, 2] = self.slots[i].pos
            pk[i, 3:] = self.tables[i]
        t0 = time.perf_counter()
        if quiet and self._last_launch_end is not None:
            # steady-state host gap: launch-to-launch wall clock minus the
            # sync waits in between = pure host scheduling/bookkeeping
            self.telemetry.decode_gap(
                max(0.0, t0 - self._last_launch_end - self._gap_sync_s)
            )
        # ship a snapshot: jax CPU may wrap numpy buffers zero-copy with
        # immutable semantics, and pk is restaged next tick while this
        # launch can still be in flight at depth > 1
        logits, nxt, fin, self.pool = self._decode(
            self.params, self.pool, jnp.asarray(pk.copy()), self._chain_tok
        )
        for i in active:
            req = self.slots[i].req
            if req.sampling.greedy:
                continue
            # the sampled token's absolute sequence index is pos + 1: the
            # cache holds ``pos`` tokens and this tick writes the consumed
            # token at ``pos`` before predicting the next one (keying by
            # ``pos`` would reuse the first token's key and break
            # recompute-preemption exactness)
            key = sampling_key(req.sampling, req.sample_idx, self.slots[i].pos + 1)
            samp = _sample_row(
                logits[i, -1, :], key,
                jnp.float32(req.sampling.temperature), req.sampling.top_k,
            )
            nxt = _SET_TOK(nxt, np.int32(i), samp)
        rows = []
        for i in active:
            slot = self.slots[i]
            slot.pos += 1  # position advances at LAUNCH (the write is
            # enqueued); token/EOS bookkeeping happens at sync
            rows.append((i, slot.req, slot.pos))
            self._chained[i] = True
        self._chain_tok = nxt
        self._inflight.append(
            _InFlight(self._tick, rows, nxt, fin)
        )
        t1 = time.perf_counter()
        self._c["decode_ticks"].inc()
        self.telemetry.pipeline_gauge(len(self._inflight))
        if self.pipeline_depth > 1:
            # depth 1 defers the time to the merged sync (legacy
            # attribution); deep mode attributes dispatch and sync apart
            self._c["t_decode_s"].inc(t1 - t0)
        self._last_launch_end = t1
        self._gap_sync_s = 0.0
        return t0

    def _sync_one(self, merge_from: Optional[float] = None) -> None:
        """Sync the OLDEST in-flight launch and book its tokens: append /
        EOS-retire / quarantine per row, exactly the bookkeeping the
        synchronous loop did — one tick later at depth 2, without changing
        which request gets demoted (fault seams key on the launch tick).
        ``merge_from`` (depth 1) folds the wait into the launch's time,
        and the caller's ``decode_tick`` span covers both, so profile-mode
        attribution matches the legacy loop exactly; otherwise the wait is
        its own ``decode_sync`` span."""
        rec = self._inflight.popleft()
        with (
            contextlib.nullcontext() if merge_from is not None
            else self.telemetry.span("decode_sync", tick=rec.tick)
        ):
            t0 = time.perf_counter()
            nxt = np.asarray(rec.nxt)  # blocks until the launch drains
            # copy: the mask is mutated by injected logits poisoning
            fin = None if rec.fin is None else np.array(rec.fin)
            self._c_syncs.inc()
            t1 = time.perf_counter()
        self._gap_sync_s += t1 - t0
        self._c["t_decode_s"].inc(t1 - (t0 if merge_from is None else merge_from))
        cap = self._seq_capacity() if self.chunked else self.max_len
        # slots with a NEWER launch still in flight: their freshest token
        # lives in _chain_tok, so booking this (older) token must NOT
        # flip them back to the host path — that would replay a stale
        # token on the next launch
        newer = {
            j for r in self._inflight for (j, rq, _) in r.rows
            if self.slots[j].req is rq
        }
        for i, req, pos in rec.rows:
            slot = self.slots[i]
            if slot.req is not req or req.done:
                continue  # speculative row: the slot retired / was
                # preempted / was torn down after this launch went out
            # per-slot fault quarantine: a poisoned row / raising sampler /
            # failed state transition demotes ONLY this request; the sync
            # completes for every other slot
            try:
                if (
                    fin is not None
                    and self.faults is not None
                    and self.faults.poison_logits(rec.tick, i)
                ):
                    fin[i] = False
                if fin is not None and not bool(fin[i]):
                    raise NonFiniteLogitsError(
                        f"non-finite decode logits (rid={req.rid}, "
                        f"slot={i})"
                    )
                if self.faults is not None:
                    self.faults.sampler_raises(rec.tick, i)
                tok = int(nxt[i])
                req.out.append(tok)
                req._progress_tick = self._tick
                self.telemetry.on_token(req, t1)
                if sequence_finished(
                    tok, len(req.out), req.max_new, pos, cap, self.eos
                ):
                    req.done = True
                    self.telemetry.on_finish(req, t1)
                    self.finished.append(req)
                    self._free_slot(i)
                else:
                    self._next_tok[i] = tok
                    if i not in newer:
                        self._chained[i] = False
            except Exception as exc:
                if self.strict:
                    raise
                self._quarantine(i, exc)

    def drain(self) -> None:
        """Sync and book every in-flight decode launch.  Public: callers
        reading ``req.out`` between manual ``step()`` calls on a
        ``pipeline_depth > 1`` engine should drain first
        (``run_to_completion`` drains on exit)."""
        while self._inflight:
            self._sync_one()
        self.telemetry.pipeline_gauge(0)

    def step(self) -> int:
        """Admit + ONE batched chunk launch covering every prefilling slot
        + ONE fused decode launch for all decoding slots (any mix of
        positions) — chunked prefill interleaves with decode instead of
        blocking admission.  Returns the number of slots served (chunks +
        decoded).  Tick order: lifecycle guard first (a freed slot admits
        THIS tick), then degradation bookkeeping, then the serving work;
        the periodic invariant audit closes the tick.

        Pipelining (``pipeline_depth``): depth 1 syncs its own launch
        before returning (legacy loop).  Depth 2 launches tick t, THEN
        syncs tick t-1 — host scheduling for t+1 overlaps the device's
        work on t, and only EOS is speculative (budget/capacity stops are
        predicted host-side, see ``_retire_pending``; a post-EOS row is
        discarded at sync).  A tick with no decode launch drains the
        pipeline — the device is idle anyway, and slots waiting on their
        final sync must retire for admission to reuse them."""
        self._tick += 1
        with self.telemetry.span("engine_step", tick=self._tick):
            self._enforce_lifecycle()
            self._update_pressure()
            admitted = self._admit()
            served = self._prefill_tick_all()

            active = []
            for i in self._decoding():
                if self._retire_pending(i):
                    continue  # retires at its pending sync below
                if self._ensure_tail_page(i):
                    active.append(i)
            active = [i for i in active if self.slots[i].req is not None
                      and self.slots[i].mode == "decode"]
            if active:
                self._decode_tick(
                    active, quiet=(served == 0 and admitted == 0),
                    span_args={"grid_steps": self._decode_grid_steps(active)},
                )
            else:
                self.drain()
            if self.audit_every and self._tick % self.audit_every == 0:
                self.audit()
        return served + len(active)

    def _decode_grid_steps(self, active: list) -> int:
        """The page-gather grid a layer of the decode launch over
        ``active`` runs: each live row attends over ``pos + 1`` tokens,
        and each idle row (length 0) takes one step."""
        kv = np.asarray([self.slots[i].pos + 1 for i in active])
        steps = page_counts(kv, self.ps, self.tables.shape[1]).sum()
        return self.n_slots - len(active) + int(steps)

    def _decode_tick(self, active: list, *args, span_args=None, **kwargs) -> None:
        """``_launch_decode(active, *args, **kwargs)`` inside the
        ``decode_tick`` span (with ``span_args`` besides its own), then
        sync while the pipeline is full.  At depth 1 the span also covers
        the merged sync (legacy attribution); deeper, each sync is its
        own ``decode_sync``."""
        if self.faults is not None:
            self.faults.delay_launch(self._tick, key=1)
        with self.telemetry.span(
            "decode_tick", n_active=len(active), tick=self._tick,
            rows_launched=self.n_slots, **(span_args or {}),
        ):
            t0 = self._launch_decode(active, *args, **kwargs)
            if self.pipeline_depth == 1:
                self._sync_one(t0)
        while len(self._inflight) >= self.pipeline_depth:
            self._sync_one()

    def run_to_completion(self, max_ticks: int = 10_000):
        """Tick until the queue and the slots drain (or max_ticks).  A
        head-of-line request the pool can NEVER serve (zero slots active,
        nothing served, queue non-empty) is shed with a typed error and
        the loop keeps serving everyone behind it — one impossible prompt
        must not wedge the engine.  ``shed_stuck=False`` restores the old
        fail-stop PagePoolExhaustedError for capacity-planning tests."""
        ticks = 0
        stuck = 0
        n_faults = len(self.faults.log) if self.faults is not None else 0
        while (self.queue or self._active()) and ticks < max_ticks:
            served = self.step()
            ticks += 1
            if self.faults is not None and len(self.faults.log) > n_faults:
                # injected faults fired this tick: a served==0 tick is
                # attributable to chaos (a flake preempting the only
                # active slot, a refused swap resume), not to a genuinely
                # unservable head-of-line request — don't count it
                n_faults = len(self.faults.log)
                stuck = 0
                continue
            if served == 0 and self.queue and not self._active():
                head = self.queue[0]
                msg = (
                    "pool too small to admit the pending request "
                    f"(need pages for {len(head.prompt)} prompt tokens, "
                    f"free={self._available_pages()}, watermark={self.watermark})"
                )
                if not self.shed_stuck:
                    raise PagePoolExhaustedError(msg)
                stuck += 1
                if stuck >= 2:  # persists past one tick — not a transient
                    # flake (an injected alloc failure clears on retry)
                    self.queue.popleft()
                    self._finish_error(head, "shed", msg)
                    stuck = 0
            else:
                stuck = 0
        self.drain()  # max_ticks can exit mid-flight at pipeline_depth > 1
        return self.finished, ticks

    # ------------------------------------------------------------ metrics
    def cache_pages_in_use(self) -> int:
        return self.pool_mgr.used()

    def snapshot(self) -> dict:
        """One JSON-able dump of everything the engine knows about itself:
        registry counters / gauges / histograms, trace counts, journal
        health, and per-request timeline summaries.  Readers should index
        the nested dicts with ``.get(..., default)`` so a renamed or
        absent metric degrades to a default instead of a KeyError
        mid-serve (see launch/serve.py)."""
        return self.telemetry.snapshot(engine=self)
