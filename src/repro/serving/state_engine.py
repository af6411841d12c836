"""StatePagedEngine: paged serving for O(1)-state families (SSM / hybrid
/ enc-dec) over the typed page store.

The KV engine (serving/engine.py) maps token positions to (page, slot)
through block tables — meaningless for families whose decode state is a
fixed-size recurrence (Mamba ssm/conv state, RG-LRU + window ring) or a
decoder slab cross-attending to a shared encoder output.  This engine
keeps the SAME request lifecycle, admission control, preemption,
pipelined tick loop, fault containment, and telemetry (it subclasses
PagedEngine's layout-independent core) but swaps the storage layout:

* **live tree** — ONE resident batch-``n_slots`` family cache tree
  (``api.live_cache_init``); each engine slot owns row i.  Decode is one
  fused per-row launch over the whole tree (``api.state_decode_fn`` with
  a (B,) position vector), so heterogeneous positions batch exactly like
  the KV engine's paged decode.

* **state pages** (kind ``state``) — at every page-aligned position
  ((pos+1) % page_size == 0) a slot checkpoints its row verbatim into
  its state page (``pages.state_checkpoint_rows`` rides the decode
  launch — the scatter costs one extra device write every page_size
  ticks, nothing on other ticks).  The page holds the family cache's
  exact bytes (quantized leaves included), so restore is bit-exact.
  Preemption hands the page to the resumed request: re-admission
  restores the checkpoint and replays only the tokens past it — at most
  ``page_size`` decode steps (vs the KV engine's full-prompt recompute)
  — then rejoins the batch.  Replay uses the same per-row decode fn at
  batch 1, so greedy outputs are bit-identical to a never-preempted run.
  A checkpoint that cannot allocate (pool dry, injected alloc failure)
  is SKIPPED gracefully: the replay bound degrades, exactness does not.

* **shared_ro pages** (enc-dec) — the Whisper encoder output
  (per-layer cross K/V) is request-independent given the audio, so it is
  keyed by the frames' content hash through serving/prefix.py and
  published once into a read-only page.  Every later request over the
  same audio takes a refcount (zero encoder FLOPs — decoder-only prefill
  against the gathered page) and the last deref parks the page in the
  prefix LRU exactly like a reclaimable KV prefix page.

Forking (best-of-n) copies live rows (``state_copy_row``) and shares the
checkpoint + encoder pages by refcount; a sibling's first page-boundary
checkpoint allocates a private page instead of writing the shared one
(divergence = new page, not COW — the checkpoint overwrites wholesale).

Scoping (documented, deliberate): prompts must fit max_len (state
families have no chunked prefill — the prompt runs as ONE prefill
launch); the hybrid family's window-KV ring rides inside its state page
(it is O(window), not O(seq)); the enc-dec "state" page checkpoints the
decoder self-KV slab up to max_len (O(max_len) — splitting it into kv
pages is roadmap follow-up).
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving import pages as pages_lib
from repro.serving.engine import (
    NonFiniteLogitsError,
    PagedEngine,
    PagePoolExhaustedError,
    PromptTooLongError,
    _InFlight,
    _SET_TOK,
)
from repro.serving.generate import (
    Request,
    _sample_row,
    api_jit,
    pick_token,
    sampling_key,
)
from repro.serving.pages import (
    KIND_SHARED_RO,
    KIND_STATE,
    NULL_PAGE,
    PagePool,
)
from repro.serving.prefix import PrefixCache


def _make_fused_state_decode(fn, guard: bool, axes, shared_enc: bool,
                             do_ckpt: bool):
    """One fused launch: chained-token select → per-row decode over the
    live tree → in-launch argmax (+ finite mask) → optional checkpoint
    scatter of the UPDATED rows into their destination pages.

    ``packed`` (B, 4+E) int32: next_tok / token-source flag / position /
    checkpoint page (NULL_PAGE = no checkpoint for that row) / enc-dec
    shared page id.  Two traced variants per guard flag (with / without
    the checkpoint scatter) so non-boundary ticks skip the full-tree
    write entirely."""

    def fused(params, live, spool, enc_pool, packed, chain_tok):
        tok = jnp.where(packed[:, 1] == 1, packed[:, 0], chain_tok)
        shared = (enc_pool, packed[:, 4]) if shared_enc else None
        logits, live = fn(params, live, tok[:, None], packed[:, 2], shared)
        row = logits[:, -1, :]
        nxt = jnp.argmax(row, axis=-1).astype(jnp.int32)
        fin = jnp.all(jnp.isfinite(row), axis=-1) if guard else None
        if do_ckpt:
            spool = pages_lib.state_checkpoint_rows(
                spool, live, axes, packed[:, 3]
            )
        return logits, nxt, fin, live, spool

    return fused


@dataclasses.dataclass
class _StateSlot:
    req: Optional[Request] = None
    pos: int = 0  # tokens the row's state currently covers
    admit_seq: int = 0
    mode: str = "decode"  # always 'decode' (no chunked prefill) — kept so
    # the inherited scheduler's mode checks hold
    reserved_by: Optional[int] = None  # inherited-_admit compatibility
    ckpt_page: Optional[int] = None  # state page (None = alloc-starved)
    ckpt_pos: int = 0  # tokens the checkpoint covers
    enc_page: Optional[int] = None  # shared_ro encoder page (enc-dec)


class StatePagedEngine(PagedEngine):
    """Continuous batching for state-checkpoint families over typed pages.

    Inherits the layout-independent core of PagedEngine — submit /
    lifecycle guard / shedding / degraded mode / pipelined sync loop /
    quarantine / health / snapshot — and overrides the storage layout:
    no block tables, one live cache tree + state/shared_ro pages."""

    PAGE_LAYOUT = "state"

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
        profile_sync: bool = False,
        pipeline_depth: int = 1,
        telemetry=None,
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
    ):
        spec = getattr(api, "page_spec", None)
        if spec is None or spec.layout != "state_checkpoint":
            from repro.models.zoo import UnsupportedModelError

            cfg = getattr(api, "cfg", None)
            raise UnsupportedModelError(
                getattr(cfg, "name", "?"), getattr(cfg, "family", "?"),
                reason="StatePagedEngine serves state_checkpoint layouts; "
                "kv_paged families serve through serving.engine.PagedEngine.",
            )
        assert max_len % page_size == 0, "page_size must divide max_len"
        self._init_shared(
            api, params, n_slots, max_len, page_size, eos_id, prefix_caching,
            profile_sync, pipeline_depth, telemetry, fault_injector, strict,
            nan_guard, audit_every, max_queue, shed_stuck, degrade_after,
            recover_after, degraded_prefix_target, host_pages,
        )
        self.spec = spec
        self.shared_enc = bool(spec.shared_encoder)
        # A tick never REQUIRES an allocation (checkpoints skip when dry),
        # so the admission watermark defaults to 0 — admission just needs
        # its own 1–2 pages free.
        self.watermark = 0 if watermark is None else watermark
        if n_pages is None:
            # per slot: a checkpoint page + transient headroom for a fork
            # sibling's private-divergence page; plus parked encoder pages
            n_pages = 1 + n_slots * (3 if self.shared_enc else 2) + 4
        self.pool_mgr = PagePool(n_pages)
        self.prefix = PrefixCache()  # shared_ro pages: frames-hash → page

        self.slots = [_StateSlot() for _ in range(n_slots)]
        # live cache tree: one row per slot; batch axes discovered by
        # shape-diffing so any family / quant layout works unmodified
        init = api.live_cache_init
        self.live = init(n_slots, max_len)
        self.axes = pages_lib.state_batch_axes(lambda b: init(b, max_len))
        self.spool = pages_lib.state_pool_init(
            lambda b: init(b, max_len), self.axes, n_pages
        )
        self.enc_pool = (
            api.enc_pool_init(n_pages) if self.shared_enc else None
        )

        axes = self.axes
        self._prefill, c_pre = api_jit(
            api, ("state_prefill", max_len),
            lambda p, t, _a=api, _ml=max_len: _a.prefill_fn(p, {"tokens": t}, _ml),
        )
        self._decode_fns = {}
        for dc in (False, True):
            self._decode_fns[dc], c_dec = api_jit(
                api, ("state_decode_fused", bool(nan_guard), dc),
                _make_fused_state_decode(
                    api.state_decode_fn, bool(nan_guard), axes,
                    self.shared_enc, dc,
                ),
            )
        self._ckpt_rows, _ = api_jit(
            api, ("state_ckpt_rows",),
            lambda sp, lv, d, _ax=axes: pages_lib.state_checkpoint_rows(
                sp, lv, _ax, d
            ),
        )
        self._restore_one, _ = api_jit(
            api, ("state_restore_one", max_len),
            lambda sp, pid, _a=api, _ml=max_len, _ax=axes: (
                pages_lib.state_restore_row(_a.live_cache_init(1, _ml), sp, _ax, 0, pid)
            ),
        )
        self._replay_step, _ = api_jit(
            api, ("state_replay",),
            (
                (lambda p, one, t, pos, ep, pid, _f=api.state_decode_fn:
                 _f(p, one, t, pos, (ep, pid)))
                if self.shared_enc
                else (lambda p, one, t, pos, _f=api.state_decode_fn:
                      _f(p, one, t, pos, None))
            ),
        )
        self._insert_row, _ = api_jit(
            api, ("state_insert",),
            lambda lv, on, r, _ax=axes: pages_lib.state_insert_row(lv, on, _ax, r),
        )
        self._copy_row, _ = api_jit(
            api, ("state_copy_row",),
            lambda lv, s, d, _ax=axes: pages_lib.state_copy_row(lv, _ax, s, d),
        )
        if self.shared_enc:
            self._enc_encode, _ = api_jit(
                api, ("enc_encode",), api.encode_xkv_fn
            )
            self._enc_store, _ = api_jit(api, ("enc_store",), api.enc_store_fn)
            self._prefill_xkv, _ = api_jit(
                api, ("state_prefill_xkv", max_len),
                lambda p, t, ep, pid, _a=api, _ml=max_len: _a.prefill_with_xkv_fn(
                    p, {"tokens": t}, _ml,
                    (ep[0][pid][:, None], ep[1][pid][:, None]),
                ),
            )
        self._trace_counters = {"prefill": c_pre, "decode": c_dec}
        self._trace_base = {k: v["traces"] for k, v in self._trace_counters.items()}
        self._trace_base["chunk"] = self._chunk_traces_total()
        # packed launch row: tok / use_host / pos / ckpt_dst / enc_pid
        self._packed = np.zeros((n_slots, 5), np.int32)
        # state-layout extras (registry counters; surfaced by health())
        _reg = self.telemetry.registry
        self._cs = {
            k: _reg.counter(k)
            for k in ("state_checkpoints", "state_restores", "replay_tokens",
                      "ckpt_skips", "encoder_launches")
        }

    # ----------------------------------------------- host-tier layout hooks
    # the host tier accepts state checkpoint pages; parked shared_ro
    # encoder pages stay re-encodable (plain eviction) by the kind gate
    HOST_SWAP_KIND = KIND_STATE

    def _fetch_page_arrays(self, pid: int) -> list:
        return pages_lib.state_page_fetch(self.spool, self.axes, pid)

    def _insert_page_arrays(self, pid: int, arrays) -> None:
        self.spool = pages_lib.state_page_insert(
            self.spool, self.axes, arrays, pid
        )

    # ----------------------------------------------------------- plumbing
    def _free_slot(self, i: int):
        s = self.slots[i]
        if s.ckpt_page is not None:
            self._drop_page(s.ckpt_page)
        if s.enc_page is not None:
            self._drop_page(s.enc_page)  # parks via prefix when last ref
        self.slots[i] = _StateSlot()
        self._chained[i] = False  # any in-flight row for i is now dead
        for s2 in self.slots:
            if s2.reserved_by == i:
                s2.reserved_by = None

    def _host_carry_state(self, slot: _StateSlot, resumed: Request) -> bool:
        """Snapshot the victim's LIVE row (not its up-to-page_size-stale
        checkpoint) to a pinned host-tier entry, staged through a state
        page: re-admission then restores the exact preemption-point state
        and replays ZERO tokens.  Refusals (tier off, injected swap_out
        fault, tier full of pinned entries, alloc-starved staging,
        unsynced in-flight row, pending fork) return False — the
        checkpoint-replay carry below still bounds the replay."""
        tier = self.host_tier
        if (
            tier is None or slot.pos <= 0 or resumed.n_samples > 1
            # an unsynced in-flight launch means the row covers one token
            # whose result was never folded into ``out`` — only the
            # recompute/replay paths can regenerate it
            or slot.pos != len(resumed.prompt) - 1
        ):
            return False
        if self.faults is not None and self.faults.swap_out_fails(
            self._tick, key=int(resumed.rid)
        ):
            self._cs_swap["swap_skips"].inc()
            return False
        while tier.full():
            ev = tier.evict_lru()
            if ev is None:
                self._cs_swap["swap_skips"].inc()
                return False  # every host entry pinned
            self.prefix.host_forget(ev[0])
        i = self.slots.index(slot)
        # stage the live row through a state page.  A private checkpoint
        # page is overwritten in place (its ckpt_pos advances with it, so
        # the checkpoint carry stays consistent); a fork-shared page must
        # survive for the siblings, so stage through a transient page.
        if (
            slot.ckpt_page is not None
            and self.pool_mgr.refcount[slot.ckpt_page] == 1
        ):
            stage_pid, transient = slot.ckpt_page, False
        else:
            stage_pid = self._alloc_page(KIND_STATE)
            if stage_pid is None:
                self._cs_swap["swap_skips"].inc()
                return False
            transient = True
        dsts = np.full((self.n_slots,), NULL_PAGE, np.int32)
        dsts[i] = stage_pid
        self.spool = self._ckpt_rows(self.spool, self.live, jnp.asarray(dsts))
        if not transient:
            slot.ckpt_pos = slot.pos
        arrays = self._fetch_page_arrays(stage_pid)
        if transient:
            self._drop_page(stage_pid)
        handle = tier.put(
            arrays, KIND_STATE, pinned=True, meta={"rid": int(resumed.rid)}
        )
        resumed._host_state_resume = (handle, slot.pos)
        self._cs_swap["swap_outs"].inc()
        self._cs_swap["swap_bytes"].inc(sum(a.nbytes for a in arrays))
        self.telemetry.instant(
            "swap_out_preempt", rid=int(resumed.rid), pages=1
        )
        return True

    def _carry_resume_state(self, slot: _StateSlot, resumed: Request) -> None:
        """Move the victim's checkpoint (and encoder page) refs onto the
        resumed request BEFORE _free_slot drops them: re-admission then
        restores + replays ≤ page_size tokens instead of the full prompt.
        With the host tier, the live row is ALSO snapshotted to a pinned
        host entry — re-admission restores it verified and replays zero
        tokens; the checkpoint ref rides along as the swap-in-refusal
        fallback."""
        self._host_carry_state(slot, resumed)
        if slot.ckpt_page is not None:
            resumed._state_resume = (slot.ckpt_page, slot.ckpt_pos)
            slot.ckpt_page = None  # ref travels with the queued request
        if slot.enc_page is not None:
            resumed._enc_page = slot.enc_page
            slot.enc_page = None

    def _drop_host_state_handle(self, req: Request) -> None:
        hsr = getattr(req, "_host_state_resume", None)
        if hsr is not None:
            if self.host_tier is not None:
                self.host_tier.drop(hsr[0])
            req._host_state_resume = None

    def _release_carried(self, req: Request) -> None:
        self._drop_host_state_handle(req)
        carried = getattr(req, "_state_resume", None)
        if carried is not None:
            self._drop_page(int(carried[0]))
            req._state_resume = None
        enc = getattr(req, "_enc_page", None)
        if enc is not None:
            self._drop_page(int(enc))
            req._enc_page = None

    def _frames_hash(self, req: Request) -> bytes:
        h = getattr(req, "_frames_digest", None)
        if h is None:
            f = np.asarray(req.frames, np.float32)
            d = hashlib.blake2b(digest_size=16)
            d.update(np.asarray(f.shape, "<i8").tobytes())
            d.update(f.tobytes())
            h = d.digest()
            req._frames_digest = h
        return h

    # ----------------------------------------------------------- admission
    def _claim_enc_page(self, req: Request, acquired: list) -> Optional[int]:
        """Resolve the request's shared_ro encoder page: carried across a
        preemption, prefix hit (revive/ref — zero encoder FLOPs), or
        encode-and-publish on a miss.  Appends newly taken refs to
        ``acquired`` for exception rollback."""
        carried = getattr(req, "_enc_page", None)
        if carried is not None:
            req._enc_page = None  # ownership moves to the slot
            acquired.append(int(carried))
            return int(carried)
        h = self._frames_hash(req)
        pid = self.prefix.peek(h)
        if (
            pid is not None
            and self.faults is not None
            and self.faults.drop_prefix_claim(self._tick, key=int(req.rid))
        ):
            pid = None  # injected racing eviction: force re-encode
        if pid is not None:
            claimed = self.prefix.lookup(h)
            assert claimed == pid
            if self.pool_mgr.refcount[pid] == 0:
                self.pool_mgr.revive(pid, KIND_SHARED_RO)
            else:
                self.pool_mgr.ref(pid)
            acquired.append(pid)
            self._c["prefix_hits"].inc()
            # encoder FLOPs avoided: the whole frame sequence
            self._c["prefill_tokens_skipped"].inc(
                int(np.asarray(req.frames).shape[0])
            )
            return pid
        pid = self._alloc_page(KIND_SHARED_RO)
        if pid is None:
            raise PagePoolExhaustedError(
                "allocator dry claiming a shared_ro encoder page"
            )
        acquired.append(pid)
        frames = jnp.asarray(np.asarray(req.frames, np.float32))[None]
        xkv = self._enc_encode(self.params, frames)
        self.enc_pool = self._enc_store(self.enc_pool, xkv, jnp.int32(pid))
        self._cs["encoder_launches"].inc()
        self._c["prefix_misses"].inc()
        if self.prefix_caching:
            self.prefix.register(h, pid)
        return pid

    def _try_resume_from_host_state(self, req: Request, slot_idx: int,
                                    hsr: tuple) -> Optional[bool]:
        """Re-admit a preemption victim from its host-resident live-row
        snapshot: one verified restore at the exact preemption position —
        ZERO replay tokens (vs ≤ page_size via the HBM checkpoint, vs the
        full prompt without either).  Returns True (admitted), False
        (blocked on pages; the pinned entry survives for a retry), or
        None (fell back — handle dropped; the carried ``_state_resume``
        checkpoint ref, when present, still bounds the replay)."""
        handle, pos = hsr
        tier = self.host_tier
        plen = len(req.prompt)

        def _fallback() -> None:
            self._drop_host_state_handle(req)

        if (
            tier is None
            or not tier.has(handle)
            # the recompute path raises the typed too-long error; resuming
            # here would mask that contract
            or plen >= self.max_len
            or pos != plen - 1
        ):
            _fallback()
            return None
        if self.shared_enc and getattr(req, "_enc_page", None) is None:
            _fallback()  # lost the encoder carry: re-claim via admission
            return None
        if self.faults is not None and self.faults.swap_in_fails(
            self._tick, key=int(req.rid)
        ):
            self._cs_swap["swap_skips"].inc()
            _fallback()
            return None
        if self._available_pages() < 1 + self.watermark:
            return False  # blocked: pinned entry survives for a retry
        pid = self._alloc_page(KIND_STATE)
        if pid is None:
            # allocation flake (injected or racing): nothing consumed,
            # the checkpoint-replay path stays exact
            self._cs_swap["swap_skips"].inc()
            _fallback()
            return None
        if self.faults is not None and self.faults.swap_corrupts(
            self._tick, key=int(req.rid)
        ):
            tier.corrupt(handle)
        self._cs_swap["swap_ins"].inc()
        try:
            entry = tier.take(handle, expect_kind=KIND_STATE)
        except pages_lib.PageCorruptionError:
            self._drop_page(pid)  # fresh state page, nothing restored
            req._host_state_resume = None  # take consumed the entry
            self._cs_swap["corrupt_swapins"].inc()
            self.telemetry.instant("swap_corrupt", rid=int(req.rid))
            self._release_carried(req)
            raise  # _admit quarantines ONLY this request
        self._cs_swap["verified_swapins"].inc()
        self._cs_swap["swap_bytes"].inc(entry.nbytes)
        req._host_state_resume = None
        self._insert_page_arrays(pid, entry.arrays)
        one = self._restore_one(self.spool, jnp.int32(pid))
        self.live = self._insert_row(self.live, one, jnp.int32(slot_idx))
        self._cs["state_restores"].inc()
        # the carried HBM checkpoint (the swap-in-refusal fallback) is now
        # redundant: the restored page itself is a checkpoint at ``pos``
        carried = getattr(req, "_state_resume", None)
        if carried is not None:
            self._drop_page(int(carried[0]))
            req._state_resume = None
        enc_page = None
        if self.shared_enc:
            enc_page = int(req._enc_page)
            req._enc_page = None  # ownership moves to the slot
        self.telemetry.on_admit(req, time.perf_counter())
        self.slots[slot_idx] = _StateSlot(
            req=req, pos=pos, admit_seq=self._admit_counter,
            ckpt_page=pid, ckpt_pos=pos, enc_page=enc_page,
        )
        self._admit_counter += 1
        # rejoin decode directly: the row covers ``pos`` tokens and the
        # next fused launch consumes the resumed prompt's final token —
        # zero replay at admission (replay_tokens stays flat)
        self._next_tok[slot_idx] = int(np.asarray(req.prompt)[-1])
        self._chained[slot_idx] = False
        req._progress_tick = self._tick
        self.telemetry.instant(
            "swap_resume", rid=int(req.rid), pages=1, pos=int(pos)
        )
        self._finish_if_budget_spent(slot_idx)
        return True

    def _try_admit(self, req: Request, slot_idx: int) -> bool:
        hsr = getattr(req, "_host_state_resume", None)
        if hsr is not None:
            res = self._try_resume_from_host_state(req, slot_idx, hsr)
            if res is not None:
                return res
            # fell back (handle dropped): checkpoint-replay admission below
        prompt = np.asarray(req.prompt, np.int64)
        plen = len(prompt)
        if plen >= self.max_len:
            raise PromptTooLongError(self._too_long_msg(plen))
        resume = getattr(req, "_state_resume", None)
        need = 0 if resume is not None else 1  # the admission checkpoint
        if self.shared_enc and getattr(req, "_enc_page", None) is None:
            assert req.frames is not None, (
                "shared-encoder family needs Request.frames"
            )
            if self.prefix.peek(self._frames_hash(req)) is None:
                need += 1
        if self._available_pages() < need + self.watermark:
            return False  # admission control: wait for pages

        acquired: list[int] = []
        try:
            enc_page = (
                self._claim_enc_page(req, acquired) if self.shared_enc else None
            )
            if self.faults is not None:
                self.faults.delay_launch(self._tick, key=0)
            t0 = time.perf_counter()
            self.telemetry.on_admit(req, t0)
            with self.telemetry.span(
                "prefill_launch", slots=1, tick=self._tick, rows_launched=1,
                synced=True,
            ) as span_args:
                if resume is not None:
                    # bounded replay: restore the checkpoint, replay only the
                    # tokens past it (≤ page_size by the boundary-checkpoint
                    # cadence), batch-1 through the same per-row decode fn
                    pid, cpos = int(resume[0]), int(resume[1])
                    one = self._restore_one(self.spool, jnp.int32(pid))
                    self._cs["state_restores"].inc()
                    logits = None
                    for k in range(cpos, plen):
                        t = jnp.asarray(prompt[k : k + 1], jnp.int32)[None]
                        args = (self.params, one, t, jnp.int32(k))
                        if self.shared_enc:
                            args += (self.enc_pool, jnp.asarray([enc_page], jnp.int32))
                        logits, one = self._replay_step(*args)
                    assert logits is not None, "checkpoint at/past prompt end"
                    n_replayed = plen - cpos
                    self._cs["replay_tokens"].inc(n_replayed)
                    ckpt_page, ckpt_pos = pid, cpos
                    req._state_resume = None  # ref now owned by the slot
                    acquired.append(pid)
                else:
                    tokens = jnp.asarray(prompt, jnp.int32)[None, :]
                    if self.shared_enc:
                        logits, caches = self._prefill_xkv(
                            self.params, tokens, self.enc_pool, jnp.int32(enc_page)
                        )
                        one = {"self": caches}
                    else:
                        logits, one = self._prefill(self.params, tokens)
                    n_replayed = plen
                    ckpt_page, ckpt_pos = None, 0
                span_args["tokens"] = span_args["chunk_bucket"] = n_replayed
                logits = jax.block_until_ready(logits)
                self._c_syncs.inc()
                t1 = time.perf_counter()
            self._c["t_prefill_s"].inc(t1 - t0)
            self._c["prefill_launches"].inc()
            self._c["prefill_tokens"].inc(n_replayed)
            self.telemetry.on_chunk(req, t1, n_replayed)

            self.live = self._insert_row(self.live, one, jnp.int32(slot_idx))
            if ckpt_page is None:
                # admission checkpoint: bounds the replay of a preemption
                # landing before the first page boundary.  Alloc failure
                # degrades gracefully (full-prompt replay on preemption).
                ckpt_page = self._alloc_page(KIND_STATE)
                if ckpt_page is not None:
                    acquired.append(ckpt_page)
                    dsts = np.full((self.n_slots,), NULL_PAGE, np.int32)
                    dsts[slot_idx] = ckpt_page
                    self.spool = self._ckpt_rows(
                        self.spool, self.live, jnp.asarray(dsts)
                    )
                    self._cs["state_checkpoints"].inc()
                    ckpt_pos = plen
                else:
                    self._cs["ckpt_skips"].inc()
        except BaseException:
            for pid in acquired:
                self._drop_page(pid)
            raise

        self.slots[slot_idx] = _StateSlot(
            req=req, pos=plen, admit_seq=self._admit_counter,
            ckpt_page=ckpt_page, ckpt_pos=ckpt_pos, enc_page=enc_page,
        )
        self._admit_counter += 1
        try:
            self._start_decode(slot_idx, logits)
        except Exception as exc:
            if self.strict:
                raise
            self._quarantine(slot_idx, exc)
        return True

    def _start_decode(self, i: int, logits) -> None:
        """First token(s) after prefill/replay; forks n_samples siblings
        by live-row copy + checkpoint/encoder page refcounts (no state
        recompute, no page copies — divergence allocates a private page
        at the sibling's next boundary checkpoint)."""
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
            self._chained[i] = False
            parent._progress_tick = self._tick
            self.telemetry.on_first_token(parent, now)
            self._finish_if_budget_spent(i)
            return
        n = parent.n_samples
        free = [
            j for j, s in enumerate(self.slots)
            if s.req is None and s.reserved_by is None and j != i
        ]
        sibs = [i] + free[: n - 1]
        assert len(sibs) == n, "fork found too few sibling slots"
        n_shared = (1 if slot.ckpt_page is not None else 0) + (
            1 if slot.enc_page is not None else 0
        )
        children = []
        for s_idx, j in enumerate(sibs):
            if j == i:
                child = parent
                child.n_samples = 1
                child.sample_idx = 0
            else:
                child = Request(
                    rid=parent.rid, prompt=parent.prompt, max_new=parent.max_new,
                    frames=parent.frames,
                    sampling=parent.sampling, sample_idx=s_idx,
                )
                self.telemetry.on_fork_child(parent, child, now)
                self.live = self._copy_row(
                    self.live, jnp.int32(i), jnp.int32(j)
                )
                if slot.ckpt_page is not None:
                    self.pool_mgr.ref(slot.ckpt_page)
                if slot.enc_page is not None:
                    self.pool_mgr.ref(slot.enc_page)
                self.slots[j] = _StateSlot(
                    req=child, pos=slot.pos, admit_seq=self._admit_counter,
                    ckpt_page=slot.ckpt_page, ckpt_pos=slot.ckpt_pos,
                    enc_page=slot.enc_page,
                )
                self._admit_counter += 1
            children.append((j, child))
        self._c["forks"].inc()
        self._c["shared_pages"].inc(n_shared * (n - 1))
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
            self._chained[j] = False
            child._progress_tick = self._tick
            self.telemetry.on_first_token(child, now)
            self._finish_if_budget_spent(j)

    # --------------------------------------------------------- checkpoints
    def _ensure_private_ckpt(self, i: int) -> int:
        """The row checkpoints THIS tick: make sure it owns a private
        state page (a fork-shared page must not be overwritten — siblings
        restore from it).  Returns the destination page, or NULL_PAGE to
        skip (alloc-starved: replay bound degrades, exactness does not)."""
        s = self.slots[i]
        if s.ckpt_page is not None and self.pool_mgr.refcount[s.ckpt_page] == 1:
            pid = s.ckpt_page
        else:
            pid = self._alloc_page(KIND_STATE)
            if pid is None:
                self._cs["ckpt_skips"].inc()
                return NULL_PAGE
            if s.ckpt_page is not None:
                self._drop_page(s.ckpt_page)  # shared: siblings keep it
            s.ckpt_page = pid
        s.ckpt_pos = s.pos + 1  # the launch writes token ``pos`` first
        self._cs["state_checkpoints"].inc()
        return pid

    # ------------------------------------------------------------- ticks
    def _launch_decode(self, active: list, dsts: np.ndarray, quiet: bool) -> float:
        """One fused per-row decode launch over the live tree (+ the
        checkpoint scatter on boundary ticks).  Token chaining, sampled
        overlays, in-flight records, and telemetry attribution mirror the
        KV engine's launch exactly."""
        pk = self._packed
        pk[:, 0] = self._next_tok
        pk[:, 1] = (~self._chained).astype(np.int32)
        pk[:, 2] = 0
        pk[:, 3] = NULL_PAGE
        pk[:, 4] = NULL_PAGE  # idle rows gather the zero enc page
        for i in active:
            s = self.slots[i]
            pk[i, 2] = s.pos
            pk[i, 3] = dsts[i]
            if s.enc_page is not None:
                pk[i, 4] = s.enc_page
        t0 = time.perf_counter()
        if quiet and self._last_launch_end is not None:
            self.telemetry.decode_gap(
                max(0.0, t0 - self._last_launch_end - self._gap_sync_s)
            )
        do_ckpt = bool((dsts != NULL_PAGE).any())
        logits, nxt, fin, self.live, self.spool = self._decode_fns[do_ckpt](
            self.params, self.live, self.spool, self.enc_pool,
            jnp.asarray(pk.copy()), self._chain_tok,
        )
        for i in active:
            req = self.slots[i].req
            if req.sampling.greedy:
                continue
            key = sampling_key(req.sampling, req.sample_idx, self.slots[i].pos + 1)
            samp = _sample_row(
                logits[i, -1, :], key,
                jnp.float32(req.sampling.temperature), req.sampling.top_k,
            )
            nxt = _SET_TOK(nxt, np.int32(i), samp)
        rows = []
        for i in active:
            slot = self.slots[i]
            slot.pos += 1  # position advances at LAUNCH; bookkeeping at sync
            rows.append((i, slot.req, slot.pos))
            self._chained[i] = True
        self._chain_tok = nxt
        self._inflight.append(_InFlight(self._tick, rows, nxt, fin))
        t1 = time.perf_counter()
        self._c["decode_ticks"].inc()
        self.telemetry.pipeline_gauge(len(self._inflight))
        if self.pipeline_depth > 1:
            self._c["t_decode_s"].inc(t1 - t0)
        self._last_launch_end = t1
        self._gap_sync_s = 0.0
        return t0

    def step(self) -> int:
        """Admit + ONE fused per-row decode launch for every active slot.
        Boundary rows ((pos+1) % page_size == 0) ride their checkpoint
        scatter in the same launch.  Pipelining semantics (depth 1 vs 2,
        speculative EOS rows, drain-on-idle) are inherited unchanged."""
        self._tick += 1
        with self.telemetry.span("engine_step", tick=self._tick):
            self._enforce_lifecycle()
            self._update_pressure()
            admitted = self._admit()

            dsts = np.full((self.n_slots,), NULL_PAGE, np.int32)
            active = []
            for i in self._decoding():
                if self._retire_pending(i):
                    continue  # retires at its pending sync below
                if (self.slots[i].pos + 1) % self.ps == 0:
                    dsts[i] = self._ensure_private_ckpt(i)
                active.append(i)
            active = [i for i in active if self.slots[i].req is not None]
            if active:
                self._decode_tick(active, dsts, quiet=(admitted == 0))
            else:
                self.drain()
            if self.audit_every and self._tick % self.audit_every == 0:
                self.audit()
        return len(active)

    def health(self) -> dict:
        h = super().health()
        h["state_counters"] = {k: c.value for k, c in self._cs.items()}
        h["pages_by_kind"] = self.pool_mgr.used_by_kind()
        return h
