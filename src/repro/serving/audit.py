"""Invariant auditor for the paged serving engine.

The allocator, the prefix cache, and the engine's block tables are three
views of one ownership story; a page leak or a double-free is a
*disagreement between the views*, which makes it mechanically checkable.
``audit_engine`` walks all three and verifies the conservation laws the
whole serving design rests on:

* **refcount ≡ table references** — every non-null page's refcount
  equals the number of active block-table rows holding it (each row
  carries exactly one reference per page: prefix claims, fork refs and
  COW replacements all preserve this), so a page nobody's table can
  reach but whose refcount is positive is a leak, caught by name;
* **partition** — every non-null page is exactly one of: on the free
  list (refcount 0), table-referenced (refcount > 0), or parked
  reclaimable in the prefix LRU (refcount 0, contents kept).  A page in
  none of the three states is leaked; a page in two is corruption
  (e.g. simultaneously free and parked);
* **no dangling references** — no live slot references a freed page,
  empty slots hold all-NULL rows, sibling-slot reservations point at
  live parents;
* **prefix-chain consistency** — hash↔page registration is a bijection,
  registered refcount-0 pages are parked (evictable), no free page
  stays registered;
* **slot geometry** — a slot's live pages form a contiguous row prefix
  exactly covering its position (±1 for a freshly ensured tail page);
* **cross-tier partition** (host tier enabled) — every page lives in
  exactly one tier: a chain hash resolves to an HBM pid OR a host
  handle, never both; every host entry carries an integrity digest;
  pinned entries are preemption carries referenced by exactly one
  queued request, unpinned entries are prefix-registered (an entry with
  neither anchor is a host-tier leak); host handles never collide with
  HBM pids (handle base offset).

Report mode collects every violation into an :class:`AuditReport`;
fail-fast mode (``engine.audit(strict=True)`` or ``Engine(strict=True)``)
raises :class:`AuditError` on the first dirty report.  The walk is pure
host-side numpy/dict reads — no device work — so ``audit_every=N`` can
ride production ticks (tests/test_telemetry.py checks that audits add
no device sync and no retrace).
"""
from __future__ import annotations

import dataclasses

from repro.serving.pages import _HANDLE_BASE, NULL_PAGE, pages_needed


class AuditError(RuntimeError):
    """The engine's page-ownership invariants do not hold (fail-fast
    mode).  The message carries every violation found."""


@dataclasses.dataclass
class AuditReport:
    """Outcome of one invariant sweep."""

    ok: bool
    violations: list
    pages_checked: int
    slots_checked: int
    tick: int

    def raise_if_dirty(self) -> "AuditReport":
        if not self.ok:
            raise AuditError(
                f"{len(self.violations)} invariant violation(s) at tick "
                f"{self.tick}: " + "; ".join(self.violations)
            )
        return self

    def to_dict(self) -> dict:
        return {
            "ok": self.ok,
            "violations": list(self.violations),
            "pages_checked": self.pages_checked,
            "slots_checked": self.slots_checked,
            "tick": self.tick,
        }


def _gather_kv_refs(engine, free_set, bad) -> dict:
    """kv layout: references = live block-table entries, plus the
    contiguous-prefix geometry check (live pages exactly cover pos)."""
    table_refs: dict[int, int] = {}
    for i, slot in enumerate(engine.slots):
        row = engine.tables[i]
        live = [int(p) for p in row if int(p) != NULL_PAGE]
        if slot.req is None:
            if live:
                bad.append(f"empty slot {i} still references pages {live[:4]}")
            if slot.reserved_by is not None:
                parent = engine.slots[slot.reserved_by]
                if parent.req is None:
                    bad.append(
                        f"slot {i} reserved by empty slot {slot.reserved_by} "
                        "(abandoned fork reservation)"
                    )
            continue
        for pid in live:
            table_refs[pid] = table_refs.get(pid, 0) + 1
            if pid in free_set:
                bad.append(f"slot {i} references FREED page {pid}")
            if pool_refcount(engine, pid) <= 0:
                bad.append(
                    f"slot {i} references page {pid} with refcount "
                    f"{pool_refcount(engine, pid)}"
                )
        # live entries must be a contiguous prefix of the row covering pos
        n_live = len(live)
        if any(int(p) != NULL_PAGE for p in row[n_live:]):
            bad.append(f"slot {i} block-table row has a NULL hole before a live page")
        need = pages_needed(slot.pos, engine.ps)
        if n_live not in (need, need + 1):
            bad.append(
                f"slot {i} holds {n_live} pages for pos={slot.pos} "
                f"(expected {need} or {need + 1})"
            )
    return table_refs


def _gather_state_refs(engine, free_set, bad) -> dict:
    """state_checkpoint layout: references = per-slot checkpoint + encoder
    pages, plus refs a preempted-and-requeued request carries through the
    queue.  Checks kind tags (state vs shared_ro — heterogeneous kinds in
    ONE pool) and the checkpoint-position geometry (ckpt_pos ≤ pos: a
    checkpoint never claims to cover tokens the row hasn't consumed)."""
    pool = engine.pool_mgr
    refs: dict[int, int] = {}

    def take(pid, want_kind, where):
        refs[pid] = refs.get(pid, 0) + 1
        if pid in free_set:
            bad.append(f"{where} references FREED page {pid}")
        elif pool_refcount(engine, pid) <= 0:
            bad.append(
                f"{where} references page {pid} with refcount "
                f"{pool_refcount(engine, pid)}"
            )
        elif pool.kind_of(pid) != want_kind:
            bad.append(
                f"{where} expects a {want_kind!r} page but {pid} is "
                f"tagged {pool.kind_of(pid)!r}"
            )

    for i, slot in enumerate(engine.slots):
        if slot.req is None:
            if slot.ckpt_page is not None or slot.enc_page is not None:
                bad.append(
                    f"empty slot {i} still references pages "
                    f"(ckpt={slot.ckpt_page}, enc={slot.enc_page})"
                )
            if slot.reserved_by is not None:
                parent = engine.slots[slot.reserved_by]
                if parent.req is None:
                    bad.append(
                        f"slot {i} reserved by empty slot {slot.reserved_by} "
                        "(abandoned fork reservation)"
                    )
            continue
        if slot.ckpt_page is not None:
            take(int(slot.ckpt_page), "state", f"slot {i} checkpoint")
            if not (0 <= slot.ckpt_pos <= slot.pos):
                bad.append(
                    f"slot {i} checkpoint covers {slot.ckpt_pos} tokens but "
                    f"the row holds {slot.pos} (ckpt_pos must be ≤ pos)"
                )
        if slot.enc_page is not None:
            take(int(slot.enc_page), "shared_ro", f"slot {i} encoder page")
    for k, req in enumerate(engine.queue):
        carried = getattr(req, "_state_resume", None)
        if carried is not None:
            take(int(carried[0]), "state", f"queued request #{k} (rid={req.rid})")
        enc = getattr(req, "_enc_page", None)
        if enc is not None:
            take(int(enc), "shared_ro", f"queued request #{k} (rid={req.rid})")
    return refs


def pool_refcount(engine, pid: int) -> int:
    return int(engine.pool_mgr.refcount[pid])


def audit_engine(engine) -> AuditReport:
    """One full consistency sweep over (PagePool, PrefixCache, and the
    engine's page-reference structure — block tables for the kv layout,
    slot checkpoint/encoder pages for the state_checkpoint layout)."""
    pool = engine.pool_mgr
    prefix = engine.prefix
    bad: list[str] = []

    free = list(pool.free)
    free_set = set(free)
    parked = set(prefix.reclaimable)
    if len(free) != len(free_set):
        bad.append("free list contains duplicate page ids")
    if NULL_PAGE in free_set:
        bad.append("null page on the free list")
    if pool.refcount[NULL_PAGE] != 0:
        bad.append(f"null page refcount {int(pool.refcount[NULL_PAGE])} != 0")

    # ---- gather page references from the engine's layout ----------------
    if getattr(engine, "PAGE_LAYOUT", "kv") == "state":
        table_refs = _gather_state_refs(engine, free_set, bad)
    else:
        table_refs = _gather_kv_refs(engine, free_set, bad)

    # ---- per-page conservation ------------------------------------------
    for pid in range(1, pool.n_pages):
        rc = int(pool.refcount[pid])
        refs = table_refs.get(pid, 0)
        if rc < 0:
            bad.append(f"page {pid} refcount {rc} < 0")
        if rc != refs:
            bad.append(
                f"page {pid} refcount {rc} != {refs} block-table references"
            )
        is_free = pid in free_set
        is_parked = pid in parked
        states = int(is_free) + int(is_parked) + int(rc > 0)
        if states == 0:
            bad.append(
                f"page {pid} LEAKED: refcount 0, not free, not parked "
                "reclaimable"
            )
        elif states > 1:
            bad.append(
                f"page {pid} in {states} states at once "
                f"(free={is_free}, parked={is_parked}, refcount={rc})"
            )

    # ---- prefix-cache registration chain --------------------------------
    if len(prefix.by_hash) != len(prefix.hash_of):
        bad.append(
            f"prefix registration not a bijection: {len(prefix.by_hash)} "
            f"hashes vs {len(prefix.hash_of)} pages"
        )
    for h, pid in prefix.by_hash.items():
        if prefix.hash_of.get(pid) != h:
            bad.append(f"prefix hash↔page maps disagree on page {pid}")
    for pid in prefix.hash_of:
        if pid in free_set:
            bad.append(f"free page {pid} still registered in the prefix cache")
        if pool.refcount[pid] == 0 and pid not in parked:
            bad.append(
                f"registered page {pid} at refcount 0 is not parked "
                "reclaimable (unevictable orphan)"
            )
    for pid in parked:
        if pid not in prefix.hash_of:
            bad.append(f"parked page {pid} has no prefix registration")

    # ---- host tier (cross-tier partition) -------------------------------
    tier = getattr(engine, "host_tier", None)
    if tier is None:
        if prefix.host_by_hash or prefix.hash_of_handle:
            bad.append(
                f"host tier disabled but {len(prefix.host_by_hash)} prefix "
                "hashes resolve to host handles"
            )
    else:
        if tier.used() > tier.capacity:
            bad.append(
                f"host tier over capacity: {tier.used()} > {tier.capacity}"
            )
        nbytes = sum(e.nbytes for e in tier.entries.values())
        if nbytes != tier.bytes_resident:
            bad.append(
                f"host tier bytes_resident {tier.bytes_resident} != {nbytes} "
                "summed entry bytes"
            )
        # preemption carries held by queued requests: each pinned entry is
        # anchored by exactly ONE request, and never doubles as a prefix
        # chunk (one owner per entry, one tier per page)
        carried: dict[int, int] = {}
        for req in engine.queue:
            hr = getattr(req, "_host_resume", None)
            for h in (hr[0] if hr is not None else ()):
                carried[h] = carried.get(h, 0) + 1
            hsr = getattr(req, "_host_state_resume", None)
            if hsr is not None:
                carried[hsr[0]] = carried.get(hsr[0], 0) + 1
        for handle, n in carried.items():
            if n != 1:
                bad.append(f"host handle {handle} carried by {n} requests")
            e = tier.entries.get(handle)
            if e is None:
                bad.append(
                    f"queued request carries dangling host handle {handle}"
                )
            elif not e.pinned:
                bad.append(f"carried host handle {handle} is not pinned")
            if handle in prefix.hash_of_handle:
                bad.append(
                    f"host handle {handle} is both a preemption carry and a "
                    "registered prefix chunk"
                )
        # prefix host registration: a bijection onto unpinned entries, with
        # every hash resolving in exactly one tier
        if len(prefix.host_by_hash) != len(prefix.hash_of_handle):
            bad.append(
                "host prefix registration not a bijection: "
                f"{len(prefix.host_by_hash)} hashes vs "
                f"{len(prefix.hash_of_handle)} handles"
            )
        for h, handle in prefix.host_by_hash.items():
            if prefix.hash_of_handle.get(handle) != h:
                bad.append(f"host prefix maps disagree on handle {handle}")
            if not tier.has(handle):
                bad.append(
                    f"prefix hash registered on dangling host handle {handle}"
                )
            if h in prefix.by_hash:
                bad.append(
                    f"hash resolves to BOTH HBM page {prefix.by_hash[h]} and "
                    f"host handle {handle} (one tier per page)"
                )
        for handle, e in tier.entries.items():
            if handle <= _HANDLE_BASE:
                bad.append(
                    f"host handle {handle} at/below the handle base "
                    "(collides with HBM page ids)"
                )
            if len(e.digest) != 16:
                bad.append(f"host handle {handle} has no integrity digest")
            want = getattr(engine, "HOST_SWAP_KIND", None)
            if want is not None and e.kind != want:
                bad.append(
                    f"host handle {handle} holds a {e.kind!r} page but this "
                    f"layout swaps {want!r}"
                )
            if e.pinned:
                if carried.get(handle, 0) == 0:
                    bad.append(
                        f"pinned host handle {handle} carried by no queued "
                        "request (host-tier leak)"
                    )
            elif handle not in prefix.hash_of_handle:
                bad.append(
                    f"unpinned host handle {handle} has no prefix "
                    "registration (unreachable host entry)"
                )
    # recompression stage markers track live/parked pages only
    for pid in getattr(engine, "_recompress_stage", {}):
        if pid in free_set:
            bad.append(f"free page {pid} still has a recompress stage marker")

    return AuditReport(
        ok=not bad,
        violations=bad,
        pages_checked=pool.n_pages - 1,
        slots_checked=len(engine.slots),
        tick=getattr(engine, "_tick", 0),
    )
