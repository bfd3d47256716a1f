"""Streaming merge — the fresh→main drain engine of the two-tier index.

A :class:`~repro.core.tiered.TieredSession` routes every mutation to a small
fresh tier and accumulates deletes of main-resident points as tombstones in
the main tier's MASK bitmap (DESIGN.md §12). :class:`StreamingMerge` is the
third maintenance op (alongside consolidate §8 and grow §9) that keeps that
arrangement sustainable on an unbounded stream: it moves a *snapshot* of the
fresh tier into main in bounded chunks, reclaiming main tombstones on the
way, while both tiers keep serving — queries fan out and deduplicate against
the pre-merge snapshot until the per-item tier swap retires the drained
copies.

Phases (each ``step()`` call performs ONE bounded chunk of work, so query
service never pauses longer than one chunk — the tiered session "pumps" one
step per insert/delete while a merge is active; queries and flushes never
pump, so fan-out latency stays flat and flush stays idempotent):

  1. **compact** — exactly ``ceil(n0/chunk)`` OP_CONSOLIDATE micro-batches
     on the main tier, where ``n0`` is main's tombstone count at merge
     start. Reuses the §8 compaction path verbatim (lowest-id tombstones
     first); tombstones that arrive mid-merge may be swept opportunistically
     by later chunks, any remainder waits for the next merge.
  2. **drain** — snapshot items (host-copied vectors, age-ordered by their
     insertion stamps, invariant I6) are appended to main through the
     batched insert applier. Room is made by growing main's capacity tier
     when armed; when growth is capped out the drain stops early and the
     undrained suffix simply stays in the fresh tier ("capped" merge).
     Each drained item becomes resident in *both* tiers — queries dedupe by
     external id, so the visible result set never changes.
  3. **swap** — the drained items' fresh slots are released through the
     fresh tier's delete applier, chunk by chunk. An item's authoritative
     copy moves atomically (per item) from fresh to main: it is reachable
     in at least one tier at every instant.

Determinism (DESIGN.md §11/§12): every device call uses the merge PRNG
chain — ``fold_in(fold_in(base, MERGE_KEY_STREAM), merge_counter)`` — never
either tier's op-key chain, so *when* a merge runs can never shift the
results of the logical op stream. Merge progress itself is a pure function
of the acknowledged mutation stream (auto-start gate + one pump per
insert/delete), which is what lets crash recovery replay a journal suffix
and land bit-exactly in the middle of a merge.

The merge's cross-layer wiring — its key stream, JR_MERGE journal code and
cseq dedup counter, checkpoint-counter contract, and the crash points fired
below (``merge-begin``, ``merge-compact-step``, ``merge-drain-step``,
``pre-merge-swap``, ``post-merge-swap``) — is declared once on the MERGE
entry of the maintenance-op registry (``core/maint.py``, DESIGN.md §14);
``faults.TIERED_CRASH_POINTS`` is generated from it.
"""
from __future__ import annotations

import numpy as np

from repro.core import ops as ops_mod
from repro.core.graph import NULL, next_capacity_tier
from repro.core.session import OpHandle
from repro.core.trace import span
from repro.testing import faults

# phase tags, in execution order
COMPACT, DRAIN, SWAP, DONE = "compact", "drain", "swap", "done"


class StreamingMerge:
    """One in-flight fresh→main merge over a fixed start-of-merge snapshot.

    Owned and driven by a ``TieredSession``; not meaningful standalone. The
    constructor takes the snapshot (synchronizing on the fresh tier's
    arrays); each ``step()`` performs one chunk of compact/drain/swap work
    and returns whether the merge is finished.
    """

    def __init__(self, owner) -> None:
        faults.crash_point("merge-begin")
        self.owner = owner
        fresh, main = owner._fresh, owner._main
        fm, mm = owner._fm, owner._mm
        self.chunk = owner._merge_chunk
        # --- snapshot: every fresh-resident item, oldest first (I6) ---
        slots = np.flatnonzero(fm.present).astype(np.int32)
        stamps = np.asarray(fresh.state.stamps)[slots]
        order = np.argsort(stamps, kind="stable")
        self.slots = slots[order]                       # fresh slot per item
        self.exts = fm.ext[self.slots].copy()           # external id per item
        self.vecs = np.asarray(fresh.state.vectors)[self.slots].copy()
        # --- compact plan: fixed at merge start (chunk count, not slot set —
        # each chunk sweeps whatever the lowest-id tombstones are *then*) ---
        n0 = int(np.sum(mm.masked))
        self._compact_left = -(-n0 // self.chunk) if n0 else 0
        self._consolidate_batch = ops_mod.make_op(
            ops_mod.OP_CONSOLIDATE, self.chunk, main.params.dim)
        self.phase = COMPACT if self._compact_left else DRAIN
        self._ptr = 0                  # next snapshot item to consider
        self._swap_ptr = 0             # next drained item to swap out
        self.cancelled: set[int] = set()   # exts deleted before their drain
        self.drained: list[tuple[int, int]] = []  # (ext, fresh_slot)
        self.capped = False            # main filled up; suffix stays fresh
        self.n_drained = 0

    @property
    def done(self) -> bool:
        return self.phase == DONE

    # -- the one-chunk work unit -------------------------------------------
    def step(self) -> bool:
        """Perform one bounded chunk of merge work. Returns ``done``."""
        if self.phase == DONE:
            return True
        with span("merge.step", self.owner.timers, "merge_s",
                  phase=self.phase):
            if self.phase == COMPACT:
                self._compact_step()
            elif self.phase == DRAIN:
                self._drain_step()
            elif self.phase == SWAP:
                self._swap_step()
        return self.phase == DONE

    def run(self) -> None:
        """Drive the merge to completion (the save/catch-up barrier)."""
        while not self.step():
            pass

    # -- phase 1: main-tier tombstone compaction ---------------------------
    def _compact_step(self) -> None:
        owner, main, mm = self.owner, self.owner._main, self.owner._mm
        key = owner._merge_key()
        main._state, ids, scores = ops_mod.apply_ops_step(
            main._state, self._consolidate_batch, key, main.params,
            main.strategy, static_op=ops_mod.OP_CONSOLIDATE,
        )
        # mirror the device's pick exactly: the chunk's lowest-id tombstones
        freed = np.flatnonzero(mm.masked)[: self.chunk]
        mm.masked[freed] = False
        mm.present[freed] = False
        n = len(freed)
        h = OpHandle("consolidate", n, main.params.search.pool_size,
                     [(ids, scores, n)], on_done=main._handle_done)
        main._pending.append(h)
        self._compact_left -= 1
        if self._compact_left == 0:
            self.phase = DRAIN
        faults.crash_point("merge-compact-step")

    # -- phase 2: fresh→main drain -----------------------------------------
    def _next_drain_batch(self) -> np.ndarray:
        """Indices of the next ≤chunk snapshot items still worth draining."""
        sel = []
        while self._ptr < len(self.slots) and len(sel) < self.chunk:
            if int(self.exts[self._ptr]) not in self.cancelled:
                sel.append(self._ptr)
            self._ptr += 1
            if len(sel) == self.chunk:
                break
        return np.asarray(sel, np.int64)

    def _drain_step(self) -> None:
        owner, main, mm = self.owner, self.owner._main, self.owner._mm
        sel = self._next_drain_batch()
        n = len(sel)
        if n == 0:
            self._enter_swap()
            return
        # room in main: compact already ran, so grow the tier (when armed)
        free = int(mm.capacity - np.sum(mm.present))
        if free < n:
            mp = owner.params.maintenance
            cap = main.state.capacity
            target = next_capacity_tier(
                cap, cap - free + n, mp.growth_factor, mp.max_capacity)
            if target > cap:
                main.grow(target, _auto=True)
                mm.grow(target)
                free += target - cap
        if free < n:
            if free == 0:
                # main is capped out: the undrained suffix stays fresh
                self.capped = True
                self._ptr = len(self.slots)
                self._enter_swap()
                return
            self._ptr = int(sel[free])  # re-consider the overflow next step
            sel = sel[:free]
            n = free
        batch = ops_mod.make_op(
            ops_mod.OP_INSERT, self.chunk, main.params.dim,
            payload=self.vecs[sel])
        key = owner._merge_key()
        main._state, ids, scores = ops_mod.apply_ops_step(
            main._state, batch, key, main.params, main.strategy,
            static_op=None if main.unified_dispatch else ops_mod.OP_INSERT,
        )
        # host mirror of the batched allocator: i-th valid row → i-th lowest
        # free slot (insert.py phase 1); room was ensured above, no refusals
        mslots = np.flatnonzero(~mm.present)[:n]
        exts = self.exts[sel]
        mm.present[mslots] = True
        mm.ext[mslots] = exts
        owner._ext_snap_dirty()
        for i, (e, ms) in enumerate(zip(exts, mslots)):
            fs = int(self.slots[sel[i]])
            owner._loc[int(e)] = ("both", fs, int(ms))
            owner._both_set.add(int(e))
            self.drained.append((int(e), fs))
        h = OpHandle("insert", n, main.params.search.pool_size,
                     [(ids, scores, n)], on_done=main._handle_done)
        main._pending.append(h)
        self.n_drained += n
        owner.timers.n_merged += n
        faults.crash_point("merge-drain-step")

    # -- phase 3: per-item tier swap ---------------------------------------
    def _enter_swap(self) -> None:
        self.phase = SWAP
        faults.crash_point("pre-merge-swap")

    def _swap_step(self) -> None:
        owner, fresh, fm = self.owner, self.owner._fresh, self.owner._fm
        # items deleted while "both" already left both tiers — skip them
        sel = []
        while self._swap_ptr < len(self.drained) and len(sel) < self.chunk:
            ext, fslot = self.drained[self._swap_ptr]
            self._swap_ptr += 1
            loc = owner._loc.get(ext)
            if loc is not None and loc[0] == "both" and loc[1] == fslot:
                sel.append((ext, fslot, loc[2]))
        if sel:
            fslots = np.asarray([s[1] for s in sel], np.int32)
            batch = ops_mod.make_op(
                ops_mod.OP_DELETE, self.chunk, fresh.params.dim, ids=fslots)
            key = owner._merge_key()
            fresh._state, ids, scores = ops_mod.apply_ops_step(
                fresh._state, batch, key, fresh.params, fresh.strategy,
                static_op=None if fresh.unified_dispatch
                else ops_mod.OP_DELETE,
            )
            fm.present[fslots] = False
            owner._fbias[fslots] = -np.inf
            fm.ext[fslots] = NULL
            owner._ext_snap_dirty()
            for ext, _, mslot in sel:
                owner._loc[ext] = ("main", mslot)
                owner._both_set.discard(ext)
            h = OpHandle("delete", len(sel), fresh.params.search.pool_size,
                         [(ids, scores, len(sel))],
                         on_done=fresh._handle_done)
            fresh._pending.append(h)
        if self._swap_ptr >= len(self.drained):
            self.phase = DONE
            owner._merges_done += 1
            owner.timers.n_merges += 1
            faults.crash_point("post-merge-swap")
