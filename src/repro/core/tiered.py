"""Two-tier streaming index — a small fresh tier in front of a big main tier.

``TieredSession`` (DESIGN.md §12) scales the session API past what one
mutable graph serves comfortably: every insert lands in a small *fresh*
:class:`~repro.core.session.Session` (cheap to mutate, hard-delete
strategy), deletes of main-resident points become tombstones in the *main*
tier's MASK bitmap, and queries fan out to both tiers and union their
results — deduplicated by **external id**, tombstone-filtered by each
tier's own alive bitmap. A background :class:`~repro.core.merge.
StreamingMerge` drains the fresh tier into main in bounded chunks (one
"pump" step per insert/delete), so neither tier ever stops serving and no
op pauses longer than one merge chunk.

External ids: callers address points by a stable external id (assigned
monotonically by ``insert``, or caller-chosen via ``insert(ids=...)``).
Query results report external ids; the slot ids of the two tiers never
escape. Re-inserting a live external id is an **upsert**: the old copy is
deleted (in whichever tier(s) hold it) before the new vector lands in
fresh — so a query can never surface a stale vector or the same id twice.

Determinism contract (the §7/§8 guarantee class, extended):

  · every public op consumes a *fixed* number of per-tier op keys —
    queries one main key (the fresh tier is served by an exact host scan,
    no key), deletes one key per tier, inserts one delete key per tier
    plus one fresh insert key — regardless of where the targets happen to
    live, so merge timing can never shift either tier's op-key chain;
  · merge work runs on its own PRNG stream
    (``fold_in(base, MERGE_KEY_STREAM)`` + merge counter), like the §8
    consolidation chain;
  · merge progress is a pure function of the acknowledged *mutation*
    stream (the auto-start gate reads exact host mirrors; one pump per
    insert/delete — queries never pump, keeping fan-out latency flat, and
    flushes never pump, keeping flush idempotent for recovery), which is
    what makes crash recovery land bit-exactly mid-merge.

Durability (DESIGN.md §11): with a ``checkpoint_dir`` the tiered session
arms its own write-ahead journal — ops journal under their OP_* codes with
*external* ids, explicit merges under JR_MERGE — and ``save`` checkpoints
both tiers plus the slot→external-id maps atomically (completing any
in-flight merge first: the checkpoint merge barrier). ``recover`` replays
the journal suffix through the normal op pipeline.

Host mirrors: the tiered layer keeps exact numpy mirrors of each tier's
``present``/``masked`` bitmaps plus the slot→ext maps. Every device-side
allocation and compaction pick is deterministic (lowest-free-first /
lowest-id-tombstones-first), so the mirrors track the device bit-exactly
without ever synchronizing — they are what lets routing, the merge gate
and refusal accounting run host-side at op rate.
"""
from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import maint
from repro.core import merge as merge_mod
from repro.core import metrics
from repro.core import ops as ops_mod
from repro.core.graph import NULL
from repro.core.ops import OP_DELETE, OP_INSERT, OP_QUERY
from repro.core.params import IndexParams
from repro.core.session import (
    PhaseTimers,
    Session,
    params_fingerprint,
)
from repro.core.trace import span
from repro.testing import faults

_HARD_STRATEGIES = ("pure", "local", "global", "rwalk")


class _TierMirror:
    """Exact host mirror of one tier's occupancy + slot→ext map.

    ``present``/``masked`` replicate the device bitmaps (allocation and
    compaction picks are deterministic, so no sync is ever needed);
    ``ext[slot]`` is the external id resident in ``slot`` (NULL = none).
    """

    def __init__(self, capacity: int):
        self.capacity = capacity
        self.present = np.zeros((capacity,), bool)
        self.masked = np.zeros((capacity,), bool)
        self.ext = np.full((capacity,), NULL, np.int32)

    def grow(self, new_capacity: int) -> None:
        extra = new_capacity - self.capacity
        if extra <= 0:
            return
        self.present = np.pad(self.present, (0, extra))
        self.masked = np.pad(self.masked, (0, extra))
        self.ext = np.pad(self.ext, (0, extra), constant_values=NULL)
        self.capacity = new_capacity

    @property
    def n_free(self) -> int:
        return int(self.capacity - np.sum(self.present))


def _union_topk(ext_ids: np.ndarray, scores: np.ndarray, k: int,
                device: bool = False, dedup: bool = True):
    """Dedup-by-ext union of fan-out results → top-k (scores descending).

    Duplicate external ids (an item resident in both tiers mid-drain) keep
    their best score only; NULL lanes never rank. Runs host-side — the
    fan-in must not cost a device dispatch on the query hot path (the
    ≥0.95x single-session throughput floor, ``benchmarks/kernel_bench.py
    run_tiered``). ``dedup=False`` skips the duplicate sweep — valid
    whenever no external id can be resident in both tiers, i.e. whenever
    no merge was in flight at dispatch (upserts delete the old copy in
    the same op, so mid-drain "both" items are the only duplicate
    source). ``device=True`` routes the final top-k through the sharded
    fan-in kernel (``distributed.ann.topk_union``) instead —
    semantically identical modulo tie order; used off the hot path
    (``ground_truth``) to keep the two unions covered by the same tests.
    """
    ids = np.ascontiguousarray(ext_ids, np.int32)
    sc = np.ascontiguousarray(scores, np.float32).copy()
    B, W = ids.shape
    if B == 0:
        return (np.full((0, k), NULL, np.int32),
                np.full((0, k), -np.inf, np.float32))
    sc[ids == NULL] = -np.inf
    if dedup:
        # one lexsort across all rows: group (row, ext), keep best score
        rowid = np.repeat(np.arange(B), W)
        flat_i, flat_s = ids.ravel(), sc.ravel()
        order = np.lexsort((-flat_s, flat_i, rowid))
        e, r = flat_i[order], rowid[order]
        dup = np.zeros(B * W, bool)
        dup[1:] = (e[1:] == e[:-1]) & (r[1:] == r[:-1]) & (e[1:] != NULL)
        flat_s = flat_s.copy()
        flat_s[order[dup]] = -np.inf
        sc = flat_s.reshape(B, W)
    if device:
        from repro.distributed.ann import topk_union  # lazy: import cycle
        top_s, top_i = topk_union(jnp.asarray(sc), jnp.asarray(ids), k)
        top_s, top_i = np.asarray(top_s), np.asarray(top_i)
    else:
        top = np.argsort(-sc, axis=1, kind="stable")[:, :k]
        rows = np.arange(B)[:, None]
        top_s = sc[rows, top]
        top_i = ids[rows, top]
    top_i = np.where(top_s > -np.inf, top_i, NULL).astype(np.int32, copy=False)
    return top_i, top_s


def _translate(slot_ids: np.ndarray, ext_map: np.ndarray) -> np.ndarray:
    """slot ids [B,K] → external ids under a dispatch-time ext snapshot."""
    safe = np.clip(slot_ids, 0, len(ext_map) - 1)
    return np.where(slot_ids >= 0, ext_map[safe], NULL).astype(np.int32)


class TieredOpHandle:
    """Future for one tiered op — fans in the per-tier handles on demand."""

    def __init__(self, op: str, n: int, k: int = 0, subs=(),
                 ext_result: np.ndarray | None = None,
                 fresh_res: np.ndarray | None = None,
                 fresh_ext: np.ndarray | None = None,
                 main_ext: np.ndarray | None = None,
                 halved: bool = False,
                 both: np.ndarray | None = None):
        self.op = op
        self.n = n
        self.k = k
        self._subs = list(subs)
        self._ext_result = ext_result   # insert: acked external ids
        self._fresh_res = fresh_res     # query: fresh key matrix [B, C]
        self._fresh_ext = fresh_ext     # query: fresh slot→ext snapshot
        self._main_ext = main_ext       # query: NULL-padded main slot→ext
        self._halved = halved           # query: keys are score/2 (l2)
        self._both = both               # query: mid-drain "both" ext ids

    def result(self):
        """Block until applied on both tiers; return the fan-in result.

        query  → (ext_ids i32[n, k], scores f32[n, k])
        insert → ext_ids i32[n] (NULL where rejected/refused/superseded)
        delete → None
        """
        if self.op == "query":
            mi, ms = self._subs[0].result()
            if self.n == 0:
                return (np.full((0, self.k), NULL, np.int32),
                        np.full((0, self.k), -np.inf, np.float32))
            # fused fan-in: one ranking pass over [fresh keys | main keys].
            # Main scores are halved to the fresh keys' scale (exact), the
            # winners' scores doubled back (exact) — see _fresh_key. The
            # engine pads empty pool lanes with NULL ids AND −inf scores
            # (search.NEG_INF), and the padded main map gathers slot NULL
            # to ext NULL, so no fix-up pass is needed anywhere on the
            # common (no-merge) path. Call count matters more than row
            # width here: each numpy call costs ~10-25µs of cache-refill
            # tax when interleaved with device dispatch, so this path
            # stays at ~8 calls on [B, C+k] rather than pre-cutting the
            # fresh side to top-k with extra partitions.
            mext = self._main_ext[mi]
            mkey = 0.5 * ms if self._halved else ms
            if self._both is not None:
                # an ext resident in both tiers mid-drain would surface
                # twice. The exact fresh scan ALWAYS carries the fresh
                # copy of every both-resident item, so the main copy can
                # be dropped unconditionally — one isin over the [B, k]
                # main lanes instead of a lexsort sweep of the union.
                mkey = np.where(np.isin(mext, self._both), -np.inf, mkey)
            allk = np.concatenate([self._fresh_res, mkey], axis=1)
            B, C = self._fresh_res.shape
            allid = np.concatenate(
                [np.broadcast_to(self._fresh_ext, (B, C)), mext], axis=1)
            # negation (not a reversed ascending slice) keeps NaN scores
            # ranked last, matching the device engine's convention
            top = np.argsort(-allk, axis=1)[:, :self.k]
            tops = np.take_along_axis(allk, top, axis=1)
            topi = np.take_along_axis(allid, top, axis=1)
            if self._halved:
                tops *= 2.0
            if self._both is not None:
                # a dropped-to-−inf main lane keeps a real (duplicate) ext
                # id; NULL it out if it still made the top-k of a row with
                # fewer than k live candidates
                topi = np.where(tops > -np.inf, topi, NULL).astype(
                    np.int32, copy=False)
            return topi, tops
        for h in self._subs:
            h.block()
        if self.op == "insert":
            return self._ext_result
        return None

    def block(self) -> None:
        for h in self._subs:
            h.block()


class TieredSession:
    """Two-tier streaming session: fresh-tier writes, fan-out reads.

    ``params`` configures the **main** tier (its maintenance strategy is
    forced to ``"mask"`` — the tombstone bitmap is what makes cross-tier
    deletes O(1)); the fresh tier reuses the same geometry at
    ``fresh_capacity`` slots with a hard-delete ``fresh_strategy``. The
    ``maintenance.merge_*`` knobs arm the streaming-merge auto-trigger.
    """

    def __init__(
        self,
        params: IndexParams,
        *,
        fresh_capacity: int | None = None,
        fresh_strategy: str = "global",
        seed: int = 0,
        checkpoint_dir: str | Path | None = None,
        checkpoint_keep: int = 3,
        unified_dispatch: bool = True,
        journal: bool | None = None,
        journal_fsync: str = "flush",
    ):
        if fresh_strategy not in _HARD_STRATEGIES:
            raise ValueError(
                f"fresh_strategy must be a hard-delete strategy "
                f"{_HARD_STRATEGIES} (the fresh tier never tombstones)")
        mp = params.maintenance
        if fresh_capacity is None:
            fresh_capacity = max(2 * mp.insert_chunk, params.capacity // 8)
        if fresh_capacity < 1:
            raise ValueError("fresh_capacity must be >= 1")
        self.params = params
        self.fresh_capacity = int(fresh_capacity)
        self.fresh_strategy = fresh_strategy
        self.seed = seed
        self._base_key = jax.random.PRNGKey(seed)
        # tier configs: neither tier self-consolidates (merge compaction is
        # the ONLY main-tier compactor — keeps the host mirrors exact) and
        # the fresh tier never grows (merge catch-up is its backpressure)
        fresh_params = dataclasses.replace(
            params, capacity=self.fresh_capacity,
            maintenance=dataclasses.replace(
                mp, strategy=fresh_strategy, consolidate_threshold=None,
                max_capacity=None, merge_fresh_threshold=None,
                merge_tombstone_threshold=None))
        main_params = dataclasses.replace(
            params,
            maintenance=dataclasses.replace(
                mp, strategy="mask", consolidate_threshold=None,
                merge_fresh_threshold=None, merge_tombstone_threshold=None))
        self._fresh = Session(fresh_params, strategy=fresh_strategy,
                              seed=2 * seed + 1, journal=False,
                              unified_dispatch=unified_dispatch)
        self._main = Session(main_params, strategy="mask", seed=2 * seed,
                             journal=False,
                             unified_dispatch=unified_dispatch)
        self._fm = _TierMirror(self.fresh_capacity)
        self._mm = _TierMirror(params.capacity)
        # host mirror of the fresh tier's stored vectors — serves the exact
        # fresh scan on the query hot path (bitwise the device rows for
        # l2/ip; cos rows may differ from the device copy in the last ulp
        # of the normalization)
        self._fvec = np.zeros((self.fresh_capacity, params.dim), np.float32)
        self._fsqh = np.zeros((self.fresh_capacity,), np.float32)  # ‖row‖²/2
        # fused additive bias for the fresh scan — occupancy penalty and
        # (for l2) the −‖x‖²/2 term in ONE vector, so the hot path is a
        # single matmul + add: −inf at absent slots, else −‖x‖²/2 (l2) or
        # 0 (ip/cos). Kept in lockstep with _fm.present at every flip site.
        self._fbias = np.full((self.fresh_capacity,), -np.inf, np.float32)
        # copy-on-write snapshots of the slot→ext maps handed to query
        # handles; None = stale, rebuilt on the next query (mutations only
        # pay a flag write, queries only pay the copy when something moved)
        self._fext_snap: np.ndarray | None = None
        self._mext_pad: np.ndarray | None = None
        self._loc: dict[int, tuple] = {}   # ext → ("fresh",f)|("main",m)|("both",f,m)
        self._both_set: set[int] = set()   # live "both" ext ids in _loc
        self._next_ext = 0
        self._op_counter = 0
        self._merge_counter = 0
        self._merges_done = 0
        self._active_merge: merge_mod.StreamingMerge | None = None
        self.timers = PhaseTimers()
        self.recovering = False
        self.recovery_info: dict | None = None
        self._ckpt = None
        if checkpoint_dir is not None:
            from repro.checkpoint import CheckpointManager
            self._ckpt = CheckpointManager(checkpoint_dir,
                                           keep=checkpoint_keep)
        self._journal = None
        self._journal_fsync = journal_fsync
        if journal is None:
            journal = checkpoint_dir is not None
        if journal:
            self._require_ckpt()
            self._attach_journal(fresh=True)

    # -- tier access (read-only views for tests/benchmarks) ----------------
    @property
    def fresh(self) -> Session:
        return self._fresh

    @property
    def main(self) -> Session:
        return self._main

    @property
    def active_merge(self) -> merge_mod.StreamingMerge | None:
        return self._active_merge

    @property
    def n_alive(self) -> int:
        """Number of live external ids (an item in both tiers counts once)."""
        return len(self._loc)

    @property
    def _merge_chunk(self) -> int:
        mp = self.params.maintenance
        return mp.merge_chunk or mp.insert_chunk

    # -- identity / durability plumbing ------------------------------------
    def _fingerprint(self) -> str:
        return json.dumps({
            "tiered": params_fingerprint(self.params, "mask"),
            "fresh_capacity": self.fresh_capacity,
            "fresh_strategy": self.fresh_strategy,
        }, sort_keys=True)

    def _require_ckpt(self):
        if self._ckpt is None:
            raise ValueError(
                "session has no checkpoint_dir; pass checkpoint_dir= to "
                "TieredSession(...) to enable save/restore")
        return self._ckpt

    def _attach_journal(self, *, fresh: bool) -> None:
        from repro.checkpoint.journal import OpJournal

        path = Path(self._ckpt.dir) / "journal.bin"
        self._journal = OpJournal(path, fsync=self._journal_fsync)
        if fresh:
            self._journal.reset(meta={"fingerprint": self._fingerprint()})
        else:
            self._journal.repair()

    def _journal_append(self, code: int, *, payload=None, ids=None,
                        aux: dict | None = None) -> None:
        if self._journal is None:
            return
        # cseq carries the merge counter here: JR_MERGE records are deduped
        # against merges a later checkpoint already covers, exactly like
        # Session's JR_CONSOLIDATE/cseq pairing (DESIGN.md §11). The
        # counter is the MERGE registry entry's ``counter_attr``
        # (core/maint.py) — the tiered tier registers exactly one
        # maintenance op, so every record snapshots it.
        with span("journal.append", code=code):
            self._journal.append(code, seq=self._op_counter,
                                 cseq=getattr(self, maint.MERGE.counter_attr),
                                 payload=payload, ids=ids, aux=aux)
        faults.crash_point("post-journal-append")

    # -- merge engine plumbing (DESIGN.md §12) -----------------------------
    def _merge_key(self) -> jax.Array:
        # drawn from the MERGE op's registered key stream (DESIGN.md §14);
        # _merge_counter advances per *draw* (several per merge), while
        # _merges_done — the cseq dedup counter — advances per merge
        key = maint.maint_key(self._base_key, maint.MERGE,
                              self._merge_counter)
        self._merge_counter += 1
        return key

    def _pump(self) -> None:
        """One bounded merge step per insert/delete while a merge is in flight."""
        if self._active_merge is not None and self._active_merge.step():
            self._active_merge = None

    def _maybe_merge_start(self) -> None:
        """Auto-trigger: start a merge when either gate arm crosses.

        Exact host counters (the mirrors), so unlike the §8 hint gate there
        is no device sync to avoid — the check is free and precise. Never
        journaled: replay re-derives the decision from the same mirrors.
        """
        if self._active_merge is not None:
            return
        mp = self.params.maintenance
        ft, tt = mp.merge_fresh_threshold, mp.merge_tombstone_threshold
        fire = False
        if ft is not None:
            fire |= int(np.sum(self._fm.present)) >= ft * self.fresh_capacity
        if tt is not None:
            n_masked = int(np.sum(self._mm.masked))
            n_present = int(np.sum(self._mm.present))
            fire |= n_masked > 0 and n_masked >= tt * max(n_present, 1)
        if fire:
            self._active_merge = merge_mod.StreamingMerge(self)

    def _merge_to_completion(self) -> int:
        if self._active_merge is None:
            self._active_merge = merge_mod.StreamingMerge(self)
        m = self._active_merge
        m.run()
        self._active_merge = None
        return m.n_drained

    def merge(self) -> int:
        """Run a streaming merge to completion (explicit, journaled).

        Completes the in-flight merge if one is active, else starts one.
        Returns the number of items drained fresh→main. The auto-triggered
        path (``maintenance.merge_*`` thresholds) instead advances one
        chunk per mutation op and is not journaled.
        """
        self._journal_append(ops_mod.JR_MERGE)
        return self._merge_to_completion()

    # -- the op surface ----------------------------------------------------
    def _ext_snap_dirty(self) -> None:
        """Invalidate the COW slot→ext snapshots after any ext-map write."""
        self._fext_snap = None
        self._mext_pad = None

    def _fresh_key(self, q: np.ndarray) -> np.ndarray:
        """Ranking keys [B, fresh_capacity] for the exact fresh scan.

        The fresh tier never exceeds ``fresh_capacity`` rows, so an exact
        host-side scan of the vector mirror beats paying a second device
        dispatch per query (that dispatch overhead is what the ≥0.95x
        single-session throughput floor forbids) — and the small tier gets
        *exact* results, FreshDiskANN-style. Consumes no fresh-tier op key.

        For l2 the key is ⟨x,q⟩ − ‖x‖²/2 — exactly HALF the engine's
        2⟨x,q⟩ − ‖x‖² score (``distances.pair_score``): halving and
        doubling are exact in binary floating point, so the fan-in ranks
        these against half-scaled main scores and recovers bit-exact
        scores by doubling the winners, touching only [B, k] lanes
        instead of the full key matrix. ip/cos: the dot itself. Absent
        slots are −inf (their ext mirror entries are already NULL).
        The −‖x‖²/2 term and the occupancy penalty live fused in
        ``_fbias``, so this is one matmul plus one add.
        """
        return q @ self._fvec.T + self._fbias

    def query(self, queries, k: int | None = None) -> TieredOpHandle:
        """Fan-out ANN query over both tiers; returns a handle (async).

        The main tier runs the device beam engine (one op key); the fresh
        tier is served by the exact host scan (no key, no device work).
        Queries do NOT pump the merge — merge progress is a function of
        the *mutation* stream only, which keeps fan-out latency flat.
        ``handle.result()`` → (ext_ids i32[B,k], scores f32[B,k]) — the
        dedup-by-external-id union of the two tiers' top-k.
        """
        q = np.asarray(queries, np.float32)
        k = k if k is not None else self.params.search.pool_size
        k = min(k, self.params.search.pool_size)
        self._journal_append(OP_QUERY, aux={"n": int(q.shape[0])})
        self._op_counter += 1
        with span("tiered.query", self.timers, "query_s"):
            fkey = self._fresh_key(q)
            hm = self._main.query(q, k=k)
            # duplicates across tiers exist only while some item is "both"-
            # resident mid-drain — snapshot the flag now, like the ext maps
            # (the padded main map turns slot NULL (−1) into ext NULL by
            # indexing). The snapshots are COW: handles share one frozen array
            # until the next mutation invalidates it (``_ext_snap_dirty``).
            fe = self._fext_snap
            if fe is None:
                fe = self._fext_snap = self._fm.ext.copy()
            mp = self._mext_pad
            if mp is None:
                mp = self._mext_pad = np.append(self._mm.ext, np.int32(NULL))
            both = (np.fromiter(self._both_set, np.int32,
                                len(self._both_set))
                    if self._both_set else None)
            h = TieredOpHandle("query", q.shape[0], k, (hm,),
                               fresh_res=fkey, fresh_ext=fe, main_ext=mp,
                               halved=self.params.metric == "l2",
                               both=both)
        self.timers.n_queries += q.shape[0]
        self.timers.n_ops += 1
        return h

    def insert(self, vectors, ids=None) -> TieredOpHandle:
        """Insert (or upsert) a batch into the fresh tier.

        ``ids`` picks the external ids (else assigned monotonically). A row
        whose external id is currently live anywhere replaces the old copy
        — the old vector is deleted from its tier(s) in the same op, so it
        can never be returned again (stale-ghost regression,
        tests/test_tiered.py). ``handle.result()`` → the acked external
        ids, NULL at rejected (non-finite), refused (both tiers full) and
        superseded (duplicate-id-within-batch, last wins) positions.
        """
        v = np.asarray(vectors, np.float32)
        n = v.shape[0]
        if ids is None:
            ext = np.arange(self._next_ext, self._next_ext + n,
                            dtype=np.int64)
        else:
            ext = np.asarray(ids, np.int64).reshape(-1)
            if ext.shape[0] != n:
                raise ValueError("ids must match vectors' row count")
            if n and (ext.min() < 0 or ext.max() >= 2**31):
                raise ValueError("external ids must be int32 and >= 0")
        ext = ext.astype(np.int32)
        if n:
            self._next_ext = max(self._next_ext, int(ext.max()) + 1)
        self._journal_append(OP_INSERT, payload=v, ids=ext)
        self._op_counter += 1
        self._pump()
        # dispatch-time validation (same rules as Session.insert) + in-batch
        # upsert order: a duplicated external id keeps its LAST finite row
        live = (np.isfinite(v).all(axis=1) if n
                else np.zeros((0,), bool))
        self.timers.n_rejected += int(n - np.sum(live))
        seen: set[int] = set()
        for i in range(n - 1, -1, -1):
            if not live[i]:
                continue
            e = int(ext[i])
            if e in seen:
                live[i] = False
            else:
                seen.add(e)
        # cross-tier upsert: evict live duplicates first (uniform key use —
        # one delete key per tier, dispatched even when there are none)
        dups = np.asarray(
            [int(e) for e, ok in zip(ext, live) if ok and int(e) in self._loc],
            np.int32)
        sub = list(self._delete_exts(dups))
        vk = v[live]
        ek = ext[live]
        nk = vk.shape[0]
        # fresh-tier backpressure: when the batch outruns the merge, finish
        # the drain synchronously (deterministic — re-derived on replay)
        if nk and self._fm.n_free < nk and (
                np.sum(self._fm.present) > 0
                or self._active_merge is not None):
            self._merge_to_completion()
        with span("tiered.insert", self.timers, "insert_s"):
            free_ids = np.flatnonzero(~self._fm.present)
            n_ok = min(nk, len(free_ids))
            self.timers.n_refused += nk - n_ok
            if nk:
                sub.append(self._fresh.insert(vk))
            else:
                sub.append(self._fresh.insert(np.zeros((0, self.params.dim),
                                                       np.float32)))
            slots = free_ids[:n_ok].astype(np.int32)
            self._fm.present[slots] = True
            self._fm.ext[slots] = ek[:n_ok]
            self._ext_snap_dirty()
            # vector mirror for the exact fresh scan — what the device
            # stores: verbatim f32 rows (cos: pre-normalized, the numpy twin
            # of distances.normalize)
            vstore = vk[:n_ok]
            if self.params.metric == "cos":
                vstore = vstore / np.sqrt(np.maximum(
                    np.sum(np.square(vstore), -1, keepdims=True), 1e-12))
            self._fvec[slots] = vstore
            self._fsqh[slots] = 0.5 * np.sum(np.square(vstore), axis=-1)
            self._fbias[slots] = (-self._fsqh[slots]
                                  if self.params.metric == "l2" else 0.0)
            for e, s in zip(ek[:n_ok], slots):
                self._loc[int(e)] = ("fresh", int(s))
            res = np.full((n,), NULL, np.int32)
            live_idx = np.flatnonzero(live)
            res[live_idx[:n_ok]] = ek[:n_ok]
        self.timers.n_inserts += nk
        self.timers.n_ops += 1
        self._maybe_merge_start()
        return TieredOpHandle("insert", n, subs=sub, ext_result=res)

    def delete(self, ids) -> TieredOpHandle:
        """Delete a batch of external ids (wherever each is resident).

        Fresh-resident ids hard-delete; main-resident ids tombstone (the
        §12 cross-tier bitmap); ids mid-drain leave both tiers. Unknown
        ids are ignored. One delete key per tier is always consumed.
        """
        arr = np.asarray(ids, np.int64).reshape(-1).astype(np.int32)
        self._journal_append(OP_DELETE, ids=arr)
        self._op_counter += 1
        self._pump()
        with span("tiered.delete", self.timers, "delete_s"):
            sub = self._delete_exts(arr)
        self.timers.n_deletes += arr.shape[0]
        self.timers.n_ops += 1
        self._maybe_merge_start()
        return TieredOpHandle("delete", arr.shape[0], subs=sub)

    def _delete_exts(self, exts: np.ndarray):
        """Route external-id deletes to their tiers (mirrors + device).

        Always dispatches exactly one delete op per tier — empty where a
        tier holds no targets — so the per-tier key chains advance
        identically no matter where the ids live (merge-timing invariance).
        """
        fslots, mslots = [], []
        m = self._active_merge
        for e in np.unique(exts):
            e = int(e)
            loc = self._loc.pop(e, None)
            if loc is None:
                continue
            if loc[0] in ("fresh", "both"):
                f = loc[1]
                fslots.append(f)
                self._fm.present[f] = False
                self._fbias[f] = -np.inf
                self._fm.ext[f] = NULL
                if loc[0] == "fresh" and m is not None and not m.done:
                    m.cancelled.add(e)
                if loc[0] == "both":
                    self._both_set.discard(e)
            if loc[0] == "main":
                mslots.append(loc[1])
                self._mm.masked[loc[1]] = True
                self._mm.ext[loc[1]] = NULL
            elif loc[0] == "both":
                mslots.append(loc[2])
                self._mm.masked[loc[2]] = True
                self._mm.ext[loc[2]] = NULL
        self._ext_snap_dirty()
        hf = self._fresh.delete(np.asarray(sorted(fslots), np.int32))
        hm = self._main.delete(np.asarray(sorted(mslots), np.int32))
        return hf, hm

    def flush(self) -> PhaseTimers:
        """Synchronize both tiers; also a merge *trigger* point.

        Flush never pumps — a journaled JR_FLUSH replays as another flush,
        and a crash inside one is resumed by re-running it, so everything
        here must be idempotent (``_maybe_merge_start`` is: a second call
        sees the active merge and does nothing; a pump would not be).
        Merge chunks advance on insert/delete only.
        """
        faults.crash_point("pre-flush")
        self._journal_append(ops_mod.JR_FLUSH)
        self._maybe_merge_start()
        self._fresh._sync()
        self._main._sync()
        if self._journal is not None and self._journal.fsync_policy == "flush":
            self._journal.sync()
        faults.crash_point("post-flush")
        return self.timers

    # -- reporting ---------------------------------------------------------
    def ground_truth(self, queries, k: int):
        """Exact top-k over the union of both tiers' alive sets (ext ids)."""
        self.flush()
        q = jnp.asarray(queries, jnp.float32)
        fs, fi = metrics.brute_force_topk(self._fresh.state, q, k)
        ms, mi = metrics.brute_force_topk(self._main.state, q, k)
        ids = np.concatenate(
            [_translate(np.asarray(fi), self._fm.ext),
             _translate(np.asarray(mi), self._mm.ext)], axis=1)
        sc = np.concatenate([np.asarray(fs), np.asarray(ms)], axis=1)
        # off the hot path: route the fan-in through the sharded union
        # kernel so both union implementations stay exercised
        return _union_topk(ids, sc, k, device=True)

    def recall(self, queries, k: int) -> float:
        ids, _ = self.query(queries, k=k).result()
        true_ids, _ = self.ground_truth(queries, k)
        return float(metrics.recall_at_k(jnp.asarray(ids),
                                         jnp.asarray(true_ids), k))

    def stats(self) -> dict:
        self.flush()
        out = {
            "n_alive": self.n_alive,
            "n_fresh": int(np.sum(self._fm.present)),
            "n_main": int(np.sum(self._mm.present & ~self._mm.masked)),
            "n_main_masked": int(np.sum(self._mm.masked)),
            "fresh_capacity": self._fresh.state.capacity,
            "main_capacity": self._main.state.capacity,
            "n_merged": self.timers.n_merged,
            "n_refused": self.timers.n_refused,
            "merge_active": self._active_merge is not None,
        }
        # registry-driven maintenance counters (n_merges/merge_s, plus the
        # session-tier counters of this facade's own timers), like
        # Session.stats (DESIGN.md §14)
        out.update(self.timers.maintenance_counters())
        return out

    def check_mirrors(self) -> None:
        """Assert the host mirrors match the device bitmaps bit-exactly."""
        self.flush()
        for name, sess, mir in (("fresh", self._fresh, self._fm),
                                ("main", self._main, self._mm)):
            present = np.asarray(sess.state.present)
            masked = np.asarray(sess.state.masked)
            if not np.array_equal(present, mir.present):
                raise AssertionError(f"{name} present mirror diverged")
            if not np.array_equal(masked, mir.masked):
                raise AssertionError(f"{name} masked mirror diverged")
        if self.params.metric != "cos":   # cos: last-ulp normalize skew
            dev = np.asarray(self._fresh.state.vectors)
            pres = np.flatnonzero(self._fm.present)
            if not np.array_equal(self._fvec[pres], dev[pres]):
                raise AssertionError("fresh vector mirror diverged")
            want = 0.5 * np.sum(np.square(self._fvec[pres]), axis=-1)
            if not np.array_equal(self._fsqh[pres], want):
                raise AssertionError("fresh sqnorm mirror diverged")
        for e, loc in self._loc.items():
            if loc[0] in ("fresh", "both"):
                assert self._fm.ext[loc[1]] == e
            if loc[0] == "main":
                assert self._mm.ext[loc[1]] == e
            elif loc[0] == "both":
                assert self._mm.ext[loc[2]] == e
        both = {e for e, loc in self._loc.items() if loc[0] == "both"}
        if both != self._both_set:
            raise AssertionError(
                f"_both_set diverged: {self._both_set} != {both}")
        alive_bias = (-self._fsqh if self.params.metric == "l2"
                      else np.float32(0.0))
        want_bias = np.where(self._fm.present, alive_bias,
                             np.float32(-np.inf))
        if not np.array_equal(self._fbias, want_bias):
            raise AssertionError("fresh scan bias diverged")
        # the COW ext snapshots, when materialized, must match the live maps
        if self._fext_snap is not None and not np.array_equal(
                self._fext_snap, self._fm.ext):
            raise AssertionError("fresh ext snapshot went stale")
        if self._mext_pad is not None and not np.array_equal(
                self._mext_pad, np.append(self._mm.ext, np.int32(NULL))):
            raise AssertionError("main ext snapshot went stale")

    # -- checkpointing (DESIGN.md §11/§12) ---------------------------------
    def _ckpt_tree(self):
        return {
            "fresh_graph": self._fresh._state,
            "main_graph": self._main._state,
            "base_key": self._base_key,
            "fresh_ext": jnp.asarray(self._fm.ext),
            "main_ext": jnp.asarray(self._mm.ext),
        }

    def save(self, step: int) -> Path:
        """Checkpoint both tiers + ext maps + counters atomically.

        An in-flight merge is completed first (the **merge barrier**): a
        checkpoint never holds a mid-drain item in both tiers, so restore
        needs no merge state beyond the counters. The barrier is journaled
        (JR_MERGE via :meth:`merge`), so a crash between the barrier and
        the checkpoint publish replays to the identical post-merge state.
        """
        mgr = self._require_ckpt()
        if self._active_merge is not None:
            self.merge()
        self.flush()
        path = mgr.save(
            step, self._ckpt_tree(),
            extra={
                "fingerprint": self._fingerprint(),
                "fresh_capacity": int(self._fresh.state.capacity),
                "main_capacity": int(self._main.state.capacity),
                "op_counter": self._op_counter,
                "fresh_op_counter": self._fresh._op_counter,
                "main_op_counter": self._main._op_counter,
                "merge_counter": self._merge_counter,
                # the MERGE registry entry's checkpoint-counter contract
                maint.MERGE.extra_key: getattr(self, maint.MERGE.counter_attr),
                "next_ext": self._next_ext,
                "timers": self.timers.to_dict(),
            },
        )
        faults.crash_point("post-checkpoint-save")
        if self._journal is not None:
            self._journal.reset(meta={"fingerprint": self._fingerprint()})
        return path

    def restore(self, step: int | None = None) -> int:
        """Restore both tiers from a saved step (latest when ``None``).

        Same guard rails as ``Session.restore``: fingerprint must match,
        the main tier's saved capacity must cover this configuration's
        initial capacity, corrupt steps are walked past when ``step`` is
        ``None``. Mirrors and the ext→location table are rebuilt from the
        checkpointed ext maps (a checkpoint never holds mid-merge state,
        so no ``"both"`` entries exist).
        """
        from repro.checkpoint.manager import CheckpointCorruptError

        mgr = self._require_ckpt()
        self.flush()
        if step is None:
            steps = mgr.all_steps()
            if not steps:
                raise FileNotFoundError(f"no checkpoint in {mgr.dir}")
            tree = extra = None
            errors: list[str] = []
            for s in reversed(steps):
                try:
                    tree, extra = mgr.restore(s, self._ckpt_tree())
                    step = s
                    break
                except CheckpointCorruptError as e:
                    errors.append(str(e))
            if tree is None:
                raise CheckpointCorruptError(
                    "every checkpoint step is corrupt:\n  "
                    + "\n  ".join(errors))
        else:
            tree, extra = mgr.restore(step, self._ckpt_tree())
        if extra.get("fingerprint") != self._fingerprint():
            raise ValueError(
                "checkpoint params/strategy fingerprint mismatch — refusing "
                "to restore an index saved under a different configuration")
        tree = jax.tree.map(jnp.asarray, tree)
        fc = int(extra["fresh_capacity"])
        mc = int(extra["main_capacity"])
        if fc != self.fresh_capacity:
            raise ValueError(
                f"checkpoint fresh capacity {fc} != configured "
                f"{self.fresh_capacity}")
        if mc < self.params.capacity:
            raise ValueError(
                f"checkpoint main capacity {mc} is below this "
                f"configuration's initial capacity {self.params.capacity}")
        self._fresh._state = dataclasses.replace(tree["fresh_graph"],
                                                 capacity=fc)
        self._main._state = dataclasses.replace(tree["main_graph"],
                                                capacity=mc)
        self._base_key = tree["base_key"]
        self._op_counter = int(extra["op_counter"])
        self._fresh._op_counter = int(extra["fresh_op_counter"])
        self._main._op_counter = int(extra["main_op_counter"])
        self._merge_counter = int(extra["merge_counter"])
        setattr(self, maint.MERGE.counter_attr,
                int(extra[maint.MERGE.extra_key]))
        self._next_ext = int(extra["next_ext"])
        self._active_merge = None
        # rebuild mirrors + location table from the checkpointed state
        self._fm = _TierMirror(fc)
        self._fm.present = np.asarray(self._fresh.state.present).copy()
        self._fm.ext = np.asarray(tree["fresh_ext"]).astype(np.int32).copy()
        self._fvec = np.asarray(self._fresh.state.vectors).astype(
            np.float32).copy()
        self._fsqh = (0.5 * np.sum(np.square(self._fvec), axis=-1)
                      ).astype(np.float32)
        alive_bias = (-self._fsqh if self.params.metric == "l2"
                      else np.float32(0.0))
        self._fbias = np.where(self._fm.present, alive_bias,
                               np.float32(-np.inf)).astype(np.float32)
        self._ext_snap_dirty()
        self._mm = _TierMirror(mc)
        self._mm.present = np.asarray(self._main.state.present).copy()
        self._mm.masked = np.asarray(self._main.state.masked).copy()
        self._mm.ext = np.asarray(tree["main_ext"]).astype(np.int32).copy()
        self._loc = {}
        self._both_set = set()   # a checkpoint never holds mid-merge state
        for s in np.flatnonzero(self._fm.ext != NULL):
            self._loc[int(self._fm.ext[s])] = ("fresh", int(s))
        for s in np.flatnonzero(self._mm.ext != NULL):
            self._loc[int(self._mm.ext[s])] = ("main", int(s))
        self._fresh._refresh_hints()
        self._main._refresh_hints()
        if self._journal is not None:
            self._journal.reset(meta={"fingerprint": self._fingerprint()})
        return step

    @classmethod
    def recover(
        cls,
        checkpoint_dir: str | Path,
        params: IndexParams,
        *,
        fresh_capacity: int | None = None,
        fresh_strategy: str = "global",
        seed: int = 0,
        checkpoint_keep: int = 3,
        unified_dispatch: bool = True,
        journal_fsync: str = "flush",
    ) -> "TieredSession":
        """Rebuild a crashed tiered session: checkpoint + journal replay.

        Same contract as ``Session.recover`` (DESIGN.md §11): the newest
        valid checkpoint restores, the journal suffix replays through the
        normal op pipeline (queries reproduce only their key/counter
        effects without re-executing), and the result — *including any
        mid-merge progress*, which is a pure function of the op stream — is
        bit-identical to the uninterrupted run over the acknowledged
        prefix.
        """
        from repro.checkpoint import journal as journal_mod

        sess = cls(
            params, fresh_capacity=fresh_capacity,
            fresh_strategy=fresh_strategy, seed=seed,
            checkpoint_dir=checkpoint_dir, checkpoint_keep=checkpoint_keep,
            unified_dispatch=unified_dispatch, journal=False,
            journal_fsync=journal_fsync,
        )
        sess.recovering = True
        t0 = time.perf_counter()
        records, _, dropped = journal_mod.scan_file(
            Path(sess._ckpt.dir) / "journal.bin")
        step = None
        try:
            step = sess.restore(None)
        except FileNotFoundError:
            pass  # crashed before the first checkpoint: replay from empty
        want = sess._fingerprint()
        n_replayed = n_skipped = n_unreplayable = 0
        for idx, rec in enumerate(records):
            code = rec.code
            if code == ops_mod.JR_META:
                fp = rec.aux.get("fingerprint")
                if fp is not None and fp != want:
                    raise ValueError(
                        "journal params/strategy fingerprint mismatch — "
                        "refusing to replay ops recorded under a different "
                        "configuration")
                continue
            if code in (OP_QUERY, OP_INSERT, OP_DELETE, ops_mod.JR_FLUSH):
                if rec.seq < sess._op_counter:
                    n_skipped += 1
                    continue
                if code != ops_mod.JR_FLUSH and rec.seq > sess._op_counter:
                    # gapped suffix: dead timeline (see Session.recover)
                    n_unreplayable = len(records) - idx
                    break
            if code == OP_QUERY:
                # results are gone; reproduce the state effects only: the
                # op-counter bump and the main tier's op key (the fresh
                # scan is host-only — no key, no pump on queries)
                sess._op_counter += 1
                sess._main._op_key()
            elif code == OP_INSERT:
                sess.insert(rec.payload, ids=rec.ids)
            elif code == OP_DELETE:
                sess.delete(rec.ids)
            elif code == ops_mod.JR_FLUSH:
                sess.flush()
            else:
                # tiered maintenance records dispatch through the registry
                # (core/maint.py), mirroring Session.recover
                mop = maint.by_journal_code(code)
                if mop is None or mop.tier != "tiered":
                    raise ValueError(f"unknown journal record code {code}")
                if not mop.replay(sess, rec):
                    n_skipped += 1
                    continue
            n_replayed += 1
        sess._fresh._sync()
        sess._main._sync()
        sess._attach_journal(fresh=n_unreplayable > 0)
        sess.recovering = False
        sess.recovery_info = {
            "step": step,
            "n_replayed": n_replayed,
            "n_skipped": n_skipped,
            "n_unreplayable": n_unreplayable,
            "dropped_bytes": int(dropped),
            "replay_s": time.perf_counter() - t0,
        }
        return sess
