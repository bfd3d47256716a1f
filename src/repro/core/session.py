"""Streaming session API — device-resident state, async dispatch, op IR.

The session is the online index's new public surface (DESIGN.md §7): it owns
a device-resident ``GraphState`` plus the PRNG chain, compiles every
operation of a mixed query/insert/delete stream into fixed-shape
:class:`~repro.core.ops.OpBatch` micro-batches, and dispatches them through
the single jitted, state-donating ``apply_ops`` step. Dispatch is
**asynchronous**: ``query``/``insert``/``delete`` return an
:class:`OpHandle` immediately and the host only synchronizes on
``flush()`` or when a handle's ``result()`` is consumed — so host Python
(padding, encoding, bookkeeping) overlaps device execution instead of
stalling on a per-op ``block_until_ready``.

Key derivation (chunking-invariant, DESIGN.md §7): op number ``t`` uses
``key_t = fold_in(base_key, t)``; lane ``i`` of the op folds its *global*
stream index on top. A query stream therefore returns bit-identical results
no matter how it is chunked or padded — which is what lets the per-op
back-compat facade (``IPGMIndex``) and the streaming session be
parity-tested against each other.

``PhaseTimers`` moves to flush-based accounting: the per-phase fields count
host dispatch time (tiny under async dispatch), ``flush_s`` the synchronous
waits, and ``wall_s`` the busy wall-clock between the first dispatch of a
window and the flush that closes it. ``timers.to_dict()`` is the summary the
bench script consumes.

Consolidation (DESIGN.md §8): when ``MaintenanceParams.consolidate_threshold``
is set, the session auto-fires the jitted compaction pass
(``consolidate()``, OP_CONSOLIDATE micro-batches) at delete-dispatch and
flush boundaries once the tombstone share crosses it — which is what lets a
MASK-strategy session survive an unbounded stream.

Maintenance framework (DESIGN.md §14): every maintenance op — consolidate,
grow, refine, (tiered) merge — is declared once in the registry of
``core/maint.py``; the session's journal cseq snapshots, checkpoint
counters, replay dispatch, ``stats()`` counters, and the fault harness's
crash-point registry all iterate that registry instead of naming ops.

Background refinement (DESIGN.md §15): when
``MaintenanceParams.refine_threshold`` is set, the session opportunistically
fires the jitted refinement pass (``refine()``, OP_REFINE micro-batches) at
flush boundaries — the stream's natural idle points, where the op queue has
just drained — once enough update rows ("wear") have been dispatched since
the last pass. Each pass re-wires one chunk of the stalest alive slots at
construction quality, pinning incremental graphs to fresh-build quality
under churn.

Capacity growth (DESIGN.md §9): when ``MaintenanceParams.max_capacity`` is
set, the session auto-grows the state to a larger capacity tier
(``graph.grow_state``, geometric ``growth_factor`` steps) at
insert-dispatch boundaries, gated exactly like the consolidation trigger —
a free conservative host hint (``_free_hint`` underestimates the free-slot
count), a device-exact check only on crossing, and grow-vs-consolidate
arbitration that compacts tombstones before paying a recompile. Inserts a
full index must refuse (growth disarmed or capped) are *counted* in
``PhaseTimers.n_refused`` instead of silently returning NULL ids.

Durability (DESIGN.md §11): a session with a ``checkpoint_dir`` arms a
write-ahead op journal by default — every acknowledged op appends a
checksummed record *before* device dispatch, checkpoint ``save`` truncates
the log, and :meth:`Session.recover` rebuilds a crashed session as
(newest complete checkpoint) + (deterministic replay of the journaled
suffix). Replay is bit-exact by construction: op keys are a pure function
of logical stream position, auto-maintenance decisions are a pure function
of device-exact state (the conservative hints only gate *when the exact
check runs*, never its outcome), and the two host-initiated trigger sites
replay needs — flush boundaries and explicit ``consolidate``/``grow``
calls — are themselves journaled as marker records. Auto-triggered
maintenance is deliberately NOT journaled: the replayed op stream
re-derives it, so it can never double-apply.
"""
from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import maint, metrics, quantize, rebuild
from repro.core import delete as delete_mod
from repro.core import ops as ops_mod
from repro.core.graph import (
    NULL,
    GraphState,
    graph_stats,
    grow_state,
    init_graph,
    next_capacity_tier,
)
from repro.core.ops import OP_DELETE, OP_INSERT, OP_QUERY
from repro.core.params import IndexParams
from repro.core.trace import span
from repro.testing import faults


@dataclasses.dataclass
class PhaseTimers:
    """Flush-based phase accounting (the paper's QPS / total-time books).

    Per-phase ``*_s`` fields record *host dispatch* time — under async
    dispatch the device wait lands in ``flush_s`` instead, and ``wall_s``
    tracks end-to-end busy wall-clock (first dispatch of a window → the
    flush closing it). The legacy per-op facade flushes after every op, so
    for it ``wall_s`` ≈ the old synchronous per-op totals. ``enqueue_s`` is
    the part of ``query_s``/``insert_s``/``delete_s`` spent in the op's
    device calls (key fold, batch transfer, step call), where the host
    blocks until the previous step releases the donated state;
    ``total()`` does not add it again. Each timed block is also an
    ``ipgm.`` profiler span (``core/trace.py``).
    """

    query_s: float = 0.0
    insert_s: float = 0.0
    delete_s: float = 0.0
    rebuild_s: float = 0.0
    consolidate_s: float = 0.0   # host dispatch + trigger sync of §8 passes
    grow_s: float = 0.0          # §9 capacity-tier moves (pad dispatch)
    merge_s: float = 0.0         # §12 tiered streaming-merge steps
    refine_s: float = 0.0        # §15 background refinement passes
    flush_s: float = 0.0
    wall_s: float = 0.0
    enqueue_s: float = 0.0       # in the ops' device calls (see above)
    n_queries: int = 0
    n_inserts: int = 0
    n_deletes: int = 0
    n_consolidated: int = 0      # tombstones physically removed
    n_consolidations: int = 0    # compaction passes run
    n_refused: int = 0           # insert rows refused by a full index (§9)
    n_grows: int = 0             # capacity-tier moves (≙ op-step recompiles)
    n_rejected: int = 0          # insert rows rejected at dispatch (NaN/Inf)
    n_retries: int = 0           # transient dispatch failures absorbed (§11)
    n_merges: int = 0            # streaming merges completed (§12)
    n_merged: int = 0            # fresh-tier items drained into main (§12)
    n_refines: int = 0           # background refinement passes run (§15)
    n_refined: int = 0           # slots re-wired by refinement (§15)
    n_ops: int = 0

    def total(self) -> float:
        return (self.query_s + self.insert_s + self.delete_s
                + self.rebuild_s + self.consolidate_s + self.grow_s
                + self.merge_s + self.refine_s + self.flush_s)

    def maintenance_counters(self) -> dict:
        """Per-op (count, seconds) pairs, driven by the maint registry —
        a new registered op surfaces here (and in ``Session.stats()`` /
        ``run_workload`` summaries) without naming it anywhere."""
        out: dict = {}
        for op in maint.REGISTRY:
            if op.count_field:
                out[op.count_field] = getattr(self, op.count_field)
            if op.time_field:
                out[op.time_field] = getattr(self, op.time_field)
        return out

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["total_s"] = self.total()
        n_items = self.n_queries + self.n_inserts + self.n_deletes
        d["n_items"] = n_items
        wall = self.wall_s + self.rebuild_s
        d["ops_per_s"] = n_items / wall if wall > 0 else 0.0
        return d


# registry contract: every registered maintenance op's timer fields must
# exist on PhaseTimers — fail at import, not deep inside a stats() call
_TIMER_FIELDS = {f.name for f in dataclasses.fields(PhaseTimers)}
for _op in maint.REGISTRY:
    for _f in (_op.time_field, _op.count_field):
        assert _f is None or _f in _TIMER_FIELDS, (
            f"maint op {_op.name!r} declares timer field {_f!r} "
            "missing from PhaseTimers")
del _TIMER_FIELDS, _op, _f


class OpHandle:
    """Future for one dispatched op — resolves to host results on demand.

    Holds only device *result* arrays (never a GraphState reference, so a
    handle can outlive any number of donations of the session state).
    Consuming the handle (``result()``/``block()``) retires it from the
    session's pending set, so serving loops that resolve every handle never
    accumulate pending state between flushes.
    """

    def __init__(self, op: str, n: int, k: int,
                 chunks: list[tuple[jax.Array, jax.Array, int]],
                 on_done=None):
        self.op = op          # "query" | "insert" | "delete"
        self.n = n            # real (unpadded) item count
        self.k = k            # reported columns for queries
        self._chunks = chunks  # [(ids_dev[B,K], scores_dev[B,K], n_valid)]
        self._on_done = on_done
        self._done = False
        # set by Session.insert when dispatch-time validation dropped rows:
        # positions of the dispatched rows within the caller's batch, so
        # result() reports NULL at the rejected positions instead of
        # silently shrinking the id array (DESIGN.md §11)
        self.row_map: np.ndarray | None = None
        self.total_rows: int | None = None

    def _finish(self) -> None:
        if not self._done:
            self._done = True
            if self._on_done is not None:
                self._on_done(self)

    def _to_host(self, take=None) -> list:
        """Each chunk in order: wait for its step (``handle.wait``), then
        copy what ``take(ids, scores, n_valid)`` picks of its results to the
        host (``handle.fetch``)."""
        out = []
        for c, (ids, scores, nv) in enumerate(self._chunks):
            with span("handle.wait", op=self.op, chunk=c):
                jax.block_until_ready((ids, scores))
            if take is not None:
                with span("handle.fetch", op=self.op, chunk=c):
                    out.append(take(ids, scores, nv))
        return out

    def result(self):
        """Block until this op's results are on host.

        query       → (ids i32[n, k], scores f32[n, k]) numpy arrays
        insert      → ids i32[n] (NULL where the index was full)
        delete      → None
        consolidate → ids i32[n] of the compacted tombstone slots
        refine      → ids i32[n] of the re-wired slots
        """
        try:
            if self.op == "delete":
                self._to_host()
                return None
            if self.op == "query":
                k = self.k
                parts = self._to_host(lambda i, s, nv: (
                    np.asarray(i)[:nv, :k], np.asarray(s)[:nv, :k]))
                if not parts:
                    return (np.full((0, k), NULL, np.int32),
                            np.full((0, k), -np.inf, np.float32))
                with span("handle.fetch", op=self.op):
                    return (np.concatenate([p[0] for p in parts]),
                            np.concatenate([p[1] for p in parts]))
            # insert/consolidate/refine: slot ids ride in column 0 of the
            # result block
            parts = self._to_host(lambda i, s, nv: np.asarray(i)[:nv, 0])
            if not parts:
                out = np.zeros((0,), np.int32)
            else:
                with span("handle.fetch", op=self.op):
                    out = np.concatenate(parts)
            if self.op == "insert" and self.total_rows is not None:
                full = np.full((self.total_rows,), NULL, np.int32)
                full[self.row_map] = out
                return full
            return out
        finally:
            self._finish()

    def block(self) -> None:
        self._to_host()
        self._finish()


def consolidate_gate_crossed(thr: float | None, masked_hint: int,
                             present_floor: int) -> bool:
    """The free host-side consolidation gate (DESIGN.md §8), shared by
    :class:`Session` and ``ShardedSession``: with an overestimated tombstone
    count and an underestimated present count it only ever errs toward
    *checking* — the device-exact measurement runs only when this crosses."""
    return (thr is not None and masked_hint > 0
            and masked_hint >= thr * max(present_floor, 1))


def params_fingerprint(params: IndexParams, strategy: str) -> str:
    """Stable identity of (index geometry + policy, strategy) for checkpoint
    guarding.

    ``capacity`` is deliberately *excluded*: it is the one axis two
    compatible configurations may legitimately differ on — the growth engine
    (DESIGN.md §9) moves a session past its initial capacity tier, so a
    checkpoint records its live capacity separately (``extra["capacity"]``)
    and ``Session.restore`` range-checks it instead of fingerprinting it.
    Everything else — geometry (dim/degrees/metric), search knobs, and the
    maintenance policy including ``growth_factor``/``max_capacity`` — must
    match exactly. The vector-code scheme (DESIGN.md §10) is part of the
    geometry: a checkpoint's int8 codes are only meaningful to an engine
    that scores them under the same quantization scheme, so
    ``quantize.VECTOR_CODE_SCHEME`` is folded in and a scheme change
    invalidates old checkpoints instead of silently mis-scoring them.
    """
    def enc(obj):
        if dataclasses.is_dataclass(obj):
            return {f.name: enc(getattr(obj, f.name))
                    for f in dataclasses.fields(obj)}
        return obj
    d = enc(params)
    d.pop("capacity", None)
    return json.dumps({"params": d, "strategy": strategy,
                       "vector_codes": quantize.VECTOR_CODE_SCHEME},
                      sort_keys=True)


class Session:
    """Device-resident streaming session over one proximity-graph index.

    The session owns its ``GraphState`` exclusively: every dispatched op
    donates the state buffers to the jitted step and replaces the held
    reference with the returned (aliased or rewritten) state — no call-site
    ever sees a pre-donation array. Reads (``stats``, ``ground_truth``,
    ``rebuild_from_alive``, ``save``) implicitly ``flush()`` first.
    """

    def __init__(
        self,
        params: IndexParams,
        *,
        strategy: str | None = None,
        seed: int = 0,
        state: GraphState | None = None,
        checkpoint_dir: str | Path | None = None,
        checkpoint_keep: int = 3,
        unified_dispatch: bool = True,
        journal: bool | None = None,
        journal_fsync: str = "flush",
        flush_retries: int = 3,
        flush_backoff_s: float = 0.005,
    ):
        known = delete_mod.STRATEGIES + delete_mod.REFERENCE_STRATEGIES
        strategy = strategy if strategy is not None else params.maintenance.strategy
        if strategy not in known:
            raise ValueError(f"strategy must be one of {known}")
        self.params = params
        self.strategy = strategy
        self.seed = seed
        self._base_key = jax.random.PRNGKey(seed)
        self._op_counter = 0
        self._state = state if state is not None else init_graph(
            params.capacity, params.dim, d_out=params.d_out,
            d_in=params.eff_d_in, metric=params.metric,
        )
        self.timers = PhaseTimers()
        self._pending: list[OpHandle] = []
        self._window_t0: float | None = None
        # unified_dispatch=True routes every op through the traced-op_code
        # switch program (ONE compiled step per shape family for the whole
        # mixed stream); False selects the branch at trace time instead
        # (per-branch programs — the facade's compile-lean mode).
        self.unified_dispatch = unified_dispatch
        # consolidation engine bookkeeping (DESIGN.md §8): a *separate* PRNG
        # chain (so auto-triggered passes never shift op keys), plus cheap
        # host-side hints that gate the trigger without syncing the stream —
        # `_masked_hint` overestimates the tombstone count (every dispatched
        # mask-delete lane bumps it), `_present_floor` underestimates the
        # present count (inserts are ignored, hard deletes over-subtract);
        # the ratio therefore only ever errs toward *checking*, and the
        # device-exact measurement happens only when the gate crosses.
        self._consolidate_counter = 0
        self._in_consolidate = False
        self._masked_hint = 0
        self._present_floor = 0
        self.last_consolidate_handle: OpHandle | None = None
        # background-refinement bookkeeping (DESIGN.md §15): its own PRNG
        # chain counter (same isolation contract as consolidation) plus the
        # "wear" odometer — update rows dispatched since the last pass. Wear
        # is a pure function of the op stream (never of pending-queue depth
        # or wall-clock), which is what makes auto-refine decisions replay
        # deterministically; it is checkpointed alongside the counter.
        self._refine_counter = 0
        self._refine_wear = 0
        self._in_refine = False
        self.last_refine_handle: OpHandle | None = None
        # growth engine bookkeeping (DESIGN.md §9): `_free_hint`
        # *underestimates* the free-slot count (every dispatched insert row
        # subtracts, hard-delete frees are ignored), so an insert the hint
        # covers can never refuse — the device-exact room check runs only
        # when the hint crosses below the incoming batch size.
        self._free_hint = self._state.capacity
        if (state is not None
                or params.maintenance.consolidate_threshold is not None):
            self._refresh_hints()
        self._ckpt = None
        if checkpoint_dir is not None:
            from repro.checkpoint import CheckpointManager
            self._ckpt = CheckpointManager(checkpoint_dir, keep=checkpoint_keep)
        # durability layer (DESIGN.md §11): journal=None arms the write-ahead
        # op log whenever a checkpoint_dir is set. A *constructed* session is
        # a fresh timeline, so attach resets the log (stamping a META record
        # with the params fingerprint); Session.recover is the only path
        # that extends an existing journal. Single writer per directory.
        self.recovering = False
        self.recovery_info: dict | None = None
        self._journal = None
        self._journal_fsync = journal_fsync
        self._flush_retries = int(flush_retries)
        self._flush_backoff_s = float(flush_backoff_s)
        if journal is None:
            journal = checkpoint_dir is not None
        if journal:
            self._require_ckpt()
            self._attach_journal(fresh=True)

    # -- state ownership ---------------------------------------------------
    @property
    def state(self) -> GraphState:
        """The current (post-all-dispatched-ops) device state."""
        return self._state

    def set_state(self, state: GraphState) -> None:
        """Replace the session state (flushes pending work first)."""
        self.flush()
        self._state = state
        self._refresh_hints()

    @property
    def chunk(self) -> int:
        """The op-IR unified micro-batch width (streaming query default)."""
        return self.params.maintenance.insert_chunk

    # -- key plumbing ------------------------------------------------------
    def _op_key(self) -> jax.Array:
        key = jax.random.fold_in(self._base_key, self._op_counter)
        self._op_counter += 1
        return key

    # -- write-ahead journal (DESIGN.md §11) -------------------------------
    def _attach_journal(self, *, fresh: bool) -> None:
        from repro.checkpoint.journal import OpJournal

        path = Path(self._ckpt.dir) / "journal.bin"
        self._journal = OpJournal(path, fsync=self._journal_fsync)
        if fresh:
            self._journal.reset(meta={
                "fingerprint": params_fingerprint(self.params, self.strategy),
            })
        else:
            # recovery path: physically drop the torn/corrupt tail so new
            # appends extend a clean record prefix
            self._journal.repair()

    def _journal_append(self, code: int, *, payload=None, ids=None,
                        aux: dict | None = None) -> None:
        """Append one record *before* the action it describes (write-ahead).

        ``seq``/``cseq`` snapshot the op counter and the record's dedup
        counter at append time, which is what lets recovery skip records a
        later checkpoint already subsumes (the crash window between
        checkpoint publish and journal truncation would otherwise
        double-replay). The dedup counter is registry-driven: a maintenance
        record snapshots its *own* op's counter (consolidate →
        ``_consolidate_counter``, refine → ``_refine_counter``, ...); every
        other record keeps the legacy consolidate-counter snapshot
        byte-compatibly (stream-op replay only ever gates on ``seq``).
        """
        if self._journal is None:
            return
        mop = maint.by_journal_code(code)
        cseq = (getattr(self, mop.counter_attr)
                if mop is not None and mop.counter_attr is not None
                else self._consolidate_counter)
        with span("journal.append", code=code):
            self._journal.append(code, seq=self._op_counter, cseq=cseq,
                                 payload=payload, ids=ids, aux=aux)
        faults.crash_point("post-journal-append")

    # -- dispatch core -----------------------------------------------------
    def _dispatch(self, op_code: int, arr, chunk: int, *,
                  fold_chunk_key: bool = False) -> OpHandle:
        """Chop one op into padded OpBatches and enqueue them (no sync)."""
        for mop in maint.SESSION_OPS:
            if mop.op_code is not None and op_code == mop.op_code:
                # static-only maintenance op: the traced switch would
                # silently clip it to NOOP — route through the op's own
                # session method instead
                raise ValueError(
                    f"OP_{mop.name.upper()} is not a stream op; "
                    f"use Session.{mop.name}()")
        # Every device call of the op sits in an ``enqueue`` span: once the
        # runtime holds as many steps in flight as it takes, whichever call
        # comes next (the key fold, the batch's transfer, the step call)
        # blocks until the previous step releases the donated state.
        with span("session.enqueue", self.timers, "enqueue_s"):
            key = self._op_key()  # consumed even for empty ops: stable chain
        n = arr.shape[0]
        if n == 0:  # no device work: don't arm the busy-wall window
            h = OpHandle(ops_mod.OP_NAMES[op_code], 0,
                         self.params.search.pool_size, [])
            self.timers.n_ops += 1
            return h
        if self._window_t0 is None:
            self._window_t0 = time.perf_counter()
        static_op = None if self.unified_dispatch else op_code
        is_delete = op_code == OP_DELETE
        name = ops_mod.OP_NAMES[op_code]
        chunks = []
        for ci, lo in enumerate(range(0, n, chunk)):
            with span("session.step", op=name, chunk=ci):
                part = arr[lo:lo + chunk]
                with span("session.enqueue", self.timers, "enqueue_s"):
                    batch = ops_mod.make_op(
                        op_code, chunk, self.params.dim,
                        payload=None if is_delete else part,
                        ids=part if is_delete else None,
                        offset=lo,
                    )
                    # deletes decorrelate multi-chunk repair searches by
                    # chunk index (their lane folds are chunk-local);
                    # query/insert fold global stream indices via `offset`
                    # instead, for chunking invariance
                    ckey = (jax.random.fold_in(key, ci) if fold_chunk_key
                            else key)
                    self._state, ids, scores = ops_mod.apply_ops_step(
                        self._state, batch, ckey, self.params, self.strategy,
                        static_op=static_op,
                    )
                chunks.append((ids, scores, part.shape[0]))
        handle = OpHandle(name, n, self.params.search.pool_size, chunks,
                          on_done=self._handle_done)
        self._pending.append(handle)
        self.timers.n_ops += 1
        return handle

    def _handle_done(self, handle: OpHandle) -> None:
        """A consumed handle retires from the pending set; when the set
        drains without an explicit flush (serving loops that resolve every
        result), the timer window closes here instead."""
        try:
            self._pending.remove(handle)
        except ValueError:
            return  # already retired by flush()
        if not self._pending and self._window_t0 is not None:
            self.timers.wall_s += time.perf_counter() - self._window_t0
            self._window_t0 = None

    # -- the op surface ----------------------------------------------------
    def query(self, queries, k: int | None = None, *,
              chunk: int | None = None) -> OpHandle:
        """Dispatch a batched ANN query; returns a handle (async).

        ``handle.result()`` → (ids i32[B,k], scores f32[B,k]). Results are
        invariant to ``chunk`` (per-item keys fold global stream indices).
        """
        q = np.asarray(queries, np.float32)
        k = k if k is not None else self.params.search.pool_size
        # queries don't mutate state but DO consume an op key, so replay must
        # know they happened — a count-only record keeps the journal cheap
        self._journal_append(OP_QUERY, aux={"n": int(q.shape[0])})
        with span("session.query", self.timers, "query_s"):
            h = self._dispatch(OP_QUERY, q, chunk or self.chunk)
            h.k = min(k, self.params.search.pool_size)
        self.timers.n_queries += q.shape[0]
        return h

    def insert(self, vectors, *, chunk: int | None = None) -> OpHandle:
        """Dispatch a batch insert; ``handle.result()`` → assigned ids.

        The insert-dispatch boundary is the growth trigger point
        (DESIGN.md §9): ``_ensure_room`` grows the capacity tier and/or
        compacts tombstones before the batch runs, so an armed session
        (``maintenance.max_capacity``) never returns NULL ids until the
        ceiling is reached — and every refusal that does happen is counted
        in ``timers.n_refused``.
        """
        v = np.asarray(vectors, np.float32)
        self._journal_append(OP_INSERT, payload=v, aux={"chunk": chunk})
        # dispatch-time validation: a NaN/Inf row would poison every distance
        # it ever participates in, so it is rejected here (counted in
        # timers.n_rejected, NULL id at its position in result()). Exact-zero
        # rows are legitimate and insert normally — the quantizer gives them
        # a positive sentinel scale so their codes can never collide with
        # the freed-slot (0, 0.0) scrub pattern of invariant I5 (§10/§11).
        total, keep = v.shape[0], None
        if total:
            finite = np.isfinite(v).all(axis=1)
            if not finite.all():
                self.timers.n_rejected += int(total - finite.sum())
                keep = np.flatnonzero(finite)
                v = v[keep]
        # the gate runs OUTSIDE the insert stopwatch: its consolidation /
        # growth work bills to consolidate_s / grow_s (as the delete-path
        # trigger does), so PhaseTimers.total() never double-counts
        if v.shape[0]:
            self._ensure_room(v.shape[0])
        with span("session.insert", self.timers, "insert_s"):
            h = self._dispatch(OP_INSERT, v, chunk or
                               self.params.maintenance.insert_chunk)
            if keep is not None:
                h.row_map, h.total_rows = keep, total
            self._free_hint = max(self._free_hint - v.shape[0], 0)
            self._refine_wear += v.shape[0]
        self.timers.n_inserts += v.shape[0]
        return h

    def delete(self, ids, *, chunk: int | None = None) -> OpHandle:
        """Dispatch a batch delete with the session's strategy.

        A MASK delete is the only op that grows the tombstone set, so this
        is one of the two consolidation trigger points (the other is
        ``flush`` — DESIGN.md §8).
        """
        arr = np.asarray(ids, np.int32)
        eff_chunk = chunk or self.params.maintenance.delete_chunk
        # delete repair keys fold the chunk index (chunk-local lanes), so the
        # effective width is part of the op's identity — journal it
        self._journal_append(OP_DELETE, ids=arr,
                             aux={"chunk": int(eff_chunk)})
        with span("session.delete", self.timers, "delete_s"):
            h = self._dispatch(OP_DELETE, arr, eff_chunk,
                               fold_chunk_key=True)
        self.timers.n_deletes += arr.shape[0]
        self._refine_wear += arr.shape[0]
        if self.strategy == "mask":
            self._masked_hint += arr.shape[0]
            self._maybe_consolidate()
        else:
            self._present_floor = max(self._present_floor - arr.shape[0], 0)
        return h

    # -- maintenance-op plumbing (DESIGN.md §14) ---------------------------
    def _maint_key(self, mop: maint.MaintOp) -> jax.Array:
        """Next key of ``mop``'s chain — derived from the base key but on
        the op's own registered stream, so firing (or not firing) a pass
        never perturbs the op-key chain of the surrounding stream."""
        counter = getattr(self, mop.counter_attr)
        setattr(self, mop.counter_attr, counter + 1)
        return maint.maint_key(self._base_key, mop, counter)

    def _refresh_hints(self) -> None:
        """Replace the host hints with device-exact counts (synchronizes)."""
        self._masked_hint = int(jnp.sum(self._state.masked))
        self._present_floor = int(jnp.sum(self._state.present))
        self._free_hint = self._state.capacity - self._present_floor

    def consolidate(self, *, strategy: str | None = None,
                    chunk: int | None = None,
                    _n_masked: int | None = None,
                    _auto: bool = False) -> int:
        """Physically remove every tombstone: the jitted compaction pass.

        Reads the exact tombstone count (synchronizing on the dispatched
        stream; the auto-trigger passes the count it just measured via
        ``_n_masked`` instead of reducing twice), then dispatches
        ``ceil(n/chunk)`` OP_CONSOLIDATE micro-batches — each compacts the
        lowest-id tombstones at its stream position, repairs the survivors'
        rows with ``consolidate_strategy`` and returns the freed slots to
        the allocator. Returns the number of consolidated vertices; the
        dispatched work itself is async (settled by ``flush``/reads).

        Only *explicit* calls journal (JR_CONSOLIDATE): auto-triggered
        passes (``_auto=True``) are a pure function of the replayed op
        stream and would double-apply if recorded (DESIGN.md §11).
        """
        if not _auto:
            self._journal_append(ops_mod.JR_CONSOLIDATE,
                                 aux={"strategy": strategy, "chunk": chunk})
        faults.crash_point("pre-consolidate")
        with span("session.consolidate", self.timers, "consolidate_s"):
            n_masked = (int(jnp.sum(self._state.masked))
                        if _n_masked is None else int(_n_masked))
            if n_masked == 0:
                self._masked_hint = 0
                return 0
            if self._window_t0 is None:
                self._window_t0 = time.perf_counter()
            mp = self.params.maintenance
            chunk = int(chunk) if chunk else (mp.consolidate_chunk
                                              or mp.delete_chunk)
            params = self.params
            if strategy is not None and strategy != mp.consolidate_strategy:
                params = dataclasses.replace(
                    self.params,
                    maintenance=dataclasses.replace(
                        mp, consolidate_strategy=strategy),
                )
            # always static-dispatched (ops.py): maintenance passes are
            # host-initiated, so the mixed-stream switch never carries this
            # branch and only consolidating sessions compile it
            static_op = ops_mod.OP_CONSOLIDATE
            chunks = []
            # the op is operand-free: one encoded batch serves every drain
            # step
            batch = ops_mod.make_op(ops_mod.OP_CONSOLIDATE, chunk,
                                    self.params.dim)
            for lo in range(0, n_masked, chunk):
                self._state, ids, scores = ops_mod.apply_ops_step(
                    self._state, batch, self._maint_key(maint.CONSOLIDATE),
                    params, self.strategy, static_op=static_op,
                )
                chunks.append((ids, scores, min(chunk, n_masked - lo)))
            handle = OpHandle(
                "consolidate", n_masked, self.params.search.pool_size, chunks,
                on_done=self._handle_done,
            )
            # the int return keeps the legacy contract; the compacted slot
            # ids stay reachable through this handle until consumed/flushed
            self.last_consolidate_handle = handle
            self._pending.append(handle)
            self.timers.n_ops += 1
            self.timers.n_consolidations += 1
            self.timers.n_consolidated += n_masked
        self._masked_hint = 0
        self._present_floor = max(self._present_floor - n_masked, 0)
        self._free_hint += n_masked  # compacted slots return to the allocator
        faults.crash_point("post-consolidate")
        return n_masked

    def _maybe_consolidate(self) -> int:
        """Auto-trigger: fire the compaction pass when the tombstone share
        crosses ``consolidate_threshold``. The host-side hint gate is free
        and conservative (only ever errs toward checking); the device-exact
        measurement — which synchronizes — runs only when it crosses."""
        thr = self.params.maintenance.consolidate_threshold
        if self._in_consolidate or not consolidate_gate_crossed(
                thr, self._masked_hint, self._present_floor):
            return 0
        self._refresh_hints()  # device-exact (synchronizes)
        if not consolidate_gate_crossed(
                thr, self._masked_hint, self._present_floor):
            return 0
        self._in_consolidate = True
        try:
            return self.consolidate(_n_masked=self._masked_hint, _auto=True)
        finally:
            self._in_consolidate = False

    # -- background refinement engine (DESIGN.md §15) ----------------------
    def refine(self, *, n: int | None = None, chunk: int | None = None,
               _auto: bool = False) -> int:
        """Re-wire the stalest alive slots at construction quality.

        Dispatches ``ceil(n/chunk)`` OP_REFINE micro-batches — each picks
        the chunk's worth of lowest-``touch`` alive slots at its stream
        position (refined rows bump their stamp on-device, so successive
        chunks sweep oldest-rows-first), re-searches their own vectors
        through the batched beam engine at ``eff_insert_search`` quality,
        re-selects over (pool ∪ current row) and scatter-applies. ``n``
        defaults to one chunk — a bounded slice of background work.
        Returns the number of slots submitted for refinement; the
        dispatched work itself is async (settled by ``flush``/reads).

        Refinement never changes the alive set, so it needs no hint
        bookkeeping; its keys come from the registered REFINE chain, so
        firing a pass never shifts the op-key chain (timing invariance).
        Only *explicit* calls journal (JR_REFINE): auto-triggered passes
        are a pure function of the replayed op stream (DESIGN.md §11).
        """
        if not _auto:
            self._journal_append(
                maint.REFINE.journal_code,
                aux={"n": None if n is None else int(n),
                     "chunk": None if chunk is None else int(chunk)})
        faults.crash_point("refine-begin")
        with span("session.refine", self.timers, "refine_s"):
            mp = self.params.maintenance
            chunk = int(chunk) if chunk else (mp.refine_chunk
                                              or mp.insert_chunk)
            n_alive = int(jnp.sum(self._state.alive))
            n_target = min(chunk if n is None else int(n), n_alive)
            self._refine_wear = 0  # any pass resets the odometer (replay too)
            if n_target <= 0:
                return 0
            if self._window_t0 is None:
                self._window_t0 = time.perf_counter()
            # static-dispatched like every maintenance op: host-initiated, so
            # the mixed-stream switch never carries this branch and only
            # refining sessions compile it. Operand-free: one encoded batch
            # serves every step of the pass.
            batch = ops_mod.make_op(ops_mod.OP_REFINE, chunk, self.params.dim)
            chunks = []
            for lo in range(0, n_target, chunk):
                self._state, ids, scores = ops_mod.apply_ops_step(
                    self._state, batch, self._maint_key(maint.REFINE),
                    self.params, self.strategy, static_op=ops_mod.OP_REFINE,
                )
                chunks.append((ids, scores, min(chunk, n_target - lo)))
                faults.crash_point("refine-step")
            handle = OpHandle(
                "refine", n_target, self.params.search.pool_size, chunks,
                on_done=self._handle_done,
            )
            self.last_refine_handle = handle
            self._pending.append(handle)
            self.timers.n_ops += 1
            self.timers.n_refines += 1
            self.timers.n_refined += n_target
        return n_target

    def _maybe_refine(self) -> int:
        """Opportunistic trigger: fire one bounded refinement pass at a
        flush boundary (the op queue has just drained — the stream's idle
        point) once ``refine_threshold`` update rows of wear accumulated.
        The wear odometer is free host arithmetic; the only device read is
        the alive count of the pass itself, paid when the gate crosses."""
        thr = self.params.maintenance.refine_threshold
        if thr is None or self._in_refine or self._refine_wear < thr:
            return 0
        self._in_refine = True
        try:
            return self.refine(_auto=True)
        finally:
            self._in_refine = False

    # -- capacity growth engine (DESIGN.md §9) -----------------------------
    def _ensure_room(self, n: int) -> None:
        """Grow/consolidate gate at the insert-dispatch boundary.

        ``_free_hint`` is a guaranteed underestimate of the free-slot count,
        so when it covers the batch no refusal is possible and the gate is
        free; the device-exact room check (which synchronizes the stream)
        runs only on crossing. Arbitration then compacts tombstones before
        paying a growth recompile — reclaiming masked slots is one
        consolidation pass inside the already-compiled shape family, growing
        is a whole new tier. Whatever shortfall survives (growth disarmed or
        capped at ``max_capacity``) is counted into ``timers.n_refused`` —
        exactly, because the allocator fills the lowest free slots first and
        refuses the remaining rows deterministically.
        """
        if self._free_hint >= n:
            return
        mp = self.params.maintenance
        self._refresh_hints()  # device-exact (synchronizes)
        free = self._free_hint
        if free < n and self._masked_hint > 0 and (
                mp.consolidate_threshold is not None
                or mp.max_capacity is not None):
            free += self.consolidate(_n_masked=self._masked_hint, _auto=True)
        if free < n and mp.max_capacity is not None:
            cap = self._state.capacity
            target = next_capacity_tier(
                cap, cap - free + n, mp.growth_factor, mp.max_capacity)
            if target > cap:
                self.grow(target, _auto=True)
                free += target - cap
        if free < n:
            self.timers.n_refused += n - free
        self._free_hint = free

    def grow(self, new_capacity: int, *, _auto: bool = False) -> None:
        """Move the state to a larger capacity tier (``graph.grow_state``).

        Dispatches asynchronously like every other op — existing slots keep
        their ids, new slots arrive free — and puts the session in a new
        shape family: the next ``apply_ops_step`` dispatch compiles once for
        the new tier (op-key chain and per-lane PRNG folds are untouched, so
        logical streams are growth-timing-invariant, DESIGN.md §9). An
        *armed* session enforces ``maintenance.max_capacity`` here too, so
        every tier it can ever save is one its own config restores.
        """
        if new_capacity == self._state.capacity:
            return
        with span("session.grow", self.timers, "grow_s",
                  capacity=int(new_capacity)):
            ceiling = self.params.maintenance.max_capacity
            if ceiling is not None and new_capacity > ceiling:
                raise ValueError(
                    f"new_capacity {new_capacity} exceeds maintenance."
                    f"max_capacity {ceiling}")
            if not _auto:
                # explicit tier moves are journaled; auto-growth re-derives
                # from the replayed op stream (same rationale as consolidate)
                self._journal_append(ops_mod.JR_GROW,
                                     aux={"new_capacity": int(new_capacity)})
            faults.crash_point("pre-grow")
            if self._window_t0 is None:
                self._window_t0 = time.perf_counter()
            grown = grow_state(self._state, new_capacity)
            self._free_hint += grown.capacity - self._state.capacity
            self._state = grown
            self.timers.n_grows += 1
        faults.crash_point("post-grow")

    def flush(self) -> PhaseTimers:
        """Synchronize: block until every dispatched op (and the state) is
        materialized; settle the timer window. Returns the timers. Also a
        consolidation trigger point (DESIGN.md §8): the threshold check runs
        first, so the flushed state is the compacted one.

        Because the trigger can compact, *when* a flush happened is part of
        the stream's logical identity — so a journaled session records a
        JR_FLUSH marker before the trigger and replay re-flushes at the same
        positions (DESIGN.md §11). The marker precedes the trigger for the
        same write-ahead reason as every other record.
        """
        faults.crash_point("pre-flush")
        self._journal_append(ops_mod.JR_FLUSH)
        self._maybe_consolidate()
        self._maybe_refine()
        self._sync()
        faults.crash_point("post-flush")
        return self.timers

    def _sync(self) -> None:
        """The synchronization body of :meth:`flush`, without the trigger or
        the journal marker — recovery settles replayed work through this so
        it cannot fire a compaction the original timeline never saw.

        Transient dispatch/sync failures (a device runtime hiccup — injected
        in tests via ``faults.transient``) are absorbed with bounded
        exponential backoff; exhaustion re-raises, counted retries land in
        ``timers.n_retries``.
        """
        with span("session.flush", self.timers, "flush_s"):
            attempt = 0
            while True:
                try:
                    faults.transient_point("flush")
                    for h in list(self._pending):  # block() retires in place
                        h.block()
                    jax.block_until_ready(self._state.adj)
                    break
                except faults.TransientDispatchError:
                    if attempt >= self._flush_retries:
                        raise
                    self.timers.n_retries += 1
                    time.sleep(self._flush_backoff_s * (2.0 ** attempt))
                    attempt += 1
            self._pending.clear()
            if (self._journal is not None
                    and self._journal.fsync_policy == "flush"):
                self._journal.sync()  # flush is the acknowledgement barrier
        if self._window_t0 is not None:
            self.timers.wall_s += time.perf_counter() - self._window_t0
            self._window_t0 = None

    def _live_params(self) -> IndexParams:
        """``self.params`` with ``capacity`` pinned to the live state's tier
        (they diverge once the growth engine moves past the initial tier)."""
        if self.params.capacity == self._state.capacity:
            return self.params
        return dataclasses.replace(
            self.params, capacity=self._state.capacity)

    # -- host-path maintenance --------------------------------------------
    def rebuild_from_alive(self) -> None:
        """ReBuild baseline: reconstruct the whole graph from alive vectors.

        Rebuilds at the *live* capacity tier (``state.capacity``), not the
        initial ``params.capacity`` — after a growth the two diverge, and
        rebuilding at the stale tier would silently shrink the index.
        """
        self.flush()
        with span("session.rebuild", self.timers, "rebuild_s"):
            live_cap = self._state.capacity
            alive = np.asarray(self._state.alive)
            vecs = np.asarray(self._state.vectors)[alive]
            n = vecs.shape[0]
            padded = np.zeros((live_cap, self.params.dim), vecs.dtype)
            padded[:n] = vecs
            valid = jnp.arange(live_cap) < n
            self._state = rebuild.bulk_knn_build(
                jnp.asarray(padded), valid, self._live_params()
            )
            jax.block_until_ready(self._state.adj)
            self._masked_hint = 0
            self._present_floor = n
            self._free_hint = live_cap - n

    # -- reporting ---------------------------------------------------------
    def ground_truth(self, queries, k: int):
        self.flush()
        return metrics.brute_force_topk(self._state, jnp.asarray(queries), k)

    def recall(self, queries, k: int) -> float:
        ids, _ = self.query(queries, k=k).result()
        _, true_ids = self.ground_truth(queries, k)
        return float(metrics.recall_at_k(jnp.asarray(ids), true_ids, k))

    def stats(self) -> dict:
        self.flush()
        out = {k: np.asarray(v).item()
               for k, v in graph_stats(self._state).items()}
        out["capacity"] = self._state.capacity  # live tier, not params'
        out["n_refused"] = self.timers.n_refused
        # every registered maintenance op reports its count/time uniformly
        # (n_consolidations/consolidate_s, n_grows/grow_s, n_refines/
        # refine_s, n_merges/merge_s) — a new op's counters arrive for free
        out.update(self.timers.maintenance_counters())
        return out

    # -- checkpointing (DESIGN.md §7) --------------------------------------
    def _require_ckpt(self):
        if self._ckpt is None:
            raise ValueError(
                "session has no checkpoint_dir; pass checkpoint_dir= to "
                "Session(...) to enable save/restore"
            )
        return self._ckpt

    def _ckpt_tree(self):
        return {"graph": self._state, "base_key": self._base_key}

    def save(self, step: int) -> Path:
        """Checkpoint GraphState + PRNG chain + timers + params fingerprint.

        The fingerprint covers geometry + policy only; the *live* capacity
        tier (which growth may have moved past ``params.capacity``) is
        recorded separately so ``restore`` can range-check it.
        """
        mgr = self._require_ckpt()
        self.flush()
        extra = {
            "fingerprint": params_fingerprint(self.params, self.strategy),
            "capacity": int(self._state.capacity),
            "op_counter": self._op_counter,
            "timers": self.timers.to_dict(),
        }
        # checkpoint-counter contract (DESIGN.md §14): each registered
        # maintenance op persists its dedup counter + declared state attrs
        for mop in maint.SESSION_OPS:
            if mop.extra_key is not None:
                extra[mop.extra_key] = int(getattr(self, mop.counter_attr))
            for attr, ekey in mop.state_attrs:
                extra[ekey] = int(getattr(self, attr))
        path = mgr.save(step, self._ckpt_tree(), extra=extra)
        # the published checkpoint subsumes the whole journal prefix; a crash
        # in this window (before truncation) is safe — recovery skips records
        # whose seq/cseq the restored counters already cover
        faults.crash_point("post-checkpoint-save")
        if self._journal is not None:
            self._journal.reset(meta={
                "fingerprint": params_fingerprint(self.params, self.strategy),
            })
        return path

    def restore(self, step: int | None = None) -> int:
        """Restore the session to a saved step (latest when ``step=None``).

        Rejects checkpoints written under a different (params, strategy)
        fingerprint — restoring a graph into mismatched geometry would
        corrupt it silently. Capacity is exempt from the fingerprint
        (DESIGN.md §9): any saved tier ≥ ``params.capacity`` restores (the
        allocator cannot shrink) and the session resumes at that tier;
        ``max_capacity`` bounds *growth*, not restorability — the matching
        policy fingerprint already guarantees the writer enforced the same
        ceiling. Returns the restored step number.

        ``step=None`` walks back past corrupt steps (a torn manifest or
        garbled shard raises :class:`~repro.checkpoint.manager.
        CheckpointCorruptError` per step and the next-older complete step is
        tried); an explicit ``step`` propagates the typed error instead.
        Restoring rewinds the timeline, so an attached journal is reset —
        its suffix described a future this session no longer has.
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
        want = params_fingerprint(self.params, self.strategy)
        if extra.get("fingerprint") != want:
            raise ValueError(
                "checkpoint params/strategy fingerprint mismatch — refusing "
                "to restore an index saved under a different configuration"
            )
        tree = jax.tree.map(jnp.asarray, tree)
        state = tree["graph"]
        saved_cap = int(extra.get("capacity", state.alive.shape[0]))
        if saved_cap < self.params.capacity:
            raise ValueError(
                f"checkpoint capacity {saved_cap} is below this "
                f"configuration's initial capacity {self.params.capacity} "
                "— shrinking an allocator is not supported, refusing to "
                "restore"
            )
        # the unflatten used the *current* session's treedef, whose static
        # capacity may be a different tier — re-pin it to the saved arrays
        self._state = dataclasses.replace(state, capacity=saved_cap)
        self._base_key = tree["base_key"]
        self._op_counter = int(extra["op_counter"])
        # registry-driven counter restore; .get(..., 0) keeps checkpoints
        # written before an op existed restorable (missing key = never fired)
        for mop in maint.SESSION_OPS:
            if mop.extra_key is not None:
                setattr(self, mop.counter_attr,
                        int(extra.get(mop.extra_key, 0)))
            for attr, ekey in mop.state_attrs:
                setattr(self, attr, int(extra.get(ekey, 0)))
        self._refresh_hints()
        if self._journal is not None:
            self._journal.reset(meta={
                "fingerprint": params_fingerprint(self.params, self.strategy),
            })
        return step

    @classmethod
    def recover(
        cls,
        checkpoint_dir: str | Path,
        params: IndexParams,
        *,
        strategy: str | None = None,
        seed: int = 0,
        checkpoint_keep: int = 3,
        unified_dispatch: bool = True,
        journal_fsync: str = "flush",
        flush_retries: int = 3,
        flush_backoff_s: float = 0.005,
    ) -> "Session":
        """Rebuild a crashed session from ``checkpoint_dir`` (DESIGN.md §11).

        Restores the newest checkpoint that validates (walking past corrupt
        steps), scans the write-ahead journal — dropping any torn/corrupt
        tail — and replays the suffix through the normal op pipeline:
        records whose ``seq``/``cseq`` the restored counters already cover
        are skipped (the checkpoint subsumes them), queries advance the key
        chain without re-executing, and JR_FLUSH markers re-run the flush
        trigger so auto-compactions land at their original stream positions.
        The result is bit-identical to the uninterrupted run over the same
        acknowledged prefix. ``params``/``strategy``/``seed`` must match the
        crashed session's (the checkpoint and journal fingerprints enforce
        the first two).

        The replayed records stay in the journal (truncation happens only at
        the next checkpoint ``save``), so a crash *during or after* recovery
        recovers again from the same disk state.
        """
        from repro.checkpoint import journal as journal_mod

        sess = cls(
            params, strategy=strategy, seed=seed,
            checkpoint_dir=checkpoint_dir, checkpoint_keep=checkpoint_keep,
            unified_dispatch=unified_dispatch, journal=False,
            journal_fsync=journal_fsync, flush_retries=flush_retries,
            flush_backoff_s=flush_backoff_s,
        )
        sess.recovering = True
        t0 = time.perf_counter()
        records, _, dropped = journal_mod.scan_file(
            Path(sess._ckpt.dir) / "journal.bin")
        step = None
        try:
            step = sess.restore(None)  # journal not attached: no reset
        except FileNotFoundError:
            pass  # crashed before the first checkpoint: replay from empty
        want = params_fingerprint(sess.params, sess.strategy)
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
                    # sequence gap: the newest checkpoint was corrupt AND the
                    # journal had already been truncated past the fallback
                    # step — the ops in between are genuinely gone, so this
                    # suffix belongs to a timeline the session can no longer
                    # reach. Stop replaying, surface the loss, come up on
                    # the longest recoverable prefix (a stale index beats
                    # refusing to serve).
                    n_unreplayable = len(records) - idx
                    break
            if code == OP_QUERY:
                sess._op_key()  # results are gone; only the chain advances
            elif code == OP_INSERT:
                sess.insert(rec.payload, chunk=rec.aux.get("chunk"))
            elif code == OP_DELETE:
                sess.delete(rec.ids, chunk=rec.aux.get("chunk"))
            elif code == ops_mod.JR_FLUSH:
                sess.flush()
            else:
                # maintenance records dispatch through the registry: the
                # op's replay hook re-executes the pass (or dedups it
                # against the restored counters) — adding an op needs no
                # new branch here (DESIGN.md §14)
                mop = maint.by_journal_code(code)
                if mop is None or mop.tier != "session":
                    raise ValueError(f"unknown journal record code {code}")
                if not mop.replay(sess, rec):
                    n_skipped += 1
                    continue
            n_replayed += 1
        sess._sync()  # settle WITHOUT the flush trigger (no extra compaction)
        # a gapped suffix is a dead timeline — it can never replay against
        # this state, so start a fresh journal rather than extend it
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
