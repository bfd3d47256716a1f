"""Sharded online ANN index — the paper's system at 256–512+ chips.

Layout (DESIGN.md §5): shard-per-device subgraphs. Each device on the
flattened ('data','model') axes owns ``cap_local`` slots and an independent
proximity graph over them; there are NO cross-shard edges, so the paper's
delete/repair algorithms run unmodified (and fully parallel) inside every
shard. The 'pod' axis holds index replicas and shards the query stream
(fault-tolerance + QPS scaling).

  query : queries replicated within a pod → every shard beam-searches its
          subgraph → all_gather(k per shard) → top-k merge. Collective bytes
          per query = P·k·8 — independent of index size.
  insert: routed by hash → rows grouped per owning shard on the host
          (``shard_blocks``) → every shard runs the vectorized insert
          pipeline (DESIGN.md §4) on its own block only: ONE batched search
          + scatter edge application, inline inside shard_map (no nested
          jit).
  delete: global id = shard·cap_local + local id → owner-masked
          delete_batch with the configured strategy (GLOBAL repair searches
          are shard-local by construction).

Straggler/fault story: the merge consumes per-shard partial top-k, so a lost
shard degrades recall by ~1/P instead of failing the query; the checkpoint
manager (checkpoint/manager.py) restores per-shard states independently and
supports re-sharding to a different device count.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import consolidate as consolidate_mod
from repro.core import delete as delete_mod
from repro.core import insert as insert_mod
from repro.core import ops as ops_mod
from repro.core import search as search_mod
from repro.core.graph import (
    NULL,
    GraphState,
    grow_state,
    init_graph,
    mask_to_slots,
    next_capacity_tier,
)
from repro.core.params import IndexParams
from repro.core.trace import span
from repro.testing import faults


@dataclasses.dataclass(frozen=True)
class DistParams:
    """Distribution config for the sharded index."""
    index: IndexParams           # per-shard params (capacity = cap_local)
    shard_axes: tuple[str, ...] = ("data", "model")
    pod_axis: str | None = None  # set for multi-pod meshes
    hierarchical_merge: bool = True  # §Perf C: two-stage top-k fan-in —
                                     # merge within 'model' first, then
                                     # across 'data': AG bytes drop from
                                     # P·B·k to (m+n)·B·k per device
    vec_dtype: str = "float32"       # "bfloat16" halves gather traffic

    @property
    def axes(self) -> tuple[str, ...]:
        return self.shard_axes

    def gid_stride(self) -> int:
        """Global-id stride: ``gid = shard · stride + local id``.

        Pinned to ``maintenance.max_capacity`` when capacity growth is armed
        (DESIGN.md §9), so gids handed out at one tier stay valid after
        every shard grows to a larger one; with growth disarmed it equals
        the (then-fixed) per-shard capacity — the legacy encoding.
        """
        mp = self.index.maintenance
        return (mp.max_capacity if mp.max_capacity is not None
                else self.index.capacity)


def init_sharded_state(dp: DistParams, mesh) -> GraphState:
    """Init of the stacked per-shard states [P, cap_local, ...], built in
    place: each device materializes only its own shard."""
    n_shards = 1
    for a in dp.shard_axes:
        n_shards *= mesh.shape[a]
    shardings = jax.tree.map(
        lambda _: NamedSharding(mesh, P(dp.shard_axes)), init_specs_tree(dp))

    def build() -> GraphState:
        one = init_graph(
            dp.index.capacity, dp.index.dim, d_out=dp.index.d_out,
            d_in=dp.index.eff_d_in, metric=dp.index.metric,
            dtype=jnp.dtype(dp.vec_dtype),
        )
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_shards,) + x.shape), one)

    return jax.jit(build, out_shardings=shardings)()


def _local(state_stacked: GraphState) -> GraphState:
    """Drop the (length-1 after shard_map) shard axis."""
    return jax.tree.map(lambda x: x[0], state_stacked)


def _restack(state: GraphState) -> GraphState:
    return jax.tree.map(lambda x: x[None], state)


def _shard_index(axes) -> jax.Array:
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def topk_union(flat_scores: jax.Array, flat_ids: jax.Array,
               k: int) -> tuple[jax.Array, jax.Array]:
    """Merge concatenated partial top-k lists into one top-k per row.

    ``flat_scores``/``flat_ids``: [B, m·k] candidates from m sources
    (higher score = better; invalid lanes carry -inf / NULL). The fan-in
    tail shared by the sharded query merge below and the two-tier fan-out
    union (``core/tiered.py``).
    """
    top_s, idx = jax.lax.top_k(flat_scores, k)
    return top_s, jnp.take_along_axis(flat_ids, idx, axis=1)


def make_query_step(dp: DistParams, mesh):
    """Build the jitted distributed query step.

    queries f32[B, dim] (replicated intra-pod / sharded over pod) →
    (gids i32[B, k], scores f32[B, k]).
    """
    sp = dp.index.search
    axes = dp.axes
    state_spec = jax.tree.map(lambda _: P(axes), init_specs_tree(dp))
    q_spec = P(dp.pod_axis) if dp.pod_axis else P()

    def _merge(scores, ids, axis, k):
        all_s = jax.lax.all_gather(scores, axis)            # [m, B, k]
        all_i = jax.lax.all_gather(ids, axis)
        m, B, _ = all_s.shape
        flat_s = jnp.transpose(all_s, (1, 0, 2)).reshape(B, -1)
        flat_i = jnp.transpose(all_i, (1, 0, 2)).reshape(B, -1)
        return topk_union(flat_s, flat_i, k)

    stride = dp.gid_stride()

    def _step(state_stacked: GraphState, queries, key):
        state = _local(state_stacked)
        shard = _shard_index(axes)
        key = jax.random.fold_in(key, shard)
        # per-shard fan-out runs the batched beam engine inline (no nested
        # jit inside shard_map): every shard beam-searches its subgraph with
        # one engine call, then the partial top-k's cross the mesh
        starts = search_mod.batch_entry_points(
            state, key, queries.shape[0], sp.num_starts
        )
        res = search_mod.beam_search(state, queries, starts, sp)
        gids = jnp.where(
            res.ids != NULL, res.ids + shard * stride, NULL
        )
        k = sp.pool_size
        if dp.hierarchical_merge and len(axes) > 1:
            # two-stage fan-in (§Perf C): intra-'model' merge shrinks the
            # candidate set 16× before it crosses the 'data' axis
            s, i = _merge(res.scores, gids, axes[-1], k)
            top_s, top_i = _merge(s, i, axes[:-1], k)
        else:
            top_s, top_i = _merge(res.scores, gids, axes, k)
        return top_i, top_s

    smapped = jax.shard_map(
        _step, mesh=mesh,
        in_specs=(state_spec, q_spec, P()),
        out_specs=(q_spec, q_spec),
        check_vma=False,
    )
    return jax.jit(smapped)


def shard_blocks(vecs, route, n_shards: int):
    """Group the rows of a routed insert by owning shard (``route % n``).

    Returns (blocks f32[n, m, dim], rows i32[n, m], valid bool[n, m],
    pos i64[B]): row i lands at flat position ``pos[i]`` of the ``[n·m]``
    frame, in arrival order within its shard, and ``rows`` holds each
    lane's index in the batch. ``m`` is the largest share rounded up to a
    power of two, so skewed routes recompile the insert step a bounded
    number of times.
    """
    vecs = np.asarray(vecs, np.float32)
    owner = np.asarray(route, np.int64) % n_shards
    n = owner.shape[0]
    counts = np.bincount(owner, minlength=n_shards)
    m = 1 << max(int(counts.max(initial=0)) - 1, 0).bit_length()
    order = np.argsort(owner, kind="stable")
    rank = np.empty_like(owner)
    rank[order] = np.arange(n) - (np.cumsum(counts) - counts)[owner[order]]
    pos = owner * m + rank
    blocks = np.zeros((n_shards * m, vecs.shape[1]), np.float32)
    blocks[pos] = vecs
    rows = np.zeros((n_shards * m,), np.int32)
    rows[pos] = np.arange(n)
    valid = np.zeros((n_shards * m,), bool)
    valid[pos] = True
    return (blocks.reshape(n_shards, m, -1), rows.reshape(n_shards, m),
            valid.reshape(n_shards, m), pos)


def make_insert_step(dp: DistParams, mesh):
    """Routed batch insert over per-shard row blocks (``shard_blocks``):
    (blocks, rows, valid) → gids i32[P, m] on every device. Each shard runs
    the insert pipeline on its own rows only."""
    axes = dp.axes
    state_spec = jax.tree.map(lambda _: P(axes), init_specs_tree(dp))
    stride = dp.gid_stride()

    def _step(state_stacked, blocks, rows, valid, key):
        state = _local(state_stacked)
        shard = _shard_index(axes)
        key = jax.random.fold_in(key, shard)
        # lane j folds key_offset + j: offset it so every row folds its own
        # index in the routed batch, whatever its place in the shard block
        offset = rows[0] - jnp.arange(rows.shape[1], dtype=jnp.int32)
        # traceable impl, not the jitted wrapper: runs inline in shard_map
        state, ids = insert_mod.insert_batch_impl(
            state, blocks[0], valid[0], key, dp.index, key_offset=offset
        )
        gids = jnp.where(ids != NULL, ids + shard * stride, NULL)
        # every device holds every shard's gids: [P, m], shard-major
        return _restack(state), jax.lax.all_gather(gids, axes)

    smapped = jax.shard_map(
        _step, mesh=mesh,
        in_specs=(state_spec, P(axes), P(axes), P(axes), P()),
        out_specs=(state_spec, P()),
        check_vma=False,
    )
    return jax.jit(smapped, donate_argnums=(0,))


def make_delete_step(dp: DistParams, mesh, strategy: str):
    """Owner-masked distributed delete over global ids i32[B]."""
    axes = dp.axes
    state_spec = jax.tree.map(lambda _: P(axes), init_specs_tree(dp))

    stride = dp.gid_stride()

    def _step(state_stacked, gids, key):
        state = _local(state_stacked)
        shard = _shard_index(axes)
        owner = gids // stride
        lids = (gids % stride).astype(jnp.int32)
        # with growth armed the stride exceeds the live tier — local ids are
        # only valid below the *current* per-shard capacity
        valid = ((gids != NULL) & (owner == shard)
                 & (lids < dp.index.capacity))
        key = jax.random.fold_in(key, shard)
        state = delete_mod.delete_batch(
            state, lids, valid, key, strategy, dp.index
        )
        return _restack(state)

    smapped = jax.shard_map(
        _step, mesh=mesh,
        in_specs=(state_spec, P(), P()),
        out_specs=state_spec,
        check_vma=False,
    )
    return jax.jit(smapped, donate_argnums=(0,))


def make_consolidate_step(dp: DistParams, mesh):
    """One per-shard compaction pass (DESIGN.md §8), SPMD over the mesh.

    Every shard independently picks its ``consolidate_chunk`` lowest-id
    tombstones and runs the jitted compaction step on its subgraph (repair
    searches are shard-local by construction — there are no cross-shard
    edges). Shards with fewer tombstones than the chunk run a partially
    valid frame; fully drained shards no-op. The host loops passes until
    the most-loaded shard is drained.
    """
    axes = dp.axes
    state_spec = jax.tree.map(lambda _: P(axes), init_specs_tree(dp))
    mp = dp.index.maintenance
    chunk = mp.consolidate_chunk or mp.delete_chunk

    def _step(state_stacked: GraphState, key):
        state = _local(state_stacked)
        shard = _shard_index(axes)
        key = jax.random.fold_in(key, shard)
        tomb, tv = mask_to_slots(state.masked, chunk)
        state, _ = consolidate_mod.consolidate_chunk_impl(
            state, tomb, tv, key, dp.index
        )
        return _restack(state)

    smapped = jax.shard_map(
        _step, mesh=mesh,
        in_specs=(state_spec, P()),
        out_specs=state_spec,
        check_vma=False,
    )
    return jax.jit(smapped, donate_argnums=(0,))


def init_specs_tree(dp: DistParams) -> GraphState:
    """A GraphState-shaped tree of placeholders (for building spec pytrees)."""
    import numpy as np

    cap, dim = dp.index.capacity, dp.index.dim
    z = lambda *s: np.zeros(s, np.int8)  # noqa: E731 — structure only
    return GraphState(
        vectors=z(1, cap, dim), sqnorms=z(1, cap),
        codes=z(1, cap, dim), scales=z(1, cap),
        adj=z(1, cap, dp.index.d_out), radj=z(1, cap, dp.index.eff_d_in),
        alive=z(1, cap), present=z(1, cap), size=z(1),
        stamps=z(1, cap), clock=z(1),
        touch=z(1, cap), tclock=z(1),
        capacity=cap, dim=dim, d_out=dp.index.d_out,
        d_in=dp.index.eff_d_in, metric=dp.index.metric,
    )


# convenience host-level wrappers -------------------------------------------

def distributed_query(state, queries, key, dp, mesh):
    return make_query_step(dp, mesh)(state, queries, key)


def distributed_insert(state, vecs, route, key, dp, mesh):
    n_shards = int(np.prod([mesh.shape[a] for a in dp.shard_axes]))
    blocks, rows, valid, pos = shard_blocks(vecs, route, n_shards)
    state, gids = make_insert_step(dp, mesh)(state, blocks, rows, valid, key)
    return state, gids.reshape(-1)[pos]


def distributed_delete(state, gids, key, dp, mesh, strategy="global"):
    return make_delete_step(dp, mesh, strategy)(state, gids, key)


class ShardedSession:
    """Session-style driver over the sharded index (DESIGN.md §7).

    The distributed twin of :class:`repro.core.session.Session`: owns the
    stacked per-shard ``GraphState`` (donated through the jitted
    insert/delete steps — no stacked-buffer copies per update), builds each
    mesh program once *per capacity tier* (DESIGN.md §9: with
    ``maintenance.max_capacity`` armed, the insert gate grows every shard
    in lockstep and the programs rebuild for the new tier; gids stay valid
    because the encoding is strided by ``max_capacity``), derives op keys
    from one seed chain, and dispatches asynchronously — callers hold the
    returned device arrays and the host only blocks in ``flush()`` / result
    consumption.
    """

    def __init__(self, dp: DistParams, mesh, *, strategy: str | None = None,
                 seed: int = 0):
        from repro.core.session import PhaseTimers

        self.dp = dp
        self.mesh = mesh
        self._n_shards = int(np.prod([mesh.shape[a] for a in dp.shard_axes]))
        self._strategy = (strategy if strategy is not None
                          else dp.index.maintenance.strategy)
        self._build_steps()
        self.state = init_sharded_state(dp, mesh)
        self._base_key = jax.random.PRNGKey(seed)
        self._op_counter = 0
        self._pending: list[jax.Array] = []  # result arrays not yet flushed
        self._insert_results: list[jax.Array] = []  # gid arrays → n_refused
        self._window_t0: float | None = None
        self.timers = PhaseTimers()
        # consolidation bookkeeping — same host-gate scheme as the core
        # Session (DESIGN.md §8): overestimated tombstone count vs
        # underestimated present count, device-exact check only on crossing
        self._consolidate_counter = 0
        self._in_consolidate = False
        self._masked_hint = 0
        self._present_floor = 0
        # growth bookkeeping (DESIGN.md §9): `_free_floor` underestimates
        # the free-slot count of the *most loaded* shard (each insert op
        # subtracts its full batch — the router could land everything on one
        # shard), so the per-shard device-exact check runs only on crossing
        self._free_floor = dp.index.capacity

    def _build_steps(self) -> None:
        """(Re)build the four mesh programs for the current capacity tier."""
        self._query_step = make_query_step(self.dp, self.mesh)
        self._insert_step = make_insert_step(self.dp, self.mesh)
        self._delete_step = make_delete_step(self.dp, self.mesh,
                                             self._strategy)
        self._consolidate_step = make_consolidate_step(self.dp, self.mesh)

    @property
    def strategy(self) -> str:
        return self._strategy

    @strategy.setter
    def strategy(self, value: str) -> None:
        # the delete step bakes the strategy at build time — rebuild it so
        # reassignment behaves like the core Session's per-dispatch strategy
        self._strategy = value
        self._delete_step = make_delete_step(self.dp, self.mesh, value)

    def _op_key(self) -> jax.Array:
        if self._window_t0 is None:
            self._window_t0 = time.perf_counter()
        key = jax.random.fold_in(self._base_key, self._op_counter)
        self._op_counter += 1
        return key

    def query(self, queries) -> tuple[jax.Array, jax.Array]:
        """Fan-out query → (global ids i32[B,k], scores f32[B,k]), async."""
        with span("sharded.query", self.timers, "query_s"):
            gids, scores = self._query_step(
                self.state, jnp.asarray(queries), self._op_key()
            )
            self._pending += [gids, scores]
        self.timers.n_queries += int(jnp.shape(queries)[0])
        self.timers.n_ops += 1
        return gids, scores

    def insert(self, vecs, route) -> jax.Array:
        """Routed insert; returns assigned global ids (async device array).

        The insert boundary is also the growth trigger point (DESIGN.md
        §9): ``_ensure_room`` grows every shard in lockstep (and/or drains
        tombstones) before the batch lands. Rows a full shard still refuses
        come back as NULL gids and are counted into ``timers.n_refused`` at
        the next ``flush``.
        """
        n = int(jnp.shape(vecs)[0])
        if n:  # outside the insert stopwatch — gate work bills to its own
            self._ensure_room(n)  # consolidate_s / grow_s phases
        faults.crash_point("sharded-pre-dispatch")
        with span("sharded.insert", self.timers, "insert_s"):
            blocks, rows, valid, pos = shard_blocks(vecs, route,
                                                    self._n_shards)
            self.state, gids = self._insert_step(
                self.state, blocks, rows, valid, self._op_key())
            gids = gids.reshape(-1)[pos]
            self._free_floor = max(self._free_floor - n, 0)
            self._pending.append(gids)
            self._insert_results.append(gids)
        self.timers.n_inserts += n
        self.timers.n_ops += 1
        faults.crash_point("sharded-post-dispatch")
        return gids

    def delete(self, gids) -> None:
        """Owner-masked distributed delete of global ids (async)."""
        faults.crash_point("sharded-pre-dispatch")
        with span("sharded.delete", self.timers, "delete_s"):
            self.state = self._delete_step(
                self.state, jnp.asarray(gids, jnp.int32), self._op_key()
            )
        self.timers.n_deletes += int(jnp.shape(gids)[0])
        self.timers.n_ops += 1
        if self._strategy == "mask":
            self._masked_hint += int(jnp.shape(gids)[0])
            self._maybe_consolidate()
        else:
            self._present_floor = max(
                self._present_floor - int(jnp.shape(gids)[0]), 0)
        faults.crash_point("sharded-post-dispatch")

    # -- capacity growth (DESIGN.md §9, lockstep over shards) --------------
    def _per_shard_present(self) -> "np.ndarray":
        """Per-shard present counts (synchronizes on the stream)."""
        return np.asarray(jnp.sum(
            self.state.present,
            axis=tuple(range(1, self.state.present.ndim)),
        ))

    def _ensure_room(self, n: int) -> None:
        """Per-shard grow/consolidate gate at the insert boundary.

        Worst-case routing (whole batch on one shard) drives the host hint,
        so the exact per-shard measurement runs only when the most-loaded
        shard could conceivably refuse. Arbitration mirrors the core
        session: drain tombstones inside the compiled tier first, grow all
        shards to the next tier only when compaction cannot make room.
        """
        if self._free_floor >= n:
            return
        mp = self.dp.index.maintenance
        cap = self.dp.index.capacity
        present = self._per_shard_present()
        masked = self._per_shard_masked()
        self._masked_hint = int(masked.sum())
        self._present_floor = int(present.sum())
        free = cap - present
        min_free = int(free.min())
        if min_free < n and masked.sum() > 0 and (
                mp.consolidate_threshold is not None
                or mp.max_capacity is not None):
            self.consolidate(_per_shard=masked)
            min_free = int((free + masked).min())
        if min_free < n and mp.max_capacity is not None:
            target = next_capacity_tier(
                cap, cap - min_free + n, mp.growth_factor, mp.max_capacity)
            if target > cap:
                self.grow(target)
                min_free += target - cap
        self._free_floor = min_free

    def grow(self, new_capacity: int) -> None:
        """Grow every shard to ``new_capacity`` slots in lockstep.

        One `grow_state` pad over the stacked axis-1 layout keeps all
        shards in a single shape family; the four mesh programs are rebuilt
        once for the new tier. Requires ``maintenance.max_capacity`` to be
        set — the global-id stride is pinned to it (``DistParams.
        gid_stride``), which is what keeps gids handed out at smaller tiers
        decodable after the move.
        """
        mp = self.dp.index.maintenance
        if mp.max_capacity is None:
            raise ValueError(
                "ShardedSession growth requires maintenance.max_capacity: "
                "the global-id stride is pinned to it so existing gids "
                "survive the tier move")
        if new_capacity > mp.max_capacity:
            raise ValueError(
                f"new_capacity {new_capacity} exceeds max_capacity "
                f"{mp.max_capacity}")
        if new_capacity == self.dp.index.capacity:
            return
        faults.crash_point("sharded-pre-grow")
        with span("sharded.grow", self.timers, "grow_s",
                  capacity=int(new_capacity)):
            if self._window_t0 is None:
                self._window_t0 = time.perf_counter()
            delta = new_capacity - self.dp.index.capacity
            self.state = grow_state(self.state, new_capacity, axis=1)
            self.dp = dataclasses.replace(
                self.dp,
                index=dataclasses.replace(self.dp.index,
                                          capacity=new_capacity),
            )
            self._build_steps()
            self._free_floor += delta
            self.timers.n_grows += 1
        faults.crash_point("sharded-post-grow")

    # -- consolidation (DESIGN.md §8, per-shard) ---------------------------
    def _per_shard_masked(self) -> "np.ndarray":
        """Per-shard tombstone counts (synchronizes on the stream)."""
        return np.asarray(jnp.sum(
            self.state.masked,
            axis=tuple(range(1, self.state.present.ndim)),
        ))

    def consolidate(self, *, _per_shard=None) -> int:
        """Drain every shard's tombstones through the per-shard compaction
        step. Runs ``ceil(max_shard_tombstones / chunk)`` SPMD passes — the
        least-loaded shards no-op while the stragglers drain. Returns the
        total number of consolidated vertices (synchronizes on the count
        read — the auto-trigger hands over the counts it just measured via
        ``_per_shard`` instead of reducing twice; the passes themselves
        dispatch async)."""
        with span("sharded.consolidate", self.timers, "consolidate_s"):
            per_shard = (self._per_shard_masked() if _per_shard is None
                         else _per_shard)
            total = int(per_shard.sum())
            if total == 0:
                self._masked_hint = 0
                return 0
            if self._window_t0 is None:
                self._window_t0 = time.perf_counter()
            mp = self.dp.index.maintenance
            chunk = mp.consolidate_chunk or mp.delete_chunk
            base = jax.random.fold_in(self._base_key,
                                      ops_mod.CONSOLIDATE_KEY_STREAM)
            for _ in range(-(-int(per_shard.max()) // chunk)):
                # lockstep SPMD passes: a kill between passes leaves some
                # shards drained further than others — exactly the
                # torn-maintenance state the recovery matrix must prove
                # replayable
                faults.crash_point("sharded-consolidate-pass")
                key = jax.random.fold_in(base, self._consolidate_counter)
                self._consolidate_counter += 1
                self.state = self._consolidate_step(self.state, key)
        self.timers.n_consolidations += 1
        self.timers.n_consolidated += total
        self.timers.n_ops += 1
        self._masked_hint = 0
        self._present_floor = max(self._present_floor - total, 0)
        return total

    def _maybe_consolidate(self) -> int:
        from repro.core.session import consolidate_gate_crossed

        thr = self.dp.index.maintenance.consolidate_threshold
        if self._in_consolidate or not consolidate_gate_crossed(
                thr, self._masked_hint, self._present_floor):
            return 0
        # exact check (synchronizes), then fire if the share really crossed
        per_shard = self._per_shard_masked()
        self._masked_hint = int(per_shard.sum())
        self._present_floor = int(jnp.sum(self.state.present))
        if not consolidate_gate_crossed(
                thr, self._masked_hint, self._present_floor):
            return 0
        self._in_consolidate = True
        try:
            return self.consolidate(_per_shard=per_shard)
        finally:
            self._in_consolidate = False

    def flush(self):
        """Block until every dispatched op landed (state AND the result
        arrays handed out since the last flush); settle the timers. Also a
        consolidation trigger point (DESIGN.md §8)."""
        self._maybe_consolidate()
        with span("sharded.flush", self.timers, "flush_s"):
            jax.block_until_ready(self._pending)
            jax.block_until_ready(self.state.adj)
            # refusal accounting (DESIGN.md §9): a full shard answers NULL
            # gids; they are counted here (the arrays are already
            # materialized) so a net-growing stream can never lose inserts
            # silently
            for gids in self._insert_results:
                self.timers.n_refused += int((np.asarray(gids) == NULL).sum())
            self._insert_results.clear()
            self._pending.clear()
        if self._window_t0 is not None:
            self.timers.wall_s += time.perf_counter() - self._window_t0
            self._window_t0 = None
        return self.timers

    def n_alive(self) -> int:
        return int(jnp.sum(self.state.alive))
