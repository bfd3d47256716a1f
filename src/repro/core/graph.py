"""Proximity-graph state — the TPU-native index layout.

The paper's adjacency lists / reverse graph become dense, fixed-degree
``int32`` arrays so every operation is a gather/scatter (no pointer chasing).
Arrays are sized to a *capacity tier*: shapes are static inside any one
compiled program, and the growth engine (DESIGN.md §9) moves the state to a
larger tier with :func:`grow_state` — slot ids never move, new slots arrive
empty (NULL rows, zero vectors, not present), so every graph invariant below
is preserved verbatim by growth.

Invariants maintained by every public op (property-tested in
``tests/test_graph_invariants.py``):

  I1  G' == reverse(G): edge (u→v) is in ``adj[u]`` iff u is in ``radj[v]``.
      Scalar edge insertion REFUSES (drops the edge) when ``radj[v]`` is
      full; the bulk primitives instead keep the first ``d_in`` in-edges by
      deterministic rank and drop the overflow from ``adj`` too — either
      way the invariant never breaks (DESIGN.md §2/§4, bounded in-degree).
  I2  adjacency entries are either -1 or the id of a *present* slot.
  I3  a slot is ``alive`` ⇒ it is ``present``; MASK-deleted slots are
      present but not alive (traversable, never reported).
  I4  no self-edges, no duplicate entries within a row.
  I5  compressed-scoring sync (DESIGN.md §10): for every *present* slot,
      ``(codes[i], scales[i]) == quantize_rows(vectors[i])`` exactly; for
      every non-present slot the codes row and scale are zero. Every mutator
      that writes ``vectors`` quantizes in the same transaction; every path
      that frees a slot scrubs its codes (``vectors`` of freed slots keep
      stale bytes — codes do not, so the invariant is checkable).
  I6  insertion stamps: every *present* slot carries the monotone stamp it
      was assigned at insertion (``0 ≤ stamps[i] < clock``); every
      non-present slot has ``stamps[i] == -1``. Stamps order slots by
      insertion age (merge drain order, OP_REFINE staleness pick) and are
      scrubbed — never recycled — when a slot is freed.
  I7  staleness stamps (DESIGN.md §15): ``touch[i]`` is the ``tclock`` value
      at the last time slot i's *out-row* was rewritten through the batched
      appliers, or -1; every non-present slot has ``touch[i] == -1`` and
      every stamp is ``< tclock``. The vectorized paths maintain touch; the
      scalar reference paths (``insert_one``/``set_out_edges``) leave it at
      -1 — a -1 stamp just means "maximally stale", so OP_REFINE's
      lowest-touch pick remains correct and the B=1 parity suites (which
      compare explicit field lists) are unaffected.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

NULL = -1  # padding id for empty adjacency entries


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "vectors", "sqnorms", "codes", "scales", "adj", "radj", "alive",
        "present", "size", "stamps", "clock", "touch", "tclock",
    ],
    meta_fields=["capacity", "dim", "d_out", "d_in", "metric"],
)
@dataclasses.dataclass(frozen=True)
class GraphState:
    """Pytree holding the full index (one shard of it when distributed)."""

    # --- data ---
    vectors: jax.Array   # f32[capacity, dim]
    sqnorms: jax.Array   # f32[capacity]            ||x||^2 cache (L2 metric)
    codes: jax.Array     # i8[capacity, dim]        per-row int8 vector codes
    scales: jax.Array    # f32[capacity]            per-row dequant scales
    adj: jax.Array       # i32[capacity, d_out]     out-neighbors, NULL padded
    radj: jax.Array      # i32[capacity, d_in]      in-neighbors,  NULL padded
    alive: jax.Array     # bool[capacity]           reportable as a result
    present: jax.Array   # bool[capacity]           traversable (alive | masked)
    size: jax.Array      # i32                      number of alive slots
    stamps: jax.Array    # i32[capacity]            insertion stamp (-1 = empty)
    clock: jax.Array     # i32                      next stamp to hand out
    touch: jax.Array     # i32[capacity]            out-row write stamp (-1 = empty)
    tclock: jax.Array    # i32                      next touch stamp to hand out
    # --- static metadata ---
    capacity: int
    dim: int
    d_out: int
    d_in: int
    metric: str          # "l2" | "ip" | "cos"

    @property
    def masked(self) -> jax.Array:
        """MASK-tombstoned slots: traversable but not reportable."""
        return self.present & ~self.alive


def init_graph(
    capacity: int,
    dim: int,
    *,
    d_out: int = 16,
    d_in: int | None = None,
    metric: str = "l2",
    dtype: Any = jnp.float32,
) -> GraphState:
    if metric not in ("l2", "ip", "cos"):
        raise ValueError(f"unknown metric {metric!r}")
    d_in = 2 * d_out if d_in is None else d_in
    return GraphState(
        vectors=jnp.zeros((capacity, dim), dtype),
        sqnorms=jnp.zeros((capacity,), jnp.float32),
        codes=jnp.zeros((capacity, dim), jnp.int8),
        scales=jnp.zeros((capacity,), jnp.float32),
        adj=jnp.full((capacity, d_out), NULL, jnp.int32),
        radj=jnp.full((capacity, d_in), NULL, jnp.int32),
        alive=jnp.zeros((capacity,), bool),
        present=jnp.zeros((capacity,), bool),
        size=jnp.asarray(0, jnp.int32),
        stamps=jnp.full((capacity,), -1, jnp.int32),
        clock=jnp.asarray(0, jnp.int32),
        touch=jnp.full((capacity,), -1, jnp.int32),
        tclock=jnp.asarray(0, jnp.int32),
        capacity=capacity,
        dim=dim,
        d_out=d_out,
        d_in=d_in,
        metric=metric,
    )


# ---------------------------------------------------------------------------
# Capacity growth (DESIGN.md §9) — the shape-family move between tiers.
# ---------------------------------------------------------------------------

def grow_state(state: GraphState, new_capacity: int, *, axis: int = 0) -> GraphState:
    """Pad every per-slot array of ``state`` to ``new_capacity`` slots.

    Existing slots keep their ids and contents byte-exactly; the new slots
    are empty — zero vectors/sqnorms, NULL adjacency rows, not alive, not
    present — so they are immediately visible to the allocator as free and
    invisible to every traversal (I1–I4 hold trivially on exit). ``size`` is
    unchanged. The returned state lives in a new shape family: the next
    dispatch through any shape-specialized jitted step (``apply_ops_step``,
    ``delete_batch``, ...) compiles once for the new tier.

    ``axis`` is the capacity axis — 0 for a local state, 1 for the stacked
    per-shard layout of ``ShardedSession`` (every shard grows in lockstep so
    the stack stays one shape family).
    """
    cap = state.capacity
    if new_capacity < cap:
        raise ValueError(
            f"grow_state cannot shrink: {cap} -> {new_capacity}")
    if new_capacity == cap:
        return state
    extra = new_capacity - cap

    def pad(x: jax.Array, fill) -> jax.Array:
        pads = [(0, 0)] * x.ndim
        pads[axis] = (0, extra)
        return jnp.pad(x, pads, constant_values=fill)

    return dataclasses.replace(
        state,
        vectors=pad(state.vectors, 0),
        sqnorms=pad(state.sqnorms, 0.0),
        codes=pad(state.codes, 0),
        scales=pad(state.scales, 0.0),
        adj=pad(state.adj, NULL),
        radj=pad(state.radj, NULL),
        alive=pad(state.alive, False),
        present=pad(state.present, False),
        stamps=pad(state.stamps, -1),
        touch=pad(state.touch, -1),
        capacity=new_capacity,
    )


def next_capacity_tier(
    capacity: int,
    needed: int,
    growth_factor: float,
    max_capacity: int | None,
) -> int:
    """Smallest geometric tier ≥ ``needed`` slots, clipped to ``max_capacity``.

    Tiers are ``capacity · growth_factor^k`` (ceil), so a stream that grows
    monotonically recompiles at most ``ceil(log_factor(final/initial))``
    times regardless of how the demand arrives. Returns the current capacity
    unchanged when it already covers ``needed`` or growth is capped out.
    """
    new = capacity
    while new < needed and (max_capacity is None or new < max_capacity):
        new = max(math.ceil(new * growth_factor), new + 1)
    if max_capacity is not None:
        new = min(new, max_capacity)
    return max(new, capacity)


# ---------------------------------------------------------------------------
# Row-level edge surgery. All helpers are jit-safe (static shapes) and keep
# rows compact-from-the-left is NOT required: rows may have NULL holes; every
# consumer masks on ``entry != NULL``.
# ---------------------------------------------------------------------------

def row_insert(row: jax.Array, value: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Insert ``value`` into the first NULL hole of ``row``.

    Returns (new_row, inserted?). Refuses (inserted=False) when the row is
    full or the value is already there (keeps I1/I4 cheaply).
    """
    already = jnp.any(row == value)
    holes = row == NULL
    has_hole = jnp.any(holes)
    pos = jnp.argmax(holes)  # first hole
    do = has_hole & ~already
    new_row = jnp.where(
        do & (jnp.arange(row.shape[0]) == pos), value, row
    )
    return new_row, do | already  # "already present" counts as success


def row_remove(row: jax.Array, value: jax.Array) -> jax.Array:
    """Remove every occurrence of ``value`` from ``row`` (→ NULL)."""
    return jnp.where(row == value, NULL, row)


def add_edge(state: GraphState, u: jax.Array, v: jax.Array) -> GraphState:
    """Add directed edge u→v, updating radj; refuses if either row is full.

    The refusal is atomic: the edge lands in both adj[u] and radj[v] or in
    neither (invariant I1).
    """
    new_adj_row, ok_a = row_insert(state.adj[u], v)
    new_radj_row, ok_r = row_insert(state.radj[v], u)
    ok = ok_a & ok_r & (u != v) & (u != NULL) & (v != NULL)
    adj = state.adj.at[u].set(jnp.where(ok, new_adj_row, state.adj[u]))
    radj = state.radj.at[v].set(jnp.where(ok, new_radj_row, state.radj[v]))
    return dataclasses.replace(state, adj=adj, radj=radj)


def remove_edge(state: GraphState, u: jax.Array, v: jax.Array) -> GraphState:
    adj = state.adj.at[u].set(row_remove(state.adj[u], v))
    radj = state.radj.at[v].set(row_remove(state.radj[v], u))
    return dataclasses.replace(state, adj=adj, radj=radj)


def set_out_edges(state: GraphState, u: jax.Array, targets: jax.Array) -> GraphState:
    """Replace the full out-neighborhood of ``u`` with ``targets``.

    ``targets`` is i32[d_out], NULL padded. Reverse rows of both the old and
    new targets are fixed up. Edges whose reverse row is full are dropped
    (refused) to keep I1. Implemented as remove-all + loop of add_edge over
    the (small, static) degree — executes inside jit.
    """
    d_out = state.d_out

    def rm_one(i, st):
        old = st.adj[u, i]
        return jax.lax.cond(
            old != NULL, lambda s: remove_edge(s, u, old), lambda s: s, st
        )

    state = jax.lax.fori_loop(0, d_out, rm_one, state)

    def add_one(i, st):
        tgt = targets[i]
        return jax.lax.cond(
            tgt != NULL, lambda s: add_edge(s, u, tgt), lambda s: s, st
        )

    return jax.lax.fori_loop(0, min(d_out, targets.shape[0]), add_one, state)


# ---------------------------------------------------------------------------
# Bulk edge primitives (DESIGN.md §4) — the scatter-based application path of
# the vectorized update engine. Instead of per-edge add/remove chains, callers
# compute whole out-rows, scatter them into ``adj`` in one shot, and have the
# affected reverse rows recomputed from ``adj`` in a single sort/segment pass.
# ---------------------------------------------------------------------------

def rebuild_radj_rows(state: GraphState, touched: jax.Array) -> GraphState:
    """Recompute ``radj[v]`` from ``adj`` for every v in the ``touched`` mask.

    ``touched``: bool[capacity]. One vectorized pass: flatten ``adj`` into
    (src, dst) edge lists, rank each in-edge within its destination by a
    stable sort on dst (rank order == flat ``adj`` order == (src id, slot)
    lexicographic), and scatter the first ``d_in`` per destination into the
    cleared touched rows.

    Bounded in-degree (DESIGN.md §2) becomes deterministic
    **truncation-by-rank** here: in-edges ranked ≥ ``d_in`` are dropped from
    ``adj`` as well, so I1 holds exactly. This replaces the scalar path's
    refuse-the-newcomer rule — under in-degree pressure the two paths keep
    different (equally sized) edge subsets, which the parity suite bounds.

    Untouched rows are byte-identical on exit. Scatter-free: rows are
    *gathered* out of the sorted edge list (XLA scatter serializes per
    update on CPU; segment gathers stay vectorized).
    """
    cap, d_out, d_in = state.capacity, state.d_out, state.d_in
    src = jnp.broadcast_to(
        jnp.arange(cap, dtype=jnp.int32)[:, None], (cap, d_out)
    ).reshape(-1)
    dst = state.adj.reshape(-1)
    E = dst.shape[0]
    ok = (dst != NULL) & touched[jnp.maximum(dst, 0)]
    # stable sort on dst (invalid lanes sink past every real id): the
    # in-edges of v occupy the contiguous segment [start[v], end[v]), in
    # (source id, slot) lexicographic order — the truncation rank order
    key_dst = jnp.where(ok, dst, cap)
    order = jnp.argsort(key_dst, stable=True)
    sorted_key = key_dst[order]
    sorted_src = src[order]
    vids = jnp.arange(cap, dtype=key_dst.dtype)
    start = jnp.searchsorted(sorted_key, vids, side="left")
    end = jnp.searchsorted(sorted_key, vids, side="right")
    # gather the first d_in in-edges of every touched row
    idx = start[:, None] + jnp.arange(d_in)[None, :]
    take = (idx < end[:, None]) & touched[:, None]
    vals = jnp.where(take, sorted_src[jnp.clip(idx, 0, E - 1)], NULL)
    radj = jnp.where(touched[:, None], vals, state.radj)
    # drop forward edges whose reverse overflowed (keeps I1 exact):
    # per-lane rank = sorted position − segment start
    inv = jnp.argsort(order)  # lane → sorted position
    rank = inv - start[jnp.clip(key_dst, 0, cap - 1)]
    drop = ok & (rank >= d_in)
    adj = jnp.where(drop, NULL, dst).reshape(cap, d_out)
    return dataclasses.replace(state, adj=adj, radj=radj)


def _segment_rank(sorted_key: jax.Array) -> jax.Array:
    """Position of each lane of a sorted key array within its run of equal
    keys (0 for the first lane of every run)."""
    n = sorted_key.shape[0]
    lane = jnp.arange(n, dtype=jnp.int32)
    new_run = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_key[1:] != sorted_key[:-1]])
    return lane - jax.lax.cummax(jnp.where(new_run, lane, 0))


def apply_row_updates(
    state: GraphState,
    us: jax.Array,        # i32[R]        rows to replace (unique where valid)
    new_rows: jax.Array,  # i32[R, d_out] sanitized new out-rows, NULL padded
    valid: jax.Array,     # bool[R]
) -> GraphState:
    """Incremental scatter-based edge application (the hot-path applier).

    Writes the forward rows with one OOB-dropping scatter and *patches*
    ``radj`` instead of recomputing it. All work and temporaries scale with
    the R·d_out edge lanes of the batch, never with capacity:

      · removals — for each dropped edge u→v (in u's old row, not its new
        one), I1 puts u in ``radj[v]``: the row is gathered, u found, and
        NULL scattered at that entry;
      · additions — edges in a new row but not its old one are ranked per
        destination by one stable sort over the R·d_out lanes (rank order
        = flat (row, slot) order), and the rank-h addition fills the h-th
        NULL hole (in entry order) of its destination's post-removal row.

    Bounded in-degree: existing in-edges keep priority; additions ranked
    past the holes are **refused** (the forward entry is dropped too, so
    I1 holds exactly — same semantics family as scalar ``add_edge``
    refusal, minus the sequential arrival order).

    ``new_rows`` must already be sanitized (no self edges / dups /
    non-present targets) — use ``set_out_edges_batch`` for the checked
    wrapper. Valid ``us`` must be unique, and the incoming state must
    satisfy I1 (every public op leaves it so).
    """
    cap, d_out, d_in = state.capacity, state.d_out, state.d_in
    R = us.shape[0]
    valid = valid & (us != NULL)
    su = jnp.where(valid, us, 0)
    wsu = jnp.where(valid, us, cap)  # OOB parks invalid lanes (mode="drop")
    old_rows = jnp.where(valid[:, None], state.adj[su], NULL)
    new_rows = jnp.where(valid[:, None], new_rows, NULL)
    src = jnp.broadcast_to(su[:, None], (R, d_out)).reshape(-1)  # [E]
    entry = jnp.broadcast_to(
        jnp.arange(d_in, dtype=jnp.int32), (R * d_out, d_in))

    # ---- removals: NULL u's entry in radj[v] for every dropped edge u→v
    gone = (old_rows != NULL) & ~jnp.any(
        old_rows[:, :, None] == new_rows[:, None, :], axis=2)
    gone_v = jnp.where(gone, old_rows, cap).reshape(-1)
    hit = state.radj[jnp.minimum(gone_v, cap - 1)] == src[:, None]
    radj = state.radj.at[
        jnp.where(hit & (gone_v < cap)[:, None], gone_v[:, None], cap), entry
    ].set(NULL, mode="drop")

    # ---- additions: rank each added edge among the additions to its
    # destination — one stable sort over the R·d_out lanes
    add = (new_rows != NULL) & ~jnp.any(
        new_rows[:, :, None] == old_rows[:, None, :], axis=2)
    add = add.reshape(-1)
    dst = new_rows.reshape(-1)
    key = jnp.where(add, dst, cap)
    order = jnp.argsort(key, stable=True)
    rank = jnp.zeros_like(order).at[order].set(_segment_rank(key[order]))
    # admit additions into the holes left after removals; refuse the rest
    isnull = radj[jnp.where(add, dst, 0)] == NULL      # [E, d_in]
    admit = add & (rank < jnp.sum(isnull, axis=1))
    final_rows = jnp.where((add & ~admit).reshape(R, d_out), NULL, new_rows)
    adj = state.adj.at[wsu].set(final_rows, mode="drop")
    # the rank-h addition takes the h-th hole of its destination row
    hole_rank = jnp.cumsum(isnull.astype(jnp.int32), axis=1) - 1
    pos = jnp.argmax(isnull & (hole_rank == rank[:, None]), axis=1)
    radj = radj.at[jnp.where(admit, dst, cap), pos].set(src, mode="drop")
    # staleness stamps (I7): every rewritten out-row takes the current tclock
    # (OP_REFINE picks the lowest-touch alive slots); one bump per call keeps
    # within-batch ties broken by slot id, deterministically
    touch = state.touch.at[wsu].set(state.tclock, mode="drop")
    return dataclasses.replace(
        state, adj=adj, radj=radj, touch=touch, tclock=state.tclock + 1
    )


def set_out_edges_batch(
    state: GraphState,
    us: jax.Array,        # i32[R]        rows to replace (unique where valid)
    targets: jax.Array,   # i32[R, d_out] new out-rows, NULL padded
    valid: jax.Array,     # bool[R]       rows to actually apply
) -> GraphState:
    """Replace the out-neighborhoods of all ``us`` rows in one scatter.

    The batched twin of ``set_out_edges``: rows are sanitized (self edges,
    in-row duplicates, non-present targets → NULL) and applied through
    ``apply_row_updates`` (one forward scatter + incremental reverse-row
    patch). Valid rows must be unique — duplicate row ids in one call make
    the scatter order undefined.
    """
    valid = valid & (us != NULL)
    su = jnp.where(valid, us, 0)
    tg = targets[:, : state.d_out]
    if tg.shape[1] < state.d_out:
        pad = jnp.full((tg.shape[0], state.d_out - tg.shape[1]), NULL, jnp.int32)
        tg = jnp.concatenate([tg, pad], axis=1)
    tv = (tg != NULL) & valid[:, None]
    tv = tv & state.present[jnp.where(tv, tg, 0)]
    tg = jnp.where(tv & (tg != su[:, None]), tg, NULL)
    # in-row dedup (keep first occurrence)
    eq = tg[:, :, None] == tg[:, None, :]
    eq = eq & (tg != NULL)[:, :, None]
    first = jnp.argmax(eq, axis=2) == jnp.arange(tg.shape[1])[None, :]
    tg = jnp.where(first, tg, NULL)
    return apply_row_updates(state, us, tg, valid)


def pack_rows(rows: jax.Array) -> jax.Array:
    """Compact non-NULL entries of each row to the left, preserving order."""
    order = jnp.argsort(rows == NULL, axis=1, stable=True)
    return jnp.take_along_axis(rows, order, axis=1)


def group_by_destination(
    src: jax.Array,       # i32[E]  edge sources
    dst: jax.Array,       # i32[E]  edge destinations
    valid: jax.Array,     # bool[E]
    capacity: int,
    max_per_row: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Group an edge list by destination into a compact frame.

    Returns (dests i32[F], rows i32[F, max_per_row], ok bool[F]) with
    F = min(E, capacity): frame slot t holds the t-th smallest destination
    that receives a valid edge and its sources in input order, NULL padded.
    Edges ranked ≥ ``max_per_row`` within their destination are dropped;
    slots past the number of distinct destinations are not ``ok`` (dest
    NULL). The grouping engine behind back-link application and LOCAL
    splice batching — one sort over the E lanes, nothing sized by capacity.
    """
    E = dst.shape[0]
    F = min(E, capacity)
    key = jnp.where(valid, dst, capacity)
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    sorted_src = src[order]
    real = sorted_key < capacity
    rank = _segment_rank(sorted_key)
    seg = jnp.cumsum((real & (rank == 0)).astype(jnp.int32)) - 1
    dests = jnp.full((F,), NULL, jnp.int32).at[
        jnp.where(real & (rank == 0), seg, F)].set(sorted_key, mode="drop")
    rows = jnp.full((F, max_per_row), NULL, jnp.int32).at[
        jnp.where(real & (rank < max_per_row), seg, F),
        jnp.minimum(rank, max_per_row - 1),
    ].set(sorted_src, mode="drop")
    return dests, rows, dests != NULL


# ---------------------------------------------------------------------------
# Whole-graph vectorized edge scrubbing — used by batched deletes. O(cap·deg)
# but a single fused gather/where, no per-edge loop.
# ---------------------------------------------------------------------------

def scrub_edges_to(state: GraphState, dead: jax.Array) -> GraphState:
    """NULL-out every adjacency entry pointing into the ``dead`` mask.

    ``dead``: bool[capacity]. Clears both directions plus the dead rows
    themselves, preserving I1 globally.
    """
    safe_adj = jnp.where(state.adj == NULL, 0, state.adj)
    adj = jnp.where((state.adj != NULL) & dead[safe_adj], NULL, state.adj)
    safe_radj = jnp.where(state.radj == NULL, 0, state.radj)
    radj = jnp.where((state.radj != NULL) & dead[safe_radj], NULL, state.radj)
    # dead rows lose all their edges too
    adj = jnp.where(dead[:, None], NULL, adj)
    radj = jnp.where(dead[:, None], NULL, radj)
    return dataclasses.replace(state, adj=adj, radj=radj)


def free_slots(state: GraphState, ids: jax.Array, valid: jax.Array) -> GraphState:
    """Mark slots fully removed (not present, not alive).

    ``.min`` combine keeps duplicate-index scatters exact: invalid lanes park
    at index 0 writing True, which can never flip a slot.
    """
    safe = jnp.where(valid, ids, 0)
    n_freed = jnp.sum(valid & state.alive[safe])
    alive = state.alive.at[safe].min(~valid)
    present = state.present.at[safe].min(~valid)
    # freed slots scrub their compressed codes (invariant I5); the boolean
    # mask + where is collision-free under duplicate/parked lanes
    freed = jnp.zeros((state.capacity,), bool).at[safe].max(valid)
    return dataclasses.replace(
        state, alive=alive, present=present,
        codes=jnp.where(freed[:, None], 0, state.codes),
        scales=jnp.where(freed, 0.0, state.scales),
        stamps=jnp.where(freed, -1, state.stamps),
        touch=jnp.where(freed, -1, state.touch),
        size=state.size - n_freed.astype(jnp.int32),
    )


def mask_to_slots(mask: jax.Array, n: int) -> tuple[jax.Array, jax.Array]:
    """Compact the lowest ``n`` set positions of ``mask`` into a fixed frame.

    Returns (ids i32[n] NULL padded, valid bool[n]): the ≤ n lowest True
    indices of ``mask`` in ascending order, valid lanes first. The
    fixed-shape bridge from a data-dependent slot set (e.g. the tombstone
    mask consumed by a CONSOLIDATE micro-batch) to a batched op frame —
    jit-safe, one ``top_k`` over negated ids.
    """
    cap = mask.shape[0]
    take = min(n, cap)
    sentinel = jnp.int32(-cap - 1)
    score = jnp.where(mask, -jnp.arange(cap, dtype=jnp.int32), sentinel)
    vals, ids = jax.lax.top_k(score, take)  # largest score = lowest set id
    valid = vals > sentinel
    ids = jnp.where(valid, ids, NULL).astype(jnp.int32)
    if n > cap:
        ids = jnp.concatenate([ids, jnp.full((n - cap,), NULL, jnp.int32)])
        valid = jnp.concatenate([valid, jnp.zeros((n - cap,), bool)])
    return ids, valid


def next_free_slot(state: GraphState) -> jax.Array:
    """First non-present slot (freelist head). capacity if full."""
    return jnp.argmin(state.present)  # False < True; full graph → 0 (caller checks)


def graph_stats(state: GraphState) -> dict[str, jax.Array]:
    out_deg = jnp.sum(state.adj != NULL, axis=1)
    in_deg = jnp.sum(state.radj != NULL, axis=1)
    p = state.present
    return {
        "n_alive": jnp.sum(state.alive),
        "n_present": jnp.sum(p),
        "n_masked": jnp.sum(state.masked),
        "avg_out_degree": jnp.sum(jnp.where(p, out_deg, 0)) / jnp.maximum(jnp.sum(p), 1),
        "avg_in_degree": jnp.sum(jnp.where(p, in_deg, 0)) / jnp.maximum(jnp.sum(p), 1),
        "max_in_degree": jnp.max(jnp.where(p, in_deg, 0)),
    }
