"""DELETE-UPDATE-EDGES — the paper's four strategies (Alg 4–6, §5), batched.

All strategies are implemented over a *batch* of deletions (the paper's
workloads delete 10k vectors per step), with each strategy expressed as
vectorized gathers/scatters + (for GLOBAL) a batched repair search that
reuses the exact query path — so on TPU the repair cost is literally
denominated in "equivalent queries", which is the amortization argument of
§6.2.

  PURE   (Alg 4): drop vertex + incident edges (vectorized edge scrub).
  MASK   (§5.2) : tombstone — traversable, not reportable, edges untouched.
  LOCAL  (Alg 5): each in-neighbor u of deleted x splices ONE diverse edge
                  chosen from x's out-neighbors (candidates local to x).
  GLOBAL (Alg 6): each in-neighbor u is re-inserted: full greedy search from
                  u's vector, SELECT-NEIGHBORS over the global candidates,
                  out-edges replaced wholesale.
  RWALK  (Mishra et al. 2025, PAPERS.md): random-walk replacement wiring —
                  each in-neighbor u splices ONE edge found by short walks
                  seeded at a *random subset of x's out-neighborhood* and
                  run through the batched beam engine with u's vector as
                  the guide. Candidate quality sits between LOCAL (x's
                  1-hop neighborhood only) and GLOBAL (full re-search) at a
                  small fixed walk budget (``MaintenanceParams.rwalk_*``).

Each repair strategy is split into a *plan* (which edges to splice/replace —
shared verbatim between the vectorized and reference appliers, so parity
tests compare pure edge-application semantics) and an *applier*. The
vectorized appliers (DESIGN.md §4) group the planned edits per source row
and apply them through the bulk primitive ``set_out_edges_batch`` — one
forward scatter + one incremental reverse patch instead of O(B·d_in)
sequential ``lax.cond`` chains. The sequential appliers are kept
as ``delete_local_reference`` / ``delete_global_reference`` /
``delete_rwalk_reference`` (strategy names
accepted by ``delete_batch`` and ``IPGMIndex``) and pinned against the
vectorized paths by ``tests/test_update_parity.py``. Under in-degree
pressure the two differ only in *which* bounded subset of edges survives
(scalar refusal vs deterministic truncation-by-rank — DESIGN.md §4).

Ordering subtlety shared by LOCAL/GLOBAL: the deleted batch is first marked
dead (``alive=False``) but kept *present* so repair searches can still route
through it (Alg 6 searches on the not-yet-updated graph); edges are scrubbed
and slots freed only after all repairs are computed.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import search, select
from repro.core.graph import (
    NULL,
    GraphState,
    add_edge,
    group_by_destination,
    pack_rows,
    remove_edge,
    scrub_edges_to,
    set_out_edges,
    set_out_edges_batch,
)
from repro.core.params import IndexParams

STRATEGIES = ("pure", "mask", "local", "global", "rwalk")
REFERENCE_STRATEGIES = ("local_reference", "global_reference",
                        "rwalk_reference")


def _dead_mask(state: GraphState, ids: jax.Array, valid: jax.Array) -> jax.Array:
    m = jnp.zeros((state.capacity,), bool)
    return m.at[jnp.where(valid, ids, 0)].max(valid)


def _precheck(state: GraphState, ids: jax.Array, valid: jax.Array) -> jax.Array:
    """Only alive vertices can be deleted."""
    safe = jnp.where(valid, ids, 0)
    return valid & (ids != NULL) & state.alive[safe]


def _mark_dead(state: GraphState, ids: jax.Array, valid: jax.Array) -> GraphState:
    """alive=False (not reportable) while still present (traversable).

    Invalid lanes park at index 0 — the ``.min`` combine makes their write a
    no-op (min(x, True) == x), so duplicate-index scatters stay exact. The
    ``size`` decrement must count *distinct* slots: the same id twice in one
    batch passes ``_precheck`` on both lanes (it checks the pre-batch
    ``alive``), and while the alive scatter is idempotent, subtracting per
    lane would drive ``size`` below the true alive count. First lane wins,
    found by a sort-free scatter-min over lane indices: O(B) work instead of
    the O(B²) all-pairs first-occurrence mask.
    """
    B = ids.shape[0]
    safe = jnp.where(valid, ids, 0)
    lane = jnp.where(valid, jnp.arange(B, dtype=jnp.int32), B)
    winner = jnp.full((state.capacity,), B, jnp.int32).at[safe].min(lane)
    first = valid & (winner[safe] == lane)
    n_dead = jnp.sum(first).astype(jnp.int32)
    alive = state.alive.at[safe].min(~valid)
    return dataclasses.replace(state, alive=alive, size=state.size - n_dead)


def _finalize_removal(
    state: GraphState, ids: jax.Array, valid: jax.Array
) -> GraphState:
    dead = _dead_mask(state, ids, valid)
    state = scrub_edges_to(state, dead)
    # slots already counted out of `size` by _mark_dead; free presence only
    safe = jnp.where(valid, ids, 0)
    present = state.present.at[safe].min(~valid)  # collision-safe scatter
    # freed slots scrub their compressed codes (invariant I5): `vectors`
    # keeps stale bytes but codes/scales return to the empty-slot encoding.
    # The dead boolean mask + where is immune to duplicate/parked lanes.
    return dataclasses.replace(
        state,
        present=present,
        codes=jnp.where(dead[:, None], 0, state.codes),
        scales=jnp.where(dead, 0.0, state.scales),
        stamps=jnp.where(dead, -1, state.stamps),  # invariant I6
        touch=jnp.where(dead, -1, state.touch),    # invariant I7
    )


# ---------------------------------------------------------------------------
# PURE (Alg 4)
# ---------------------------------------------------------------------------

def delete_pure(
    state: GraphState, ids: jax.Array, valid: jax.Array, key, params: IndexParams
) -> GraphState:
    del key
    valid = _precheck(state, ids, valid)
    state = _mark_dead(state, ids, valid)
    return _finalize_removal(state, ids, valid)


# ---------------------------------------------------------------------------
# MASK (§5.2)
# ---------------------------------------------------------------------------

def delete_mask(
    state: GraphState, ids: jax.Array, valid: jax.Array, key, params: IndexParams
) -> GraphState:
    del key
    valid = _precheck(state, ids, valid)
    return _mark_dead(state, ids, valid)  # present stays True: tombstone


# ---------------------------------------------------------------------------
# LOCAL (Alg 5)
# ---------------------------------------------------------------------------

def _local_repair_plan(
    state: GraphState, ids: jax.Array, valid: jax.Array, dead: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Alg 5 lines 3–6 for the whole batch: which edge each surviving
    in-neighbor u of deleted x splices in. Returns (u, x, z, valid) flats of
    length B·d_in. Shared by the vectorized and reference appliers."""
    B, d_in, d_out = ids.shape[0], state.d_in, state.d_out

    safe_ids = jnp.where(valid, ids, 0)
    in_nbrs = state.radj[safe_ids]                     # i32[B, d_in]  the u's
    out_nbrs = state.adj[safe_ids]                     # i32[B, d_out] candidates

    u_flat = in_nbrs.reshape(-1)                       # [B*d_in]
    x_flat = jnp.repeat(safe_ids, d_in)                # deleted vertex per unit
    # each deletion's candidate row, repeated once per its d_in in-neighbor slot
    c_flat = jnp.broadcast_to(
        out_nbrs[:, None, :], (B, d_in, d_out)
    ).reshape(B * d_in, d_out)
    u_valid = (u_flat != NULL) & jnp.repeat(valid, d_in)
    su = jnp.where(u_valid, u_flat, 0)
    # u must itself survive (not in the delete batch)
    u_valid = u_valid & ~dead[su] & state.present[su]

    def pick_one(u, cands, uv):
        """SELECT-NEIGHBORS(u, N(x), 1, N(u) ∪ {u}) — Alg 5 line 6."""
        exclude = jnp.concatenate([state.adj[u], u[None]])
        cv = (cands != NULL) & ~dead[jnp.maximum(cands, 0)]
        cv = cv & state.alive[jnp.maximum(cands, 0)]
        cv = cv & ~jnp.any(cands[:, None] == exclude[None, :], axis=1)
        picked = select.select_neighbors(
            state.vectors[u], cands, state.vectors[jnp.maximum(cands, 0)],
            cv & uv, 1, state.metric,
        )
        return picked[0]

    z_flat = jax.vmap(pick_one)(su, c_flat, u_valid)   # i32[B*d_in]
    return u_flat, x_flat, z_flat, u_valid


def _splice_apply(
    state: GraphState, dead: jax.Array,
    u_flat: jax.Array, z_flat: jax.Array, u_valid: jax.Array,
) -> GraphState:
    """Vectorized one-edge-splice applier shared by LOCAL and RWALK: group
    the planned additions per surviving row u, drop each row's dying
    entries, and apply through one ``set_out_edges_batch`` scatter."""
    cap, d_out = state.capacity, state.d_out

    # group the planned additions per surviving row u (each u holds ≤ d_out
    # lanes — one per deleted out-neighbor), in a compact frame over the
    # ≤ B·d_in rows that actually gain an edge
    uid, adds_rows, u_ok = group_by_destination(
        z_flat, u_flat, u_valid & (z_flat != NULL), cap, d_out
    )
    uv = jnp.where(u_ok, uid, 0).astype(jnp.int32)
    # dedup additions within a row (several x's may pick the same z for u)
    eqa = (adds_rows[:, :, None] == adds_rows[:, None, :]) \
        & (adds_rows != NULL)[:, :, None]
    first = jnp.argmax(eqa, axis=2) == jnp.arange(d_out)[None, :]
    adds_rows = jnp.where(first, adds_rows, NULL)
    old_rows = state.adj[uv]
    # drop additions already present in u's row ("already there" = success)
    dup = jnp.any(adds_rows[:, :, None] == old_rows[:, None, :], axis=2)
    adds_rows = jnp.where(dup, NULL, adds_rows)

    # new row = (old row minus the dying x entries) ++ additions, truncated
    # at d_out in that order — matching the sequential remove-then-add order
    old_rows = jnp.where(
        (old_rows != NULL) & dead[jnp.maximum(old_rows, 0)], NULL, old_rows
    )
    packed = pack_rows(jnp.concatenate([old_rows, adds_rows], axis=1))
    return set_out_edges_batch(state, uid, packed[:, :d_out], u_ok)


def _splice_apply_reference(
    state: GraphState,
    u_flat: jax.Array, x_flat: jax.Array, z_flat: jax.Array,
    u_valid: jax.Array,
) -> GraphState:
    """Sequential splice applier (parity oracle for ``_splice_apply``):
    remove (u → x) first (frees the row slot), then add (u → z)."""
    def body(i, st):
        def splice(s):
            s = remove_edge(s, u_flat[i], x_flat[i])
            return jax.lax.cond(
                z_flat[i] != NULL,
                lambda s2: add_edge(s2, u_flat[i], z_flat[i]),
                lambda s2: s2,
                s,
            )
        return jax.lax.cond(u_valid[i], splice, lambda s: s, st)

    return jax.lax.fori_loop(0, u_flat.shape[0], body, state)


def _local_repair_apply(
    state: GraphState, ids: jax.Array, valid: jax.Array, dead: jax.Array,
    key, params: IndexParams,
) -> GraphState:
    """LOCAL plan + vectorized applier: splices grouped per u, one scatter.

    Shared by ``delete_local`` and the consolidation pass (DESIGN.md §8) —
    the ``dead`` mask is the caller's batch, which for consolidation is a
    chunk of tombstones rather than freshly marked deletions.
    """
    del key, params
    u_flat, _, z_flat, u_valid = _local_repair_plan(state, ids, valid, dead)
    return _splice_apply(state, dead, u_flat, z_flat, u_valid)


def delete_local(
    state: GraphState, ids: jax.Array, valid: jax.Array, key, params: IndexParams
) -> GraphState:
    """LOCAL with the vectorized applier: splices grouped per u, one scatter."""
    valid = _precheck(state, ids, valid)
    state = _mark_dead(state, ids, valid)
    dead = _dead_mask(state, ids, valid)
    state = _local_repair_apply(state, ids, valid, dead, key, params)
    return _finalize_removal(state, ids, valid)


def delete_local_reference(
    state: GraphState, ids: jax.Array, valid: jax.Array, key, params: IndexParams
) -> GraphState:
    """LOCAL with the pre-refactor sequential applier (parity oracle)."""
    del key
    valid = _precheck(state, ids, valid)
    state = _mark_dead(state, ids, valid)
    dead = _dead_mask(state, ids, valid)
    u_flat, x_flat, z_flat, u_valid = _local_repair_plan(state, ids, valid, dead)
    state = _splice_apply_reference(state, u_flat, x_flat, z_flat, u_valid)
    return _finalize_removal(state, ids, valid)


# ---------------------------------------------------------------------------
# GLOBAL (Alg 6) — the paper's recommended strategy
# ---------------------------------------------------------------------------

def _global_repair_plan(
    state: GraphState,
    ids: jax.Array,
    valid: jax.Array,
    dead: jax.Array,
    key,
    params: IndexParams,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Alg 6 lines 3–6 for the whole batch: the unique surviving in-neighbors
    and their wholesale replacement rows. Returns (u_flat, u_valid,
    new_nbrs). Shared by the vectorized and reference appliers."""
    B, d_in = ids.shape[0], state.d_in

    # ---- collect the unique surviving in-neighbors of the whole batch ----
    safe_ids = jnp.where(valid, ids, 0)
    u_flat = state.radj[safe_ids].reshape(-1)          # [B*d_in]
    u_valid = (u_flat != NULL) & jnp.repeat(valid, d_in)
    su = jnp.where(u_valid, u_flat, 0)
    u_valid = u_valid & ~dead[su] & state.alive[su]
    # dedupe (first occurrence wins) — a u may point at several deleted x's
    eq = u_flat[:, None] == u_flat[None, :]
    eq = eq & u_valid[None, :] & u_valid[:, None]
    first = jnp.argmax(eq, axis=1) == jnp.arange(u_flat.shape[0])
    u_valid = u_valid & first
    su = jnp.where(u_valid, u_flat, 0)

    # ---- batched repair search: GREEDY-SEARCH(u, G, k) on the marked graph,
    # all B·d_in in-neighbors through ONE batched beam-engine call (the same
    # compiled program the query path runs — §6.2's "repair cost in units of
    # queries" is now literal) ----
    sp = params.eff_insert_search
    u_vecs = state.vectors[su]
    starts = search.batch_entry_points(
        state, key, u_flat.shape[0], sp.num_starts
    )
    res = search.beam_search(
        state, u_vecs, starts, sp
    )  # alive-only candidates — deleted batch is already non-alive

    # ---- SELECT-NEIGHBORS(u, C, d, {x_i}) ----
    new_nbrs = jax.vmap(
        lambda u, vec, cids: select.select_from_pool(
            state, vec, cids, params.d_out, exclude=u[None]
        )
    )(su, u_vecs, res.ids)                              # i32[B*d_in, d_out]
    return u_flat, u_valid, new_nbrs


def _global_repair_apply(
    state: GraphState, ids: jax.Array, valid: jax.Array, dead: jax.Array,
    key, params: IndexParams,
) -> GraphState:
    """GLOBAL plan + vectorized applier: wholesale row replacement of every
    repaired u in one ``set_out_edges_batch`` scatter. Shared by
    ``delete_global`` and the consolidation pass (DESIGN.md §8)."""
    u_flat, u_valid, new_nbrs = _global_repair_plan(
        state, ids, valid, dead, key, params
    )
    return set_out_edges_batch(state, u_flat, new_nbrs, u_valid)


def delete_global(
    state: GraphState, ids: jax.Array, valid: jax.Array, key, params: IndexParams
) -> GraphState:
    """GLOBAL with the vectorized applier: wholesale row replacement of every
    repaired u in one ``set_out_edges_batch`` scatter."""
    valid = _precheck(state, ids, valid)
    state = _mark_dead(state, ids, valid)
    dead = _dead_mask(state, ids, valid)
    state = _global_repair_apply(state, ids, valid, dead, key, params)
    return _finalize_removal(state, ids, valid)


def delete_global_reference(
    state: GraphState, ids: jax.Array, valid: jax.Array, key, params: IndexParams
) -> GraphState:
    """GLOBAL with the pre-refactor sequential applier (parity oracle)."""
    valid = _precheck(state, ids, valid)
    state = _mark_dead(state, ids, valid)
    dead = _dead_mask(state, ids, valid)
    u_flat, u_valid, new_nbrs = _global_repair_plan(
        state, ids, valid, dead, key, params
    )

    def body(i, st):
        def repair(s):
            return set_out_edges(s, u_flat[i], new_nbrs[i])
        return jax.lax.cond(u_valid[i], repair, lambda s: s, st)

    state = jax.lax.fori_loop(0, u_flat.shape[0], body, state)
    return _finalize_removal(state, ids, valid)


# ---------------------------------------------------------------------------
# RWALK — random-walk replacement wiring (Mishra et al. 2025, PAPERS.md)
# ---------------------------------------------------------------------------

def _rwalk_walk_params(params: IndexParams):
    """The short-walk search budget: a few steps of the beam engine at
    beam_width=1 (the classic walk) over a small pool. Static under jit —
    built from the frozen param dataclasses at trace time."""
    mp = params.maintenance
    return dataclasses.replace(
        params.eff_insert_search,
        pool_size=mp.rwalk_pool,
        max_steps=mp.rwalk_steps,
        num_starts=min(mp.rwalk_starts, mp.rwalk_pool),
        beam_width=1,
        rerank_depth=0,
    )


def _rwalk_repair_plan(
    state: GraphState,
    ids: jax.Array,
    valid: jax.Array,
    dead: jax.Array,
    key,
    params: IndexParams,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Random-walk replacement plan: for each surviving in-neighbor u of a
    deleted x, short walks seeded at a random subset of x's out-neighborhood
    (the walk origins) run through the batched beam engine guided by u's
    vector; ONE replacement edge u → z is then picked from the walk pool.
    Returns (u, x, z, valid) flats of length B·d_in — the same contract as
    ``_local_repair_plan``, so both strategies share the splice appliers."""
    B, d_in, d_out = ids.shape[0], state.d_in, state.d_out
    mp = params.maintenance

    safe_ids = jnp.where(valid, ids, 0)
    in_nbrs = state.radj[safe_ids]                     # i32[B, d_in]  the u's
    out_nbrs = state.adj[safe_ids]                     # i32[B, d_out] origins
    u_flat = in_nbrs.reshape(-1)                       # [B*d_in]
    x_flat = jnp.repeat(safe_ids, d_in)                # deleted vertex per lane
    c_flat = jnp.broadcast_to(
        out_nbrs[:, None, :], (B, d_in, d_out)
    ).reshape(B * d_in, d_out)
    u_valid = (u_flat != NULL) & jnp.repeat(valid, d_in)
    su = jnp.where(u_valid, u_flat, 0)
    # u must itself survive (not in the delete batch)
    u_valid = u_valid & ~dead[su] & state.present[su]

    # ---- walk origins: a Gumbel-top-k random subset of x's out-neighbors,
    # per lane (fold_in by lane index — same per-lane key discipline as
    # batch_entry_points). Dead-but-present origins are allowed: the delete
    # batch stays traversable until _finalize_removal, exactly like the
    # GLOBAL repair search.
    S = max(1, min(mp.rwalk_starts, d_out))
    n_lanes = u_flat.shape[0]
    lane_keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(n_lanes, dtype=jnp.int32)
    )

    def origins(k_i, cands):
        cv = cands != NULL
        cv = cv & state.present[jnp.where(cv, cands, 0)]
        g = jax.random.gumbel(k_i, (d_out,))
        _, idx = jax.lax.top_k(jnp.where(cv, g, -jnp.inf), S)
        return jnp.where(cv[idx], cands[idx], NULL).astype(jnp.int32)

    starts = jax.vmap(origins)(lane_keys, c_flat)      # i32[B*d_in, S]

    # ---- short walks through the batched beam engine, ONE call for all
    # B·d_in lanes — raw pools (tombstones steer but never get selected)
    wp = _rwalk_walk_params(params)
    u_vecs = state.vectors[su]
    res = search.beam_search(state, u_vecs, starts, wp, raw=True)

    # ---- one replacement per u: diverse pick from the walk pool, never an
    # existing neighbor, never u itself, alive targets only (excludes the
    # delete batch and tombstones)
    def pick_one(u, vec, cids):
        exclude = jnp.concatenate([state.adj[u], u[None]])
        picked = select.select_from_pool(
            state, vec, cids, 1, exclude=exclude, keep_pruned=False
        )
        return picked[0]

    z_flat = jax.vmap(pick_one)(su, u_vecs, res.ids)   # i32[B*d_in]
    z_flat = jnp.where(u_valid, z_flat, NULL)
    return u_flat, x_flat, z_flat, u_valid


def _rwalk_repair_apply(
    state: GraphState, ids: jax.Array, valid: jax.Array, dead: jax.Array,
    key, params: IndexParams,
) -> GraphState:
    """RWALK plan + vectorized splice applier (shared with LOCAL). Shared by
    ``delete_rwalk`` and the consolidation pass (DESIGN.md §8)."""
    u_flat, _, z_flat, u_valid = _rwalk_repair_plan(
        state, ids, valid, dead, key, params
    )
    return _splice_apply(state, dead, u_flat, z_flat, u_valid)


def delete_rwalk(
    state: GraphState, ids: jax.Array, valid: jax.Array, key, params: IndexParams
) -> GraphState:
    """RWALK with the vectorized applier: splices grouped per u, one scatter."""
    valid = _precheck(state, ids, valid)
    state = _mark_dead(state, ids, valid)
    dead = _dead_mask(state, ids, valid)
    state = _rwalk_repair_apply(state, ids, valid, dead, key, params)
    return _finalize_removal(state, ids, valid)


def delete_rwalk_reference(
    state: GraphState, ids: jax.Array, valid: jax.Array, key, params: IndexParams
) -> GraphState:
    """RWALK with the sequential splice applier (parity oracle)."""
    valid = _precheck(state, ids, valid)
    state = _mark_dead(state, ids, valid)
    dead = _dead_mask(state, ids, valid)
    u_flat, x_flat, z_flat, u_valid = _rwalk_repair_plan(
        state, ids, valid, dead, key, params
    )
    state = _splice_apply_reference(state, u_flat, x_flat, z_flat, u_valid)
    return _finalize_removal(state, ids, valid)


# the vectorized repair appliers, keyed the way the consolidation pass
# (core/consolidate.py) selects them; signature (state, ids, valid, dead,
# key, params) → state — the ``dead`` mask is supplied by the caller so the
# same appliers serve freshly marked deletions and long-lived tombstones
REPAIR_APPLIERS = {
    "local": _local_repair_apply,
    "global": _global_repair_apply,
    "rwalk": _rwalk_repair_apply,
}

_STRATEGY_FNS = {
    "pure": delete_pure,
    "mask": delete_mask,
    "local": delete_local,
    "global": delete_global,
    "rwalk": delete_rwalk,
    "local_reference": delete_local_reference,
    "global_reference": delete_global_reference,
    "rwalk_reference": delete_rwalk_reference,
}


@functools.partial(
    jax.jit, static_argnames=("strategy", "params"), donate_argnums=(0,)
)
def delete_batch(
    state: GraphState,
    ids: jax.Array,       # i32[B]
    valid: jax.Array,     # bool[B]
    key: jax.Array,
    strategy: str,
    params: IndexParams,
) -> GraphState:
    return _STRATEGY_FNS[strategy](state, ids, valid, key, params)
