"""Vertex insertion (Alg 3, Insert branch): search → select → connect.

Deviation from the literal pseudocode (documented in DESIGN.md §2): Alg 3
line 10 only adds out-edges from the new vertex, which would leave fresh
vertices unreachable by greedy search. Following NSW/HNSW practice (which the
paper adapts its edge selection from), ``bidirectional_insert=True`` (default)
also links each selected neighbor back to the new vertex, re-running
SELECT-NEIGHBORS on the neighbor when its row is full ("shrink"). The
strict-paper variant is available via ``bidirectional_insert=False``.

``insert_batch`` is the **vectorized update engine** path (DESIGN.md §4):
the whole micro-batch is inserted by one batched pipeline — batched
free-slot allocation, ONE ``beam_search`` call against the pre-batch
snapshot (intra-batch members become candidates by appending the allocated
slot ids to every pool), vmapped SELECT-NEIGHBORS, and scatter-based edge
application (forward rows in one ``adj.at[slots].set``, back-link rows via
a grouped pack/shrink pass, reverse rows rebuilt in one sort/segment pass).
The pre-refactor sequential path is kept verbatim as
``insert_batch_reference`` — the parity oracle pinned by
``tests/test_update_parity.py`` (bit-exact at B=1; batch semantics differ
only in the documented snapshot-search / truncation-by-rank deviations).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import distances, quantize, search, select
from repro.core.graph import (
    NULL,
    GraphState,
    add_edge,
    group_by_destination,
    next_free_slot,
    pack_rows,
    set_out_edges,
    set_out_edges_batch,
)
from repro.core.params import IndexParams


def _connect_backward(state: GraphState, z: jax.Array, new_id: jax.Array) -> GraphState:
    """Add edge z→new, shrinking z's neighborhood if its row is full."""

    def simple(st: GraphState) -> GraphState:
        return add_edge(st, z, new_id)

    def shrink(st: GraphState) -> GraphState:
        cands = jnp.concatenate([st.adj[z], new_id[None]])
        picked = select.select_from_pool(
            st, st.vectors[z], cands, st.d_out, exclude=z[None],
            require_alive=False,  # keep existing (possibly masked) neighbors eligible
        )
        return set_out_edges(st, z, picked)

    row_full = ~jnp.any(state.adj[z] == NULL)
    return jax.lax.cond(row_full, shrink, simple, state)


def insert_one(
    state: GraphState,
    vec: jax.Array,        # f32[dim]
    key: jax.Array,
    params: IndexParams,
) -> tuple[GraphState, jax.Array]:
    """Insert one vector. Returns (state, new_id) — new_id == NULL if full."""
    sp = params.eff_insert_search
    slot = next_free_slot(state).astype(jnp.int32)
    ok = ~state.present[slot]

    # ---- ef-search for nearest candidates (alive-only results) via the
    # batched beam engine at B=1 — same compiled program family as queries
    # and GLOBAL repair (DESIGN.md §3) ----
    starts = search.entry_points(state, key, sp.num_starts)
    res = search.beam_search(state, vec[None], starts[None], sp)

    # ---- select diverse out-neighbors ----
    nbrs = select.select_from_pool(
        state, vec, res.ids[0], params.d_out, exclude=slot[None]
    )

    # ---- write the vertex ----
    vec_cast = vec.astype(state.vectors.dtype)
    if params.metric == "cos":
        vec_cast = distances.normalize(vec_cast)
    new_vectors = state.vectors.at[slot].set(
        jnp.where(ok, vec_cast, state.vectors[slot])
    )
    new_sqnorms = state.sqnorms.at[slot].set(
        jnp.where(ok, distances.sqnorm(vec_cast), state.sqnorms[slot])
    )
    # codes land in the same transaction as the vector write (invariant I5)
    code_row, code_scale = quantize.quantize_rows(vec_cast)
    state = dataclasses.replace(
        state,
        vectors=new_vectors,
        sqnorms=new_sqnorms,
        codes=state.codes.at[slot].set(
            jnp.where(ok, code_row, state.codes[slot])
        ),
        scales=state.scales.at[slot].set(
            jnp.where(ok, code_scale, state.scales[slot])
        ),
        alive=state.alive.at[slot].set(jnp.where(ok, True, state.alive[slot])),
        present=state.present.at[slot].set(
            jnp.where(ok, True, state.present[slot])
        ),
        size=state.size + ok.astype(jnp.int32),
        stamps=state.stamps.at[slot].set(
            jnp.where(ok, state.clock, state.stamps[slot])
        ),
        clock=state.clock + ok.astype(jnp.int32),
    )

    def do_connect(st: GraphState) -> GraphState:
        st = set_out_edges(st, slot, nbrs)
        if params.bidirectional_insert:
            def back(i, s):
                z = nbrs[i]
                return jax.lax.cond(
                    z != NULL,
                    lambda ss: _connect_backward(ss, z, slot),
                    lambda ss: ss,
                    s,
                )
            st = jax.lax.fori_loop(0, params.d_out, back, st)
        return st

    state = jax.lax.cond(ok, do_connect, lambda st: st, state)
    return state, jnp.where(ok, slot, NULL)


# ---------------------------------------------------------------------------
# Vectorized batch insertion — the update engine's insert path (DESIGN.md §4)
# ---------------------------------------------------------------------------

def insert_batch_impl(
    state: GraphState,
    vecs: jax.Array,      # f32[B, dim]
    valid: jax.Array,     # bool[B] — rows to actually insert
    key: jax.Array,
    params: IndexParams,
    key_offset: jax.Array | int = 0,
) -> tuple[GraphState, jax.Array]:
    """Traceable body of the batched insert pipeline.

    Phases (all O(1) device dispatches, no per-item loops):
      1. allocate — every valid row gets a free slot up front (stable scan
         over ``~present``: the i-th valid row gets the i-th lowest free id,
         matching the sequential ``next_free_slot`` order).
      2. search — ONE ``beam_search`` call for the whole micro-batch against
         the *pre-batch snapshot* (new slots are not yet present, so pools
         hold pre-batch candidates only; per-row keys fold exactly like the
         reference path, so B=1 is bit-identical).
      3. write — vectors/norms/flags land with one OOB-dropping scatter.
      4. select — vmapped SELECT-NEIGHBORS over pools extended with the
         whole batch's slot ids (intra-batch candidates; the pairwise
         [B, B] block is scored inside the select, no separate pass).
      5. connect — back-links grouped by target (``group_by_destination``),
         computed as a vectorized pack (row has room) / vmapped
         shrink-select (row overflows) against a virtual post-forward view,
         then forward + back-link rows land in ONE ``set_out_edges_batch``
         call (single scatter + incremental reverse patch,
         ``graph.apply_row_updates``) — no sequential edge chains; I1 holds
         with deterministic addition refusal under in-degree pressure.
    """
    B = vecs.shape[0]
    sp = params.eff_insert_search
    d_out, cap = params.d_out, state.capacity

    # ---- phase 1: batched free-slot allocation ----
    free = ~state.present
    n_free = jnp.sum(free.astype(jnp.int32))
    free_order = jnp.argsort(~free, stable=True).astype(jnp.int32)
    alloc_rank = jnp.cumsum(valid.astype(jnp.int32)) - 1
    ok = valid & (alloc_rank < n_free)
    slots = jnp.where(
        ok, free_order[jnp.where(ok, alloc_rank, 0)], NULL
    ).astype(jnp.int32)
    # OOB index parks invalid lanes: scatter mode="drop" makes them no-ops
    wslots = jnp.where(ok, slots, cap)

    # ---- phase 2: one ef-search for the whole batch (pre-batch snapshot).
    # Row i's search key folds the row's *global* stream index
    # (key_offset + i), so a padded final micro-batch searches exactly like
    # its unpadded twin (DESIGN.md §7) ----
    starts = search.batch_entry_points(
        state, key, B, sp.num_starts, offset=key_offset
    )
    res = search.beam_search(state, vecs, starts, sp)

    # ---- phase 3: write all vertices ----
    vec_cast = vecs.astype(state.vectors.dtype)
    if params.metric == "cos":
        vec_cast = distances.normalize(vec_cast)
    code_rows, code_scales = quantize.quantize_rows(vec_cast)
    state = dataclasses.replace(
        state,
        vectors=state.vectors.at[wslots].set(vec_cast, mode="drop"),
        sqnorms=state.sqnorms.at[wslots].set(
            distances.sqnorm(vec_cast), mode="drop"
        ),
        codes=state.codes.at[wslots].set(code_rows, mode="drop"),
        scales=state.scales.at[wslots].set(code_scales, mode="drop"),
        alive=state.alive.at[wslots].set(True, mode="drop"),
        present=state.present.at[wslots].set(True, mode="drop"),
        size=state.size + jnp.sum(ok).astype(jnp.int32),
        # stamps follow allocation rank, so batch order == sequential order
        stamps=state.stamps.at[wslots].set(
            state.clock + alloc_rank, mode="drop"
        ),
        clock=state.clock + jnp.sum(ok).astype(jnp.int32),
    )

    # ---- phase 4: vmapped SELECT-NEIGHBORS with intra-batch candidates ----
    slot_block = jnp.broadcast_to(slots[None, :], (B, B))
    cands = jnp.concatenate([res.ids, slot_block], axis=1)   # [B, K+B]
    nbrs = jax.vmap(
        lambda v, c, s: select.select_from_pool(
            state, v, c, d_out, exclude=s[None]
        )
    )(vecs, cands, slots)
    nbrs = jnp.where(ok[:, None], nbrs, NULL)

    # ---- phase 5: scatter-based edge application. Forward rows and
    # back-link rows are computed against a *virtual* post-forward view and
    # applied in ONE ``set_out_edges_batch`` call (one scatter + one
    # incremental reverse patch) ----
    if params.bidirectional_insert:
        # group back-link sources by their target z: bl[z] = new slots that
        # selected z. Per-row candidate budget d_out — a row keeps ≤ d_out
        # edges anyway, and the sequential path also never weighs more than
        # row+1 candidates per arrival (deviation bounded, B=1 unaffected).
        src = jnp.broadcast_to(slots[:, None], nbrs.shape).reshape(-1)
        dst = nbrs.reshape(-1)
        # compact frame: all work below happens on the ≤ B·d_out rows that
        # actually receive back-links, in ascending row id order
        zid, bl_rows, z_ok = group_by_destination(
            src, dst, dst != NULL, cap, d_out)
        zv = jnp.where(z_ok, zid, 0).astype(jnp.int32)
        # virtual current row: a z that is itself a freshly inserted slot
        # sees its just-selected forward row (mutual intra-batch selection)
        is_slot = (zv[:, None] == slots[None, :]) & ok[None, :]
        sidx = jnp.argmax(is_slot, axis=1)
        old_z = jnp.where(
            jnp.any(is_slot, axis=1)[:, None], nbrs[sidx], state.adj[zv]
        )                                                    # [R_z, d_out]
        # mutual selection: the virtual row may already hold the back-link
        dup = jnp.any(
            bl_rows[:, :, None] == old_z[:, None, :], axis=2
        ) & (bl_rows != NULL)
        bl_rows = jnp.where(dup, NULL, bl_rows)
        comb = jnp.concatenate([old_z, bl_rows], axis=1)     # [R_z, 2·d_out]
        counts = jnp.sum(comb != NULL, axis=1)
        packed = pack_rows(comb)[:, :d_out]
        needs_shrink = counts > d_out
        shrunk = jax.vmap(
            lambda z, c: select.select_from_pool(
                state, state.vectors[z], c, d_out, exclude=z[None],
                require_alive=False,
            )
        )(zv, comb)
        z_rows = jnp.where(needs_shrink[:, None], shrunk, packed)

        # combined application; where z is itself a slot, the z row is the
        # complete (forward ∪ back-link) row and supersedes the slot lane
        slot_valid = ok & ~jnp.any(
            slots[:, None] == jnp.where(z_ok, zid, NULL)[None, :], axis=1)
        us_all = jnp.concatenate([slots, zid])
        rows_all = jnp.concatenate([nbrs, z_rows], axis=0)
        valid_all = jnp.concatenate([slot_valid, z_ok])
        state = set_out_edges_batch(state, us_all, rows_all, valid_all)
    else:
        state = set_out_edges_batch(state, slots, nbrs, ok)
    return state, slots


@functools.partial(jax.jit, static_argnames=("params",), donate_argnums=(0,))
def insert_batch(
    state: GraphState,
    vecs: jax.Array,      # f32[B, dim]
    valid: jax.Array,     # bool[B] — rows to actually insert
    key: jax.Array,
    params: IndexParams,
) -> tuple[GraphState, jax.Array]:
    """Vectorized batch insertion (one batched pipeline, DESIGN.md §4)."""
    return insert_batch_impl(state, vecs, valid, key, params)


# ---------------------------------------------------------------------------
# Reference sequential path — the pre-refactor implementation, kept as the
# parity oracle for tests/test_update_parity.py and the baseline rows of
# benchmarks/kernel_bench.py's update section. Do not optimize.
# ---------------------------------------------------------------------------

def insert_batch_reference_impl(
    state: GraphState,
    vecs: jax.Array,      # f32[B, dim]
    valid: jax.Array,     # bool[B]
    key: jax.Array,
    params: IndexParams,
) -> tuple[GraphState, jax.Array]:
    """Sequential insertion of a batch (insert i+1 may link to insert i)."""
    B = vecs.shape[0]
    ids = jnp.full((B,), NULL, jnp.int32)

    def body(i, carry):
        st, out = carry
        k = jax.random.fold_in(key, i)

        def do(args):
            st_, out_ = args
            st2, nid = insert_one(st_, vecs[i], k, params)
            return st2, out_.at[i].set(nid)

        return jax.lax.cond(valid[i], do, lambda a: a, (st, out))

    state, ids = jax.lax.fori_loop(0, B, body, (state, ids))
    return state, ids


@functools.partial(jax.jit, static_argnames=("params",))
def insert_batch_reference(
    state: GraphState,
    vecs: jax.Array,
    valid: jax.Array,
    key: jax.Array,
    params: IndexParams,
) -> tuple[GraphState, jax.Array]:
    return insert_batch_reference_impl(state, vecs, valid, key, params)
