"""SELECT-NEIGHBORS (Alg 2) — diversity edge-selection heuristic, batched.

The rule (Malkov et al. 2014 / HNSW "heuristic"): scan candidates in order of
proximity to ``x``; keep ``y`` iff ``x`` is at least as close to ``y`` as any
already-selected neighbor ``z`` is (``||x-y|| <= min_z ||z-y||``, Alg 2 line
6; the standard ip-NSW generalization replaces distances with the similarity
``f``).

Metric care: the dominance test compares f(y, x) with f(y, z) — both must be
scored with *y in the query role* so the per-candidate norm offsets cancel
(for L2 scores ``2<a,b> - ||b||^2`` the offset is ``+||y||^2`` on both sides).
The candidate *ordering* instead puts x in the query role. Getting this wrong
silently breaks diversity selection for L2; the unit tests pin both.

TPU shape: candidates are a fixed-size pool (≤ pool_size), so the pairwise
candidate score matrix is a tiny fp32 matmul and the greedy scan is a
``fori_loop`` carrying a selection mask.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import distances
from repro.core.graph import NULL

NEG_INF = distances.NEG_INF


def select_neighbors(
    x_vec: jax.Array,       # f32[dim]   the vertex being (re)connected
    cand_ids: jax.Array,    # i32[n]     candidate ids (NULL padded)
    cand_vecs: jax.Array,   # f32[n,dim] gathered candidate vectors
    cand_valid: jax.Array,  # bool[n]    validity incl. the invalid set I
    d: int,                 # out-degree threshold
    metric: str,
    keep_pruned: bool = False,  # HNSW keepPrunedConnections: fill to d with
                                # the nearest dominated candidates
) -> jax.Array:
    """Returns i32[d] selected ids, NULL padded, proximity-descending."""
    n = cand_ids.shape[0]
    x32 = x_vec.astype(jnp.float32)
    v32 = cand_vecs.astype(jnp.float32)
    dots = jnp.matmul(v32, x32, precision=distances.HIGHEST)  # [n]

    if metric == "l2":
        order_key = 2.0 * dots - distances.sqnorm(v32)   # x as query
        chk_to_x = 2.0 * dots - distances.sqnorm(x32)    # y as query
    else:  # ip / cos
        order_key = dots
        chk_to_x = dots

    order_key = jnp.where(cand_valid, order_key, NEG_INF)
    okey_o, order = jax.lax.top_k(order_key, n)
    ids_o = jnp.where(okey_o > NEG_INF, cand_ids[order], NULL)
    vecs_o = v32[order]
    chk_o = chk_to_x[order]
    valid_o = ids_o != NULL

    # pair[i, j] = f(y_i as query, y_j) — same query role as chk_o[i]
    pair = distances.score_matrix(
        vecs_o, distances.sqnorm(vecs_o), vecs_o, metric
    )  # [n, n]

    def body(i, carry):
        selected, count = carry
        # y_i survives iff  f(y_i, x) >= f(y_i, z)  for every selected z
        dominated = jnp.any(selected & (pair[i] > chk_o[i]))
        take = valid_o[i] & ~dominated & (count < d)
        selected = selected.at[i].set(take)
        return selected, count + take.astype(jnp.int32)

    selected, n_sel = jax.lax.fori_loop(
        0, n, body, (jnp.zeros((n,), bool), jnp.asarray(0, jnp.int32))
    )

    # compact: first d selected (already proximity-ordered)
    rank = jnp.where(selected, okey_o, NEG_INF)
    top_scores, idx = jax.lax.top_k(rank, min(d, n))
    out = jnp.where(top_scores > NEG_INF, ids_o[idx], NULL)

    if keep_pruned:
        # fill remaining slots with the closest dominated candidates
        rank2 = jnp.where(valid_o & ~selected, okey_o, NEG_INF)
        fs, fi = jax.lax.top_k(rank2, min(d, n))
        fill = jnp.where(fs > NEG_INF, ids_o[fi], NULL)
        pos = jnp.arange(min(d, n))
        take_fill = jnp.clip(pos - n_sel, 0, min(d, n) - 1)
        out = jnp.where(pos < n_sel, out, fill[take_fill])

    if d > n:
        out = jnp.concatenate([out, jnp.full((d - n,), NULL, jnp.int32)])
    return out.astype(jnp.int32)


def select_from_pool(
    state,                 # GraphState
    x_vec: jax.Array,      # f32[dim]
    cand_ids: jax.Array,   # i32[n]
    d: int,
    exclude: jax.Array | None = None,  # i32[m] ids to exclude (invalid set I)
    require_alive: bool = True,
    keep_pruned: bool = True,  # system default (HNSW practice); the
                               # strict-paper heuristic is keep_pruned=False
) -> jax.Array:
    """Gather + validate a candidate pool from the graph, then select."""
    valid = cand_ids != NULL
    safe = jnp.where(valid, cand_ids, 0)
    if require_alive:
        valid = valid & state.alive[safe]
    else:
        valid = valid & state.present[safe]
    if exclude is not None:
        valid = valid & ~jnp.any(cand_ids[:, None] == exclude[None, :], axis=1)
    # dedupe within the pool (keep first occurrence)
    eq = cand_ids[:, None] == cand_ids[None, :]
    first = jnp.argmax(eq, axis=1) == jnp.arange(cand_ids.shape[0])
    valid = valid & first
    vecs = state.vectors[safe]
    return select_neighbors(x_vec, cand_ids, vecs, valid, d, state.metric,
                            keep_pruned=keep_pruned)
