"""Parity suite: vectorized update engine vs the sequential reference paths.

Pins the DESIGN.md §4 contract:
  · at B=1 with in-degree headroom, the batched insert pipeline is
    edge-set identical to ``insert_batch_reference`` (same slots, same
    adj/radj up to within-row permutation);
  · LOCAL/GLOBAL delete edge application matches the sequential reference
    appliers exactly when ``d_in`` is not under pressure (the repair *plans*
    are shared code, so this isolates the scatter-based application);
  · under in-degree pressure the paths may keep different edge subsets
    (scalar refusal vs truncation-by-rank) but both stay invariant-clean
    and within degree bounds;
  · batched inserts see the pre-batch snapshot + intra-batch candidates,
    and produce healthy, searchable graphs.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from helpers import check_invariants, small_params
from repro.core import IPGMIndex, IndexParams, SearchParams
from repro.core import delete as delete_mod
from repro.core import insert as insert_mod
from repro.core.graph import NULL, init_graph


def _params(d_in=None, capacity=128, dim=8, d_out=6, pool=16):
    return IndexParams(
        capacity=capacity, dim=dim, d_out=d_out, d_in=d_in,
        search=SearchParams(pool_size=pool, max_steps=3 * pool, num_starts=2),
    )


def _copy(state):
    return jax.tree.map(lambda x: x.copy() if hasattr(x, "copy") else x, state)


def _row_sets(arr):
    return [frozenset(int(v) for v in row if v != NULL) for row in np.asarray(arr)]


def _fresh(p):
    return init_graph(p.capacity, p.dim, d_out=p.d_out, d_in=p.eff_d_in,
                      metric=p.metric)


def _grow_pair(p, n, seed=0):
    """Build identical graphs through both insert paths, asserting parity."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, p.dim)).astype(np.float32)
    st_new, st_ref = _fresh(p), _fresh(p)
    for i in range(n):
        key = jax.random.PRNGKey(1000 + i)
        v = jnp.asarray(X[i][None])
        val = jnp.ones((1,), bool)
        st_new, id_new = insert_mod.insert_batch(st_new, v, val, key, p)
        st_ref, id_ref = insert_mod.insert_batch_reference(
            st_ref, v, val, key, p
        )
        assert int(id_new[0]) == int(id_ref[0]), f"slot diverged at insert {i}"
        assert _row_sets(st_new.adj) == _row_sets(st_ref.adj), (
            f"adj diverged at insert {i}"
        )
        assert _row_sets(st_new.radj) == _row_sets(st_ref.radj), (
            f"radj diverged at insert {i}"
        )
    return st_new, st_ref, X


def test_insert_b1_parity_exact():
    """B=1, ample d_in: the pipelines are edge-set identical step by step."""
    p = _params(d_in=64)
    st_new, st_ref, _ = _grow_pair(p, 50)
    assert not check_invariants(st_new)
    assert not check_invariants(st_ref)


@pytest.mark.parametrize("strategy", ["local", "global", "rwalk"])
def test_delete_apply_parity_exact(strategy):
    """Shared repair plan + no d_in pressure ⇒ identical edge application."""
    p = _params(d_in=64)
    st, _, _ = _grow_pair(p, 50, seed=1)
    rng = np.random.default_rng(2)
    ids = jnp.asarray(rng.choice(50, size=16, replace=False).astype(np.int32))
    valid = jnp.ones((16,), bool)
    key = jax.random.PRNGKey(7)
    new = delete_mod._STRATEGY_FNS[strategy](_copy(st), ids, valid, key, p)
    ref = delete_mod._STRATEGY_FNS[strategy + "_reference"](
        _copy(st), ids, valid, key, p
    )
    assert _row_sets(new.adj) == _row_sets(ref.adj)
    assert _row_sets(new.radj) == _row_sets(ref.radj)
    assert not check_invariants(new)
    assert not check_invariants(ref)


@pytest.mark.parametrize("strategy", ["local", "global", "rwalk"])
def test_delete_apply_parity_b1_bit_exact(strategy):
    """B=1 with in-degree headroom: vectorized and reference appliers agree
    on every non-edge field bit-for-bit and on every row's edge set."""
    p = _params(d_in=64)
    st, _, _ = _grow_pair(p, 40, seed=5)
    for victim in (3, 17, 31):
        ids = jnp.asarray([victim], dtype=jnp.int32)
        valid = jnp.ones((1,), bool)
        key = jax.random.PRNGKey(100 + victim)
        new = delete_mod._STRATEGY_FNS[strategy](_copy(st), ids, valid, key, p)
        ref = delete_mod._STRATEGY_FNS[strategy + "_reference"](
            _copy(st), ids, valid, key, p
        )
        assert _row_sets(new.adj) == _row_sets(ref.adj)
        assert _row_sets(new.radj) == _row_sets(ref.radj)
        for field in ("alive", "present", "size", "stamps", "codes", "scales"):
            np.testing.assert_array_equal(
                np.asarray(getattr(new, field)), np.asarray(getattr(ref, field)),
                err_msg=f"{field} diverged deleting {victim}",
            )
        assert not check_invariants(new)
        assert not check_invariants(ref)


@pytest.mark.parametrize("strategy", ["local", "global", "rwalk"])
def test_delete_apply_under_pressure_bounded_deviation(strategy):
    """Tight d_in: refusal vs truncation-by-rank may keep different edges,
    but both sides stay invariant-clean and inside the degree bounds."""
    p = _params(d_in=8)  # tight: in-degree pressure guaranteed
    rng = np.random.default_rng(3)
    X = rng.normal(size=(60, p.dim)).astype(np.float32)
    st = _fresh(p)
    st, _ = insert_mod.insert_batch(
        st, jnp.asarray(X), jnp.ones((60,), bool), jax.random.PRNGKey(0), p
    )
    ids = jnp.asarray(rng.choice(60, size=20, replace=False).astype(np.int32))
    valid = jnp.ones((20,), bool)
    key = jax.random.PRNGKey(9)
    new = delete_mod._STRATEGY_FNS[strategy](_copy(st), ids, valid, key, p)
    ref = delete_mod._STRATEGY_FNS[strategy + "_reference"](
        _copy(st), ids, valid, key, p
    )
    assert not check_invariants(new)
    assert not check_invariants(ref)
    # bounded deviation: same number of repaired rows, in-degree ≤ d_in
    for state in (new, ref):
        in_deg = np.sum(np.asarray(state.radj) != NULL, axis=1)
        assert (in_deg <= p.eff_d_in).all()


def test_batched_insert_healthy_and_complete():
    """B=32 through the one-shot pipeline: everything lands, graph healthy,
    intra-batch members are reachable from each other."""
    p = _params(capacity=96)
    rng = np.random.default_rng(4)
    X = rng.normal(size=(64, p.dim)).astype(np.float32)
    st = _fresh(p)
    for lo in (0, 32):  # two micro-batches: second sees the first as snapshot
        st, ids = insert_mod.insert_batch(
            st, jnp.asarray(X[lo:lo + 32]), jnp.ones((32,), bool),
            jax.random.PRNGKey(lo), p,
        )
        assert (np.asarray(ids) != NULL).all()
    assert not check_invariants(st)
    assert int(st.size) == 64
    # every vertex has at least one out-edge (intra-batch candidates made
    # the very first, empty-snapshot batch connect to itself)
    out_deg = np.sum(np.asarray(st.adj)[:64] != NULL, axis=1)
    assert (out_deg > 0).all()


def test_batched_insert_capacity_refusal():
    """Lanes beyond capacity refuse deterministically (NULL ids)."""
    p = _params(capacity=20)
    st = _fresh(p)
    rng = np.random.default_rng(5)
    X = jnp.asarray(rng.normal(size=(32, p.dim)).astype(np.float32))
    st, ids = insert_mod.insert_batch(
        st, X, jnp.ones((32,), bool), jax.random.PRNGKey(0), p
    )
    arr = np.asarray(ids)
    assert (arr[:20] != NULL).all()
    assert (arr[20:] == NULL).all()
    assert not check_invariants(st)
    assert int(st.size) == 20


def test_batched_insert_masked_lanes_are_noops():
    """valid=False lanes must not allocate slots or touch the graph."""
    p = _params(capacity=64)
    st = _fresh(p)
    rng = np.random.default_rng(6)
    X = jnp.asarray(rng.normal(size=(16, p.dim)).astype(np.float32))
    valid = jnp.asarray([True, False] * 8)
    st, ids = insert_mod.insert_batch(
        st, X, valid, jax.random.PRNGKey(0), p
    )
    arr = np.asarray(ids)
    assert (arr[::2] != NULL).all()
    assert (arr[1::2] == NULL).all()
    assert int(st.size) == 8
    assert not check_invariants(st)


def test_incremental_radj_patch_matches_recompute_oracle():
    """After batched updates, the incrementally patched radj must equal a
    full ``rebuild_radj_rows`` recompute from adj (row-set identical) —
    pins the patch applier against the sort/segment recompute oracle."""
    from repro.core.graph import rebuild_radj_rows

    p = _params(capacity=96)
    st = _fresh(p)
    rng = np.random.default_rng(11)
    for lo in (0, 24):
        st, _ = insert_mod.insert_batch(
            st, jnp.asarray(rng.normal(size=(24, p.dim)).astype(np.float32)),
            jnp.ones((24,), bool), jax.random.PRNGKey(lo), p,
        )
    ids = jnp.asarray(rng.choice(48, size=12, replace=False).astype(np.int32))
    st = delete_mod.delete_global(
        _copy(st), ids, jnp.ones((12,), bool), jax.random.PRNGKey(5), p
    )
    oracle = rebuild_radj_rows(_copy(st), jnp.ones((p.capacity,), bool))
    assert _row_sets(st.radj) == _row_sets(oracle.radj)
    # no truncation happened (invariants already clean), so adj is untouched
    np.testing.assert_array_equal(np.asarray(st.adj), np.asarray(oracle.adj))


def test_insert_empty_batch_is_noop():
    p = small_params(capacity=32)
    idx = IPGMIndex(p, strategy="pure")
    rng = np.random.default_rng(12)
    idx.insert(rng.normal(size=(5, 8)).astype(np.float32))
    ids = idx.insert(np.zeros((0, 8), np.float32))
    assert ids.shape == (0,)
    assert idx.stats()["n_alive"] == 5


def test_reference_strategy_names_accepted_by_index():
    p = small_params(capacity=64)
    idx = IPGMIndex(p, strategy="global_reference")
    rng = np.random.default_rng(7)
    idx.insert(rng.normal(size=(30, 8)).astype(np.float32))
    idx.delete(np.arange(8))
    assert not check_invariants(idx.state)
    assert idx.stats()["n_alive"] == 22


def test_query_ragged_chunk_padding_matches_full():
    """Padded ragged chunks return the same ids as an unpadded query."""
    import dataclasses
    p = dataclasses.replace(small_params(capacity=128), query_chunk=16)
    idx = IPGMIndex(p, strategy="global", seed=3)
    rng = np.random.default_rng(8)
    idx.insert(rng.normal(size=(80, 8)).astype(np.float32))
    Q = rng.normal(size=(21, 8)).astype(np.float32)  # 16 + ragged 5
    ids, scores = idx.query(Q, k=5)
    assert ids.shape == (21, 5)
    # brute-force agreement on the top-1 for a healthy small graph
    _, true_ids = idx.ground_truth(Q, 5)
    agree = np.mean([
        t[0] in set(np.asarray(r).tolist()) for r, t in zip(ids, np.asarray(true_ids))
    ])
    assert agree > 0.8


# ---------------------------------------------------------------------------
# apply_row_updates vs the dense capacity-wide formula it replaced
# ---------------------------------------------------------------------------

def _dense_apply_row_updates(state, us, new_rows, valid):
    """The pre-rewrite applier, kept verbatim as the bit-exact oracle: it
    patches radj with [capacity, d_in, d_out] and [capacity, d_in]
    temporaries (removals tested against every reverse entry, additions
    grouped by a searchsorted over every slot)."""
    import dataclasses

    cap, d_out, d_in = state.capacity, state.d_out, state.d_in
    R = us.shape[0]
    valid = valid & (us != NULL)
    su = jnp.where(valid, us, 0)
    wsu = jnp.where(valid, us, cap)
    old_rows = jnp.where(valid[:, None], state.adj[su], NULL)
    new_rows = jnp.where(valid[:, None], new_rows, NULL)

    row_of = jnp.full((cap + 1,), -1, jnp.int32).at[wsu].set(
        jnp.arange(R, dtype=jnp.int32), mode="drop"
    )[:cap]
    rv = state.radj
    r_idx = jnp.where(rv != NULL, row_of[jnp.maximum(rv, 0)], -1)
    nr = new_rows[jnp.maximum(r_idx, 0)]
    still = jnp.any(nr == jnp.arange(cap)[:, None, None], axis=2)
    radj1 = jnp.where((r_idx >= 0) & ~still, NULL, rv)

    add_m = (new_rows != NULL) & ~jnp.any(
        new_rows[:, :, None] == old_rows[:, None, :], axis=2
    )
    src = jnp.broadcast_to(su[:, None], (R, d_out)).reshape(-1)
    dst = new_rows.reshape(-1)
    add_flat = add_m.reshape(-1)
    E = dst.shape[0]
    key_dst = jnp.where(add_flat, dst, cap)
    order = jnp.argsort(key_dst, stable=True)
    sorted_key = key_dst[order]
    sorted_src = src[order]
    vids = jnp.arange(cap, dtype=key_dst.dtype)
    start = jnp.searchsorted(sorted_key, vids, side="left")
    end = jnp.searchsorted(sorted_key, vids, side="right")
    idx = start[:, None] + jnp.arange(d_in)[None, :]
    add_rows = jnp.where(
        idx < end[:, None], sorted_src[jnp.clip(idx, 0, E - 1)], NULL
    )

    holes = d_in - jnp.sum(radj1 != NULL, axis=1)
    ar = add_rows[jnp.clip(new_rows, 0, cap - 1)]
    match = ar == su[:, None, None]
    past_holes = (
        jnp.arange(d_in)[None, None, :]
        >= holes[jnp.clip(new_rows, 0, cap - 1)][:, :, None]
    )
    refused = add_m & (
        jnp.any(match & past_holes, axis=2) | ~jnp.any(match, axis=2)
    )
    final_rows = jnp.where(refused, NULL, new_rows)
    adj = state.adj.at[wsu].set(final_rows, mode="drop")

    isnull = radj1 == NULL
    hole_rank = jnp.cumsum(isnull.astype(jnp.int32), axis=1) - 1
    fill = jnp.take_along_axis(
        add_rows, jnp.clip(hole_rank, 0, d_in - 1), axis=1
    )
    radj2 = jnp.where(isnull, fill, radj1)
    touch = state.touch.at[wsu].set(state.tclock, mode="drop")
    return dataclasses.replace(
        state, adj=adj, radj=radj2, touch=touch, tclock=state.tclock + 1
    )


def _rewrite_batch(st, rng, n_rows, *, keep_old, hubs=None, R=None):
    """Sanitized rewrite rows: unique present targets, no self edges, valid
    rows unique, plus masked and NULL lanes. ``keep_old`` carries part of
    each old row over (permuted); ``hubs`` concentrates the new targets on
    a few destinations to force in-degree pressure."""
    present = np.flatnonzero(np.asarray(st.present))
    adj = np.asarray(st.adj)
    d_out = st.d_out
    R = R or n_rows + 3
    us = np.full((R,), NULL, np.int32)
    rows = np.full((R, d_out), NULL, np.int32)
    valid = np.zeros((R,), bool)
    picked = rng.choice(present, size=n_rows, replace=False)
    for r, u in enumerate(picked):
        pool = hubs if hubs is not None else present
        pool = np.setdiff1d(pool, [u])
        k = int(rng.integers(1, d_out + 1))
        row = []
        if keep_old:
            old = adj[u][adj[u] != NULL]
            row = list(rng.permutation(old)[: int(rng.integers(0, len(old) + 1))])
        fresh = rng.permutation(np.setdiff1d(pool, row))
        row = (row + list(fresh))[:k]
        rows[r, : len(row)] = row
        us[r] = u
        valid[r] = True
    # a masked lane with a real id, and NULL lanes (must both be no-ops)
    us[n_rows] = int(rng.choice(np.setdiff1d(present, picked)))
    rows[n_rows] = rows[0]
    order = rng.permutation(R)
    return (jnp.asarray(us[order]), jnp.asarray(rows[order]),
            jnp.asarray(valid[order]))


@pytest.mark.parametrize("case", ["no_pressure", "pressure", "rewrite_existing",
                                  "grown_capacity"])
def test_apply_row_updates_matches_dense_oracle(case):
    """The R·d_out-scaled applier is array-equal to the dense formula on
    adj, radj, touch and tclock — refusals under in-degree pressure
    included — over several seeded batches per case."""
    from repro.core.graph import apply_row_updates, grow_state

    d_in = {"pressure": 6}.get(case, 24)
    p = _params(d_in=d_in, capacity=64)
    st = _fresh(p)
    rng = np.random.default_rng({"no_pressure": 1, "pressure": 2,
                                 "rewrite_existing": 3,
                                 "grown_capacity": 4}[case])
    for lo in (0, 20, 40):
        st, _ = insert_mod.insert_batch(
            st, jnp.asarray(rng.normal(size=(20, p.dim)).astype(np.float32)),
            jnp.ones((20,), bool), jax.random.PRNGKey(lo), p,
        )
    if case == "grown_capacity":
        st = grow_state(st, 97)
    assert not check_invariants(st)
    n_refused = 0
    for it in range(4):
        hubs = (np.flatnonzero(np.asarray(st.present))[:8]
                if case == "pressure" else None)
        us, rows, valid = _rewrite_batch(
            st, rng, 12, keep_old=case == "rewrite_existing", hubs=hubs)
        want = jax.jit(_dense_apply_row_updates)(st, us, rows, valid)
        got = jax.jit(apply_row_updates)(st, us, rows, valid)
        for f in ("adj", "radj", "touch", "tclock"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, f)), np.asarray(getattr(want, f)),
                err_msg=f"{case} batch {it}: {f}")
        v = np.asarray(valid)
        n_refused += int(np.sum(np.asarray(rows)[v] != NULL)) - int(
            np.sum(np.asarray(got.adj)[np.asarray(us)[v]] != NULL))
        assert not check_invariants(got)
        st = got
    if case == "pressure":
        assert n_refused > 0, "pressure case never refused an addition"
    else:
        assert n_refused == 0
