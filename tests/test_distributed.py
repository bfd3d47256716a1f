"""Distributed sharded index — runs in a subprocess with 8 fake devices
(XLA device count is locked at first jax init, so the multi-device tests
must not share this process)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from repro.distributed.ann import (DistParams, init_sharded_state,
                                   make_query_step, distributed_insert,
                                   make_delete_step)
from repro.core.params import IndexParams, SearchParams

out = {}
mesh = jax.make_mesh((4, 2), ('data', 'model'))
ip = IndexParams(capacity=64, dim=16, d_out=8,
                 search=SearchParams(pool_size=16, max_steps=32, num_starts=2))
dp = DistParams(index=ip)
state = init_sharded_state(dp, mesh)
rng = np.random.default_rng(0)
X = rng.normal(size=(200, 16)).astype(np.float32)
route = np.arange(200).astype(np.int32)
with jax.set_mesh(mesh):
    st, gids = distributed_insert(state, X, route, jax.random.PRNGKey(0),
                                  dp, mesh)
    g = np.asarray(gids)
    out['n_inserted'] = int((g >= 0).sum())
    out['gids_unique'] = len(set(g.tolist())) == 200

    Q = jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))
    ids, scores = make_query_step(dp, mesh)(st, Q, jax.random.PRNGKey(1))
    allv = np.asarray(jax.device_get(st.vectors)).reshape(-1, 16)
    alive = np.asarray(jax.device_get(st.alive)).reshape(-1)
    d2 = ((allv[None] - np.asarray(Q)[:, None])**2).sum(-1)
    d2[:, ~alive] = np.inf
    true10 = np.argsort(d2, 1)[:, :10]
    found = np.asarray(ids)[:, :10]
    out['recall'] = float(np.mean([
        len(set(found[i]) & set(true10[i])) / 10 for i in range(32)
    ]))

    dels = jnp.asarray(g[:50])
    st2 = make_delete_step(dp, mesh, 'global')(st, dels, jax.random.PRNGKey(2))
    out['alive_after_delete'] = int(np.asarray(jax.device_get(st2.alive)).sum())

    # per-shard consolidation (DESIGN.md section 8): mask-delete through a
    # ShardedSession with an armed threshold, then drain the tombstones
    from repro.distributed.ann import ShardedSession
    from repro.core.params import MaintenanceParams
    ipm = IndexParams(capacity=64, dim=16, d_out=8,
                      search=SearchParams(pool_size=16, max_steps=32,
                                          num_starts=2),
                      maintenance=MaintenanceParams(
                          strategy='mask', delete_chunk=16,
                          consolidate_threshold=0.25, consolidate_chunk=16))
    sess = ShardedSession(DistParams(index=ipm), mesh, strategy='mask')
    gids2 = np.asarray(sess.insert(X, jnp.asarray(route)))
    sess.delete(gids2[:40])
    sess.flush()  # trigger point: 40/200 = 0.2 < 0.25 → explicit drain below
    out['sharded_masked_mid'] = int(np.asarray(jnp.sum(sess.state.masked)))
    n_cons = sess.consolidate()
    sess.flush()
    out['sharded_consolidated'] = n_cons
    out['sharded_masked_after'] = int(np.asarray(jnp.sum(sess.state.masked)))
    out['sharded_present_after'] = int(np.asarray(jnp.sum(sess.state.present)))
    sess.delete(gids2[40:100])  # 60 more: crosses 0.25 → auto-trigger
    sess.flush()
    out['sharded_auto_masked'] = int(np.asarray(jnp.sum(sess.state.masked)))
    out['sharded_n_consolidations'] = sess.timers.n_consolidations

    # lockstep capacity growth (DESIGN.md section 9): armed max_capacity,
    # inserts past the per-shard tier grow every shard at once, and gids
    # handed out at the small tier stay decodable (stride = max_capacity)
    ipg = IndexParams(capacity=16, dim=16, d_out=8,
                      search=SearchParams(pool_size=16, max_steps=32,
                                          num_starts=2),
                      maintenance=MaintenanceParams(
                          strategy='pure', insert_chunk=32, delete_chunk=32,
                          max_capacity=128))
    gs = ShardedSession(DistParams(index=ipg), mesh, strategy='pure')
    g1 = np.asarray(gs.insert(X[:100], jnp.arange(100)))
    g2 = np.asarray(gs.insert(X[100:200], jnp.arange(100, 200)))
    gs.flush()
    out['growth_cap'] = gs.dp.index.capacity
    out['growth_n_grows'] = gs.timers.n_grows
    out['growth_refused'] = gs.timers.n_refused
    out['growth_gids_unique'] = (
        len(set(g1.tolist()) | set(g2.tolist())) == 200)
    out['growth_alive'] = int(np.asarray(jnp.sum(gs.state.alive)))
    gs.delete(jnp.asarray(g1[:20]))  # pre-growth gids must still decode
    gs.flush()
    out['growth_alive_after_delete'] = int(np.asarray(jnp.sum(gs.state.alive)))
    qi, _ = gs.query(Q[:8])
    out['growth_query_valid'] = bool((np.asarray(qi)[:, 0] >= 0).all())

    # fault-injection coverage (DESIGN.md section 11): a mixed sharded
    # stream with growth + consolidation armed reaches every registered
    # sharded crash point, and an armed plan kills at the exact site
    from repro.testing import faults
    ipf = IndexParams(capacity=16, dim=16, d_out=8,
                      search=SearchParams(pool_size=16, max_steps=32,
                                          num_starts=2),
                      maintenance=MaintenanceParams(
                          strategy='mask', insert_chunk=32, delete_chunk=32,
                          consolidate_threshold=0.25, consolidate_chunk=16,
                          max_capacity=128))
    probe = faults.FaultPlan()
    with faults.inject(probe):
        fs = ShardedSession(DistParams(index=ipf), mesh, strategy='mask')
        fg1 = np.asarray(fs.insert(X[:100], jnp.arange(100)))
        fs.insert(X[100:200], jnp.arange(100, 200))
        fs.delete(jnp.asarray(fg1[:60]))
        fs.consolidate()
        fs.flush()
    out['fault_hits'] = {p: probe.hits.get(p, 0)
                         for p in faults.SHARDED_CRASH_POINTS}
    crashed = False
    with faults.inject(faults.crash_once('sharded-pre-dispatch', hit=1)):
        try:
            fs.insert(X[:10], jnp.arange(10))
        except faults.SimulatedCrash:
            crashed = True
    out['fault_crash_fired'] = crashed

    # multi-pod replica mesh
    mesh3 = jax.make_mesh((2, 2, 2), ('pod', 'data', 'model'))
    dp3 = DistParams(index=ip, pod_axis='pod')
with jax.set_mesh(mesh3):
    st3 = init_sharded_state(dp3, mesh3)
    st3, gids3 = distributed_insert(st3, X[:80], route[:80],
                                    jax.random.PRNGKey(0), dp3, mesh3)
    ids3, _ = make_query_step(dp3, mesh3)(st3, Q[:8], jax.random.PRNGKey(1))
    out['multipod_inserted'] = int((np.asarray(gids3) >= 0).sum())
    out['multipod_results_valid'] = bool((np.asarray(ids3)[:, 0] >= 0).all())

print('RESULT ' + json.dumps(out))
"""


@pytest.mark.slow
def test_sharded_index_8dev():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True,
        text=True, timeout=540,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    assert line, proc.stdout
    out = json.loads(line[-1][len("RESULT "):])
    assert out["n_inserted"] == 200
    assert out["gids_unique"]
    assert out["recall"] > 0.9
    assert out["alive_after_delete"] == 150
    assert out["sharded_masked_mid"] == 40
    assert out["sharded_consolidated"] == 40
    assert out["sharded_masked_after"] == 0
    assert out["sharded_present_after"] == 160
    assert out["sharded_auto_masked"] == 0, "threshold crossing must drain"
    assert out["sharded_n_consolidations"] >= 2
    assert out["growth_cap"] > 16, "shards must have grown in lockstep"
    assert out["growth_cap"] <= 128
    assert out["growth_n_grows"] <= 3  # ceil(log2(128/16)) recompiles max
    assert out["growth_refused"] == 0
    assert out["growth_gids_unique"]
    assert out["growth_alive"] == 200
    assert out["growth_alive_after_delete"] == 180
    assert out["growth_query_valid"]
    missing = [p for p, n in out["fault_hits"].items() if n == 0]
    assert not missing, f"sharded stream never reached crash points: {missing}"
    assert out["fault_crash_fired"], "armed sharded crash point must fire"
    assert out["multipod_inserted"] == 80
    assert out["multipod_results_valid"]


@pytest.mark.parametrize("route", ["round_robin", "skewed", "empty"])
def test_shard_blocks_groups_rows_by_owner(route):
    """Host grouping behind the sharded insert: every row lands in its
    owner's block, in arrival order, with its batch index alongside."""
    import numpy as np

    from repro.distributed.ann import shard_blocks

    rng = np.random.default_rng(0)
    n = 0 if route == "empty" else 37
    vecs = rng.normal(size=(n, 5)).astype(np.float32)
    r = (np.arange(n) if route == "round_robin"
         else rng.choice([1, 1, 1, 6], size=n))
    blocks, rows, valid, pos = shard_blocks(vecs, r, 4)
    m = blocks.shape[1]
    assert m & (m - 1) == 0 and m >= max(np.bincount(r % 4, minlength=4))
    assert blocks.shape == (4, m, 5) and rows.shape == valid.shape == (4, m)
    np.testing.assert_array_equal(blocks.reshape(-1, 5)[pos], vecs)
    np.testing.assert_array_equal(rows.reshape(-1)[pos], np.arange(n))
    np.testing.assert_array_equal(pos // m, r % 4)
    assert valid.sum() == n and valid.reshape(-1)[pos].all()
    for s in range(4):   # arrival order within each shard
        assert (np.diff(rows[s][valid[s]]) > 0).all()
