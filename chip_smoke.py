#!/usr/bin/env python3
"""Chip smoke: the serve path end to end on one TPU at SIFT1M size.

    python chip_smoke.py               # one chip: repro.launch.serve.serve_online
    python chip_smoke.py --four-chips  # ShardedSession over a (4, 1) mesh

One chip: 1,000,000 ``sift``-surrogate vectors (d=128, l2, seed 0) in a
2^20-slot index, d_out 12, pool 32, GLOBAL deletes; two maintenance steps of
10,000 deletes + 10,000 inserts, then 256 queries scored against the exact
brute force. Checked before the last line:

  (a) the compiled ``gather_scores`` / ``gather_scores_q8`` kernels agree
      with ``kernels/ref.py`` on 4,096 candidate ids of the 2^20 table, to
      1e-5 of the largest |score|, with -inf on the same invalid lanes;
  (b) ``search_batch`` with and without the Pallas kernel returns the same
      top-10 ids on at least 99% of the 256 queries;
  (c) ``radj`` equals ``rebuild_radj_rows`` (as sets, every slot);
  (d) no insert was refused;
  (e) recall@10 after each step is a finite number in (0, 1].

Four chips: 2^20 slots per shard, 4,000,000 vectors routed round-robin, one
churn step of 2,048 GLOBAL deletes + 2,048 inserts, 256 queries against an
exact top-10 over the union of shards; each shard's buffers must sit on
their own device.

Exits non-zero without a TPU and when any check fails. The last line of
standard output is the JSON device record.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

K = 10
N_QUERIES = 256
BATCH = 10_000          # deletes and inserts per maintenance step
SEED = 0


class _CompileClock:
    """Sums the backend compile seconds JAX reports."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def require(ok, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def device_line(devices) -> dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def check_kernels(state, queries, rng) -> None:
    """(a) compiled gather kernels vs the jnp oracles on the live table."""
    from repro.kernels import ops, ref

    n = state.capacity
    ids = rng.integers(0, n, size=(64, 64)).astype(np.int32)   # 4,096 ids
    ids[:, ::16] = -1
    ids[:, 1::16] = n + 7
    ids = jnp.asarray(ids)
    q = jnp.asarray(queries[:64])
    bad = (ids < 0) | (ids >= n)
    safe = jnp.where(bad, 0, ids)
    pairs = {
        "gather_scores": (
            ops.gather_scores(state.vectors, state.sqnorms, ids, q),
            ref.ref_gather_scores(state.vectors, state.sqnorms, safe, q)),
        "gather_scores_q8": (
            ops.gather_scores_q8(state.codes, state.scales, ids, q),
            ref.ref_gather_scores_q8(state.codes, state.scales, safe, q)),
    }
    for name, (got, want) in pairs.items():
        got, want = np.asarray(got), np.asarray(want)
        bad_np = np.asarray(bad)
        require(np.array_equal(got == -np.inf, bad_np),
                f"{name}: -inf lanes differ from the invalid ids")
        err = np.max(np.abs(got[~bad_np] - want[~bad_np]))
        scale = np.max(np.abs(want[~bad_np]))
        print(f"kernel {name}: max |err| {err:.3e} vs max |score| "
              f"{scale:.3e}", flush=True)
        require(err <= 1e-5 * scale, f"{name} disagrees with kernels/ref.py")


def check_pallas_parity(state, queries, params) -> None:
    """(b) the Pallas and jnp scoring paths find the same top-10."""
    import dataclasses

    from repro.core import search

    key = jax.random.PRNGKey(SEED)
    q = jnp.asarray(queries)
    top = {}
    for use in (True, False):
        sp = dataclasses.replace(params.search, use_pallas=use)
        top[use] = np.asarray(search.search_batch(state, q, key, sp).ids)[:, :K]
    same = np.mean([set(a) == set(b) for a, b in zip(top[True], top[False])])
    print(f"search_batch pallas vs jnp: same top-{K} on {same:.4f} of rows",
          flush=True)
    require(same >= 0.99, "Pallas and jnp search disagree on >1% of rows")


def check_radj(state) -> None:
    """(c) the patched reverse graph equals a full recompute from adj."""
    from repro.core.graph import rebuild_radj_rows

    @jax.jit
    def same(st):
        oracle = rebuild_radj_rows(st, jnp.ones((st.capacity,), bool))
        return (jnp.all(jnp.sort(st.radj, axis=1)
                        == jnp.sort(oracle.radj, axis=1)),
                jnp.all(st.adj == oracle.adj))
    radj_ok, adj_ok = same(state)
    require(bool(radj_ok), "radj != rebuild_radj_rows(adj) as row sets")
    require(bool(adj_ok), "the radj recompute had to truncate adj")


def one_chip(n_base: int = 1_000_000, batch: int = BATCH) -> dict:
    from repro.launch.serve import serve_online

    clock = _CompileClock()
    session, build_s, records = serve_online(
        dataset="sift", strategy="global", n_base=n_base, n_steps=2,
        batch_size=batch, n_queries=N_QUERIES, d_out=12, pool=32, seed=SEED,
        k=K)
    state = session.state
    print(f"compile_s={clock.seconds:.3f} (backend compiles, whole run) "
          f"build_s={build_s:.3f}", flush=True)
    for rec in records:
        print(f"step {rec['step']}: update_s={rec['update_s']:.3f} "
              f"qps={rec['qps']:.1f} recall@{K}={rec['recall@10']:.4f}",
              flush=True)
    require(state.capacity == 1 << n_base.bit_length(),
            f"capacity {state.capacity}")
    for rec in records:
        r = rec["recall@10"]
        require(np.isfinite(r) and 0.0 < r <= 1.0, f"recall@10 = {r}")
    require(session.timers.n_refused == 0,
            f"{session.timers.n_refused} inserts refused")

    from repro.data.workload import make_workload
    queries = make_workload(
        "sift", n_base=n_base, n_steps=2, batch_size=batch,
        n_queries=N_QUERIES, seed=SEED).queries
    check_kernels(state, queries, np.random.default_rng(SEED))
    check_pallas_parity(state, queries, session.params)
    check_radj(state)
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    print(f"peak_bytes_in_use={peak}", flush=True)
    return device_line(jax.devices())


def four_chips(cap: int = 1 << 20, n_base: int = 4_000_000,
               churn: int = 2_048) -> dict:
    from concurrent.futures import ThreadPoolExecutor

    from jax.sharding import PartitionSpec as P

    from repro.core import metrics
    from repro.core.params import IndexParams, MaintenanceParams, SearchParams
    from repro.data.synthetic import make_dataset
    from repro.distributed.ann import (
        DistParams, ShardedSession, make_delete_step, make_query_step,
        topk_union)

    devices = jax.devices()
    require(len(devices) >= 4, f"{len(devices)} devices, need 4")
    n_shards = 4
    chunk = 512 * n_shards           # routed rows per insert: 512 per shard
    axes = ("data", "model")
    mesh = jax.make_mesh((4, 1), axes, devices=devices[:4])
    dp = DistParams(index=IndexParams(
        capacity=cap, dim=128, d_out=12,
        search=SearchParams(pool_size=32, max_steps=96, num_starts=2),
        maintenance=MaintenanceParams(strategy="global")))
    x = make_dataset("sift", n_base + churn + N_QUERIES, seed=SEED)
    base, fresh = x[:n_base], x[n_base:n_base + churn]
    queries = jnp.asarray(x[n_base + churn:])
    rng = np.random.default_rng(SEED)
    stride = dp.gid_stride()

    def exact_step(state_stacked, q):
        """Exact top-K over each shard's alive rows, merged over shards."""
        local = jax.tree.map(lambda a: a[0], state_stacked)
        s, i = metrics.brute_force_topk(local, q, K)
        g = jnp.where(i >= 0, i + jax.lax.axis_index("data") * stride, -1)

        def flat(a):                                       # [4, B, K] → [B, 4K]
            a = jax.lax.all_gather(a, "data")
            return jnp.transpose(a, (1, 0, 2)).reshape(a.shape[1], -1)
        return topk_union(flat(s), flat(g), K)[1]

    def warm(fn, *args):
        with jax.set_mesh(mesh):
            fn.lower(*args).compile()

    clock = _CompileClock()
    with jax.set_mesh(mesh), ThreadPoolExecutor() as pool:
        sess = ShardedSession(dp, mesh, strategy="global", seed=SEED)
        exact = jax.jit(jax.shard_map(
            exact_step, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(axes), sess.state), P()),
            out_specs=P(), check_vma=False))
        t0 = time.perf_counter()
        gids, warming = [], []
        for lo in range(0, n_base, chunk):
            gids.append(sess.insert(
                base[lo:lo + chunk],
                np.arange(lo, min(lo + chunk, n_base)) % n_shards))
            if not warming:
                # compile the churn, query and reference programs on host
                # threads while the build keeps the chips busy; the
                # session's own calls then find them in the compile cache.
                # The state now has the sharding those calls will see.
                avals = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(
                        a.shape, a.dtype, sharding=a.sharding), sess.state)
                key = jax.random.PRNGKey(SEED)
                # delete() hands the step uncommitted gids: no sharding
                gids_like = jax.ShapeDtypeStruct((64,), jnp.int32)
                warming = [
                    pool.submit(warm, make_delete_step(dp, mesh, "global"),
                                avals, gids_like, key),
                    pool.submit(warm, make_query_step(dp, mesh), avals,
                                queries, key),
                    pool.submit(warm, exact, avals, queries),
                ]
        sess.flush()
        build_s = time.perf_counter() - t0
        for w in warming:
            w.result()
        gids = np.concatenate([np.asarray(g) for g in gids])
        print(f"compile_s={clock.seconds:.3f} (backend compiles, "
              f"overlapping the build) build_s={build_s:.3f}", flush=True)

        t0 = time.perf_counter()
        dead = rng.choice(gids, size=churn, replace=False)
        for lo in range(0, churn, 64):
            sess.delete(dead[lo:lo + 64])
        for lo in range(0, churn, chunk):
            part = fresh[lo:lo + chunk]
            sess.insert(part, np.arange(lo, lo + len(part)) % n_shards)
        sess.flush()
        update_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        found, _ = sess.query(queries)
        found = np.asarray(found)[:, :K]
        query_s = time.perf_counter() - t0
        true_ids = np.asarray(exact(sess.state, queries))
    recall = float(np.mean([len(set(f) & set(t)) / K
                            for f, t in zip(found, true_ids)]))
    print(f"update_s={update_s:.3f} qps={N_QUERIES / query_s:.1f} "
          f"recall@{K}={recall:.4f} n_alive={sess.n_alive()}", flush=True)
    require(np.isfinite(recall) and 0.0 < recall <= 1.0,
            f"recall@10 = {recall}")
    require(sess.timers.n_refused == 0, f"{sess.timers.n_refused} refused")
    homes = {s.device for s in sess.state.vectors.addressable_shards}
    require(len(homes) == n_shards and all(
        s.data.shape[0] == 1 for s in sess.state.vectors.addressable_shards),
        f"shards share devices: {homes}")
    for d in devices[:4]:
        print(f"device {d.id} peak_bytes_in_use="
              f"{d.memory_stats()['peak_bytes_in_use']}", flush=True)
    return device_line(devices[:4])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded four-chip phase")
    args = ap.parse_args()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {devices[0].platform}",
              file=sys.stderr)
        return 1
    from repro.launch.serve import enable_compile_cache

    cache = enable_compile_cache()
    print(f"platform={devices[0].platform} kind={devices[0].device_kind} "
          f"count={len(devices)} compile_cache={cache}", flush=True)
    device = four_chips() if args.four_chips else one_chip()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
