"""Online ANN serving driver — the paper's production loop (Alg 3 at scale).

Drives an (op, payload) stream through a streaming :class:`Session`
(DESIGN.md §7): each maintenance step dispatches its delete and insert ops
asynchronously through the unified op IR and synchronizes once per step
(``flush``), so host-side bookkeeping overlaps device execution; queries run
through the same session for recall accounting. Per-phase latency books come
from the session's flush-based ``PhaseTimers``.

    PYTHONPATH=src python -m repro.launch.serve --scale 2000 --steps 3
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path

import jax
import numpy as np

from repro.core import (
    IndexParams,
    MaintenanceParams,
    SearchParams,
    Session,
    TieredSession,
)
from repro.data.workload import make_workload

# a fixed directory in the checkout: each run looks where the last one wrote
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Keep compiled programs across runs; returns the cache directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, places the cache (JAX reads the
    variable itself, so nothing is set here); otherwise the cache lives in
    ``.jax_cache/`` at the root of the checkout. Call before the first
    compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def serve_online(
    *,
    dataset: str = "sift",
    strategy: str = "global",
    n_base: int = 2000,
    n_steps: int = 3,
    batch_size: int = 200,
    n_queries: int = 256,
    d_out: int = 12,
    pool: int = 32,
    seed: int = 0,
    k: int = 10,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 0,
    recover: bool = False,
    tiered: bool = False,
    fresh_capacity: int | None = None,
) -> tuple[Session | TieredSession, float, list[dict]]:
    """Build the index from the workload's base set, then run its
    maintenance steps. Returns (session, build seconds, per-step records).
    """
    wl = make_workload(
        dataset, n_base=n_base, n_steps=n_steps, batch_size=batch_size,
        n_queries=n_queries, pattern="random", seed=seed,
    )
    dim = wl.base.shape[1]
    # the power-of-two tier that holds the whole stream (DESIGN.md §9 tiers)
    capacity = 1 << (n_base + n_steps * batch_size + 16 - 1).bit_length()
    maintenance = MaintenanceParams(strategy=strategy)
    if tiered:
        # two-tier serving (DESIGN.md §12): inserts land in a small fresh
        # tier, deletes of main-resident points tombstone, and the
        # streaming merge drains fresh→main one chunk per op
        fresh_capacity = fresh_capacity or max(2 * batch_size, 256)
        maintenance = MaintenanceParams(
            strategy="mask", merge_fresh_threshold=0.5,
            merge_tombstone_threshold=0.25,
            max_capacity=2 * capacity)
    params = IndexParams(
        capacity=capacity, dim=dim, d_out=d_out,
        search=SearchParams(pool_size=pool, max_steps=3 * pool, num_starts=2),
        maintenance=maintenance,
    )
    if recover:
        # crash restart: newest complete checkpoint + journal replay
        # (DESIGN.md §11) — params/strategy/seed must match the dead run
        if checkpoint_dir is None:
            raise ValueError("--recover requires --checkpoint-dir")
        t0 = time.perf_counter()
        if tiered:
            session = TieredSession.recover(
                checkpoint_dir, params, fresh_capacity=fresh_capacity,
                seed=seed)
        else:
            session = Session.recover(
                checkpoint_dir, params, strategy=strategy, seed=seed)
        info = session.recovery_info or {}
        print(
            f"recovered from {checkpoint_dir}: step={info.get('step')} "
            f"replayed={info.get('n_replayed', 0)} ops "
            f"(skipped {info.get('n_skipped', 0)}, "
            f"dropped {info.get('dropped_bytes', 0)}B torn tail) "
            f"in {time.perf_counter() - t0:.2f}s"
        )
    elif tiered:
        session = TieredSession(params, fresh_capacity=fresh_capacity,
                                seed=seed, checkpoint_dir=checkpoint_dir)
    else:
        # a checkpoint_dir arms the write-ahead journal automatically, so
        # every acknowledged op survives a crash up to the fsync policy
        session = Session(params, seed=seed, checkpoint_dir=checkpoint_dir)

    build_s = 0.0
    if recover and session._op_counter > 0:
        # the recovered timeline already contains the base build (and
        # whatever stream prefix was acknowledged before the crash); the
        # deterministic workload lets us rebuild the id map host-side
        print("skipping base build (recovered mid-stream)")
        id_map = list(range(n_base))
    else:
        print(f"building base index ({n_base} × d={dim}) ...")
        t0 = time.perf_counter()
        if tiered:
            # a fresh tier only holds fresh_capacity rows at once: bulk-load
            # in fresh-sized waves, the merge engine drains between them
            id_map = []
            for lo in range(0, n_base, fresh_capacity):
                ids = session.insert(wl.base[lo:lo + fresh_capacity]).result()
                id_map.extend(ids)
        else:
            ids = session.insert(wl.base).result()
            id_map = list(np.asarray(ids))   # pool position → graph id
        session.flush()
        build_s = time.perf_counter() - t0
        print(f"  built in {build_s:.1f}s")

    records = []
    for step in range(wl.n_steps):
        rec = {"step": step}
        dele_pos = wl.step_deletes[step]
        gids = [id_map[p] for p in dele_pos]
        # one maintenance step = delete + insert dispatched back-to-back,
        # one synchronization point
        t0 = time.perf_counter()
        session.delete(np.asarray(gids))
        h_ins = session.insert(wl.step_inserts[step])
        new_ids = h_ins.result()
        session.flush()
        rec["update_s"] = time.perf_counter() - t0
        rec["update_ops_per_s"] = (len(gids) + len(new_ids)) / rec["update_s"]
        id_map.extend(new_ids)

        t0 = time.perf_counter()
        rec["recall@10"] = session.recall(wl.queries, k=k)
        rec["query_s"] = time.perf_counter() - t0
        rec["qps"] = n_queries / rec["query_s"]
        rec.update(session.stats())
        if (checkpoint_dir is not None and checkpoint_every
                and (step + 1) % checkpoint_every == 0):
            session.save(step)   # publishes atomically, truncates the journal
            rec["checkpointed"] = True
        records.append(rec)
        print(
            f"step {step}: recall@{k}={rec['recall@10']:.3f} "
            f"qps={rec['qps']:.1f} upd={rec['update_s']:.2f}s "
            f"({rec['update_ops_per_s']:.0f} ops/s) alive={rec['n_alive']}"
        )
    print("session timers:", session.flush().to_dict())
    return session, build_s, records


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="sift")
    ap.add_argument("--strategy", default="global")
    ap.add_argument("--scale", type=int, default=2000)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--checkpoint-dir", default=None,
                    help="arm checkpoints + the write-ahead op journal")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="save every N maintenance steps (0 = never)")
    ap.add_argument("--recover", action="store_true",
                    help="restart from checkpoint-dir: newest complete "
                         "checkpoint + journal replay (DESIGN.md §11)")
    ap.add_argument("--tiered", action="store_true",
                    help="serve through the two-tier index (fresh tier + "
                         "streaming merge, DESIGN.md §12)")
    ap.add_argument("--fresh-capacity", type=int, default=None,
                    help="fresh-tier slot count (tiered mode only)")
    args = ap.parse_args()
    enable_compile_cache()
    serve_online(
        dataset=args.dataset, strategy=args.strategy, n_base=args.scale,
        n_steps=args.steps, batch_size=max(args.scale // 10, 10),
        n_queries=min(256, args.scale),
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        recover=args.recover,
        tiered=args.tiered,
        fresh_capacity=args.fresh_capacity,
    )


if __name__ == "__main__":
    main()
