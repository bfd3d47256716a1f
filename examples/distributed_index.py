"""Sharded online index on 8 simulated devices — the production layout.

Shard-per-device subgraphs, routed inserts, fan-out queries with
hierarchical top-k merge, GLOBAL delete repair running shard-locally.
Must set the device count before jax initializes.

    PYTHONPATH=src python examples/distributed_index.py
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.params import IndexParams, SearchParams  # noqa: E402
from repro.distributed.ann import DistParams, ShardedSession  # noqa: E402

mesh = jax.make_mesh((4, 2), ("data", "model"))
dp = DistParams(index=IndexParams(
    capacity=128, dim=32, d_out=8,
    search=SearchParams(pool_size=16, max_steps=48, num_starts=2),
))
rng = np.random.default_rng(0)

with jax.set_mesh(mesh):
    # the sharded session owns the stacked per-shard state (donated through
    # every update step) and dispatches ops async — flush() to synchronize
    sess = ShardedSession(dp, mesh, strategy="global", seed=0)
    X = rng.normal(size=(400, 32)).astype(np.float32)
    gids = sess.insert(X, np.arange(400))
    print("inserted:", int((np.asarray(gids) >= 0).sum()), "across",
          mesh.devices.size, "shards")

    Q = rng.normal(size=(16, 32)).astype(np.float32)
    ids, scores = sess.query(Q)
    print("query results (global ids):", np.asarray(ids)[0, :5])

    sess.delete(np.asarray(gids)[:100])
    sess.flush()
    print("alive after GLOBAL delete of 100:", sess.n_alive())
    print("timers:", sess.timers.to_dict())
