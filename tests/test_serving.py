"""Serving front-end: batching, pad stability, quorum degradation,
MASK consolidation."""
import numpy as np
import pytest

from helpers import build_index, check_invariants
from repro.core.consolidate import (
    consolidate,
    consolidate_reference,
    masked_fraction,
    maybe_consolidate,
)
from repro.core.graph import NULL
from repro.serving.batcher import BatchedServer, ServeConfig, quorum_merge


def test_batched_server_roundtrip():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(200, 8)).astype(np.float32)
    idx = build_index(X, capacity=256)
    srv = BatchedServer(idx, ServeConfig(max_batch=16, k=5))
    rids = [srv.submit(X[i] + 0.01) for i in range(5)]
    out = srv.step()
    assert set(out) == set(rids)
    for i, rid in enumerate(rids):
        ids, scores = out[rid]
        assert ids.shape == (5,)
        assert i in ids.tolist(), "query ≈ a stored vector must find it"
    assert srv.stats["requests"] == 5 and srv.stats["batches"] == 1


class _FakeTime:
    """Deterministic clock that only advances when sleep() is called."""

    def __init__(self):
        self.now = 0.0
        self.sleeps = []
        self.on_sleep = None

    def clock(self):
        return self.now

    def sleep(self, dt):
        assert dt > 0, "sleep(<=0) would busy-spin"
        self.sleeps.append(dt)
        self.now += dt
        if self.on_sleep is not None:
            self.on_sleep(self.now)


def _server(cfg, ft):
    rng = np.random.default_rng(9)
    X = rng.normal(size=(60, 8)).astype(np.float32)
    idx = build_index(X, capacity=96)
    return BatchedServer(idx, cfg, clock=ft.clock, sleep=ft.sleep)


def test_drain_waits_for_late_arrivals():
    """Regression: the max_wait_s branch used to be dead code (an empty
    queue hit `break` immediately), so adaptive batching never waited."""
    ft = _FakeTime()
    srv = _server(ServeConfig(max_batch=4, max_wait_s=0.005, k=3), ft)
    srv.submit(np.zeros(8, np.float32))

    def late_arrival(now):
        if now >= 0.002 and srv.stats.get("_arrived") is None:
            srv.stats["_arrived"] = True
            srv.submit(np.ones(8, np.float32))

    ft.on_sleep = late_arrival
    batch = srv._drain()
    assert len(batch) == 2, "mid-window arrival must join the batch"
    assert ft.now <= 0.005 + srv._POLL_S, "deadline overshot"


def test_drain_deadline_bounded_and_not_spinning():
    ft = _FakeTime()
    srv = _server(ServeConfig(max_batch=4, max_wait_s=0.005, k=3), ft)
    srv.submit(np.zeros(8, np.float32))
    batch = srv._drain()
    assert len(batch) == 1
    # waited the full window (clock advanced to the deadline)...
    assert abs(ft.now - 0.005) < 1e-9
    # ...in bounded slices, not a hot spin
    assert 0 < len(ft.sleeps) <= int(0.005 / srv._POLL_S) + 2
    assert all(dt > 0 for dt in ft.sleeps)


def test_drain_idle_and_full_batch_skip_the_wait():
    ft = _FakeTime()
    srv = _server(ServeConfig(max_batch=2, max_wait_s=0.005, k=3), ft)
    assert srv._drain() == [] and not ft.sleeps, "idle queue must not block"
    srv.submit(np.zeros(8, np.float32))
    srv.submit(np.ones(8, np.float32))
    srv.submit(np.zeros(8, np.float32))
    assert len(srv._drain()) == 2 and not ft.sleeps, "full batch is immediate"
    assert len(srv._queue) == 1



def test_stats_count_hold_and_queue_wait_on_the_injected_clock():
    """``hold_s`` is the simulated hold of a partial batch; ``queue_s`` sums
    each served request's submit → leaving the queue."""
    ft = _FakeTime()
    srv = _server(ServeConfig(max_batch=4, max_wait_s=0.005, k=3), ft)
    srv.submit(np.zeros(8, np.float32))           # t = 0
    ft.now = 0.001
    srv.submit(np.ones(8, np.float32))            # t = 0.001
    ft.now = 0.003                                # the step starts

    def late_arrival(now):
        if now >= 0.004 and srv._next_id == 2:
            srv.submit(np.full(8, 2.0, np.float32))   # taken at once

    ft.on_sleep = late_arrival
    assert len(srv.step()) == 3
    # three in hand, room for four: held to the deadline, 0.003 + 0.005
    assert srv.stats["hold_s"] == pytest.approx(0.005)
    assert srv.stats["hold_s"] == pytest.approx(sum(ft.sleeps))
    assert srv.stats["queue_s"] == pytest.approx(0.003 + 0.002 + 0.0)
    assert srv.stats["batches"] == 1 and srv.stats["requests"] == 3


def test_quorum_merge_degrades_gracefully():
    rng = np.random.default_rng(1)
    P, B, k = 8, 4, 10
    scores = rng.normal(size=(P, B, k)).astype(np.float32)
    scores.sort(axis=-1)
    scores = scores[..., ::-1]
    ids = rng.integers(0, 10_000, size=(P, B, k)).astype(np.int32)

    full_i, full_s = quorum_merge(ids, scores, np.ones(P, bool), k)
    # drop 2 shards
    arrived = np.ones(P, bool)
    arrived[[2, 5]] = False
    part_i, part_s = quorum_merge(ids, scores, arrived, k)
    assert (part_s <= full_s + 1e-6).all(), "partial merge can't beat full"
    # every returned id comes from an arrived shard
    alive_ids = set(ids[arrived].reshape(-1).tolist())
    got = part_i[part_i != NULL]
    assert set(got.tolist()) <= alive_ids
    # overlap stays high: ≥ k - 2·k/P expected per row on average
    overlap = np.mean([
        len(set(full_i[b]) & set(part_i[b])) / k for b in range(B)
    ])
    assert overlap >= 0.6


def test_consolidate_removes_tombstones():
    rng = np.random.default_rng(2)
    X = rng.normal(size=(150, 8)).astype(np.float32)
    idx = build_index(X, strategy="mask", capacity=256)
    idx.delete(np.arange(40))
    assert masked_fraction(idx.state) > 0.25
    n = consolidate(idx, strategy="global")
    assert n == 40
    assert masked_fraction(idx.state) == 0.0
    st = idx.stats()
    assert st["n_alive"] == 110 and st["n_masked"] == 0
    assert not check_invariants(idx.state)
    # recall survives consolidation
    Q = rng.normal(size=(32, 8)).astype(np.float32)
    assert idx.recall(Q, k=5) > 0.6


def test_maybe_consolidate_threshold():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(100, 8)).astype(np.float32)
    idx = build_index(X, strategy="mask", capacity=160)
    idx.delete(np.arange(10))           # 10% masked < 20% threshold
    assert maybe_consolidate(idx, threshold=0.2) == 0
    idx.delete(np.arange(10, 25))       # now 25% masked
    assert maybe_consolidate(idx, threshold=0.2) == 25


def _masked_index(seed=8, n=120, n_del=35):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 8)).astype(np.float32)
    idx = build_index(X, strategy="mask", capacity=192)
    idx.delete(rng.choice(n, size=n_del, replace=False))
    return idx, rng


def test_consolidate_reference_parity_pins_jitted_pass():
    """The exception-safe revive-then-delete oracle and the jitted chunked
    compaction agree semantically at small N: identical alive/present sets,
    masked fraction 0, invariant-clean graphs, equivalent recall. (Edge
    layouts differ by construction — the repair searches draw from
    different key chains — so the pin is set-level, not byte-level.)"""
    idx_ref, rng = _masked_index()
    idx_jit, _ = _masked_index()
    n_ref = consolidate_reference(idx_ref, strategy="global")
    n_jit = consolidate(idx_jit, strategy="global")
    assert n_ref == n_jit == 35
    for idx in (idx_ref, idx_jit):
        assert masked_fraction(idx.state) == 0.0
        assert not check_invariants(idx.state)
    np.testing.assert_array_equal(
        np.asarray(idx_ref.state.alive), np.asarray(idx_jit.state.alive))
    np.testing.assert_array_equal(
        np.asarray(idx_ref.state.present), np.asarray(idx_jit.state.present))
    Q = rng.normal(size=(48, 8)).astype(np.float32)
    r_ref = idx_ref.recall(Q, k=10)
    r_jit = idx_jit.recall(Q, k=10)
    assert abs(r_ref - r_jit) < 0.1, (r_ref, r_jit)
    assert min(r_ref, r_jit) > 0.6


def test_consolidate_reference_is_exception_safe():
    """Regression for the revive-then-delete hack: a repair failure used to
    leave tombstones revived and a foreign strategy installed. Now the
    state and strategy roll back, and a later pass still succeeds."""
    idx, _ = _masked_index()
    alive_before = np.asarray(idx.state.alive).copy()
    present_before = np.asarray(idx.state.present).copy()

    real_delete = idx.session.delete

    def boom(*a, **k):
        raise RuntimeError("injected repair failure")

    idx.session.delete = boom
    with pytest.raises(RuntimeError, match="injected"):
        consolidate_reference(idx, strategy="global")
    idx.session.delete = real_delete

    assert idx.strategy == "mask", "strategy must roll back"
    np.testing.assert_array_equal(np.asarray(idx.state.alive), alive_before)
    np.testing.assert_array_equal(
        np.asarray(idx.state.present), present_before)
    assert not check_invariants(idx.state)
    # the rolled-back index is fully functional: the real pass still drains
    assert consolidate(idx, strategy="global") == 35
    assert masked_fraction(idx.state) == 0.0


# ---------------------------------------------------------------------------
# graceful degradation (DESIGN.md §11): shedding, deadlines, readiness
# ---------------------------------------------------------------------------

def test_bounded_queue_sheds_overload():
    from repro.serving.batcher import ServerOverloadError

    ft = _FakeTime()
    srv = _server(ServeConfig(max_batch=4, k=3, max_queue=3), ft)
    for _ in range(3):
        srv.submit(np.zeros(8, np.float32))
    with pytest.raises(ServerOverloadError):
        srv.submit(np.zeros(8, np.float32))
    assert srv.stats["shed_overload"] == 1
    assert len(srv._queue) == 3, "the shed request must not occupy a slot"
    # draining frees capacity: admission recovers
    out = srv.step()
    assert len(out) == 3
    srv.submit(np.zeros(8, np.float32))
    assert srv.stats["shed_overload"] == 1


def test_per_request_deadline_expires_stale_entries():
    ft = _FakeTime()
    srv = _server(
        ServeConfig(max_batch=4, max_wait_s=0.0, k=3, deadline_s=0.01), ft)
    stale = srv.submit(np.zeros(8, np.float32))
    ft.now += 0.02  # the request ages past its deadline while queued
    fresh = srv.submit(np.ones(8, np.float32))
    out = srv.step()
    assert fresh in out and stale not in out
    assert srv.failed[stale] == "deadline"
    assert srv.stats["shed_deadline"] == 1
    ids, _ = out[fresh]
    assert ids.shape == (3,)


def test_readiness_gate_rejects_until_recovered():
    from repro.serving.batcher import ServerNotReadyError

    ft = _FakeTime()
    srv = _server(ServeConfig(max_batch=4, k=3), ft)
    assert srv.ready
    srv.set_ready(False)
    with pytest.raises(ServerNotReadyError):
        srv.submit(np.zeros(8, np.float32))
    srv.set_ready(True)
    srv.submit(np.zeros(8, np.float32))
    assert len(srv.step()) == 1


def test_readiness_tracks_session_recovery_flag():
    """A server wrapping a recovering session reports not-ready without any
    explicit wiring: `ready` consults session.recovering."""
    from repro.serving.batcher import ServerNotReadyError

    ft = _FakeTime()
    srv = _server(ServeConfig(max_batch=4, k=3), ft)
    srv.session.recovering = True
    assert not srv.ready
    with pytest.raises(ServerNotReadyError):
        srv.submit(np.zeros(8, np.float32))
    srv.session.recovering = False
    assert srv.ready
