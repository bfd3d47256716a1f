"""Request batching + quorum degradation — the online serving front-end.

Production behaviours the 1000-node story needs (DESIGN.md §5, §11):

  · **adaptive batching** — requests accumulate until ``max_batch`` or
    ``max_wait_s``; the device step always runs at a pad-stable shape so
    one compiled program serves every batch size (no recompiles at p99).
  · **quorum degradation** — the fan-out/merge query only *needs* all
    shards for exact results; with ``quorum < 1.0`` the merge accepts the
    first ⌈quorum·P⌉ shard results and degrades recall by ≤ (1-quorum)
    instead of stalling on a straggler. Simulated here by masking shard
    contributions (the merge math is identical to dropping late arrivals).
  · **graceful degradation under overload/recovery** — a bounded queue
    that sheds with a typed error past ``max_queue`` (backpressure to the
    caller, not an unbounded latency cliff), a per-request deadline so
    requests that waited too long fail fast instead of wasting a device
    step, and a readiness gate that holds traffic while the underlying
    session is replaying its journal after a crash.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable

import numpy as np

from repro.core.graph import NULL
from repro.core.maintenance import IPGMIndex
from repro.core.session import Session
from repro.core.trace import span


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_batch: int = 64
    max_wait_s: float = 0.005
    k: int = 10
    quorum: float = 1.0        # fraction of shards required (sharded mode)
    max_queue: int | None = None   # bound on queued requests (None = ∞)
    deadline_s: float | None = None  # per-request age limit at drain time


class ServerOverloadError(RuntimeError):
    """submit() refused: the bounded queue is full (load shed)."""


class ServerNotReadyError(RuntimeError):
    """submit() refused: the server is holding traffic (e.g. recovery)."""


class BatchedServer:
    """Pad-stable batched front-end over a :class:`Session`.

    Accepts a streaming ``Session`` directly, or an :class:`IPGMIndex`
    facade (whose underlying session is used). Every device step is one
    op-IR query micro-batch at the ``max_batch`` shape — the same padded
    program for every batch size — dispatched async and consumed when the
    step's results are handed back.

    Compile note: a session with ``unified_dispatch=True`` traces the full
    op switch (incl. the insert/delete-repair branches) at the serving
    shape on the first step; a query-only server can avoid that cold-start
    cost by handing in ``Session(..., unified_dispatch=False)`` (what the
    ``IPGMIndex`` facade uses), which compiles only the query branch.

    ``clock``/``sleep`` are injectable for deterministic tests of the
    batching window (tests/test_serving.py).

    ``stats`` counts served ``batches`` and ``requests``, sheds, and
    seconds on ``clock``: ``queue_s`` (each served request, submit →
    leaving the queue) and ``hold_s`` (the drain holding a partial batch
    open for arrivals). Each step writes the profiler spans
    ``ipgm.serve.step`` ⊃ ``ipgm.serve.drain`` ⊃ ``ipgm.serve.hold``, then
    ``ipgm.serve.pad``, the session's spans (the device wait and the copy
    to the host among them), and ``ipgm.serve.scatter``.
    """

    _POLL_S = 0.0005  # wait-slice; bounds drain latency jitter, not a spin

    def __init__(
        self,
        index: IPGMIndex | Session,
        cfg: ServeConfig = ServeConfig(),
        *,
        clock: Callable[[], float] = time.perf_counter,
        sleep: Callable[[float], None] = time.sleep,
    ):
        # `index` is kept only for caller introspection (back-compat attr);
        # every device step goes through `self.session` — don't mix paths
        self.index = index
        self.session = index.session if isinstance(index, IPGMIndex) else index
        self.cfg = cfg
        self._clock = clock
        self._sleep = sleep
        # queue entries carry their submit time for the deadline check
        self._queue: deque[tuple[int, np.ndarray, float]] = deque()
        self._next_id = 0
        self._ready = True
        self.stats = {"batches": 0, "requests": 0, "shed_overload": 0,
                      "shed_deadline": 0, "queue_s": 0.0, "hold_s": 0.0}
        # rid → reason for every request shed after admission (deadline):
        # callers poll this the same way they poll step() results
        self.failed: dict[int, str] = {}

    @property
    def ready(self) -> bool:
        """False while traffic must be held: an explicit ``set_ready(False)``
        or the underlying session replaying its journal (DESIGN.md §11)."""
        return self._ready and not getattr(self.session, "recovering", False)

    def set_ready(self, ready: bool) -> None:
        self._ready = bool(ready)

    def submit(self, query: np.ndarray) -> int:
        if not self.ready:
            raise ServerNotReadyError(
                "server is not accepting traffic (recovery in progress?)")
        if (self.cfg.max_queue is not None
                and len(self._queue) >= self.cfg.max_queue):
            self.stats["shed_overload"] += 1
            raise ServerOverloadError(
                f"queue full ({self.cfg.max_queue} pending); load shed")
        rid = self._next_id
        self._next_id += 1
        self._queue.append((rid, np.asarray(query, np.float32), self._clock()))
        return rid

    def _expire(self, now: float) -> None:
        """Fail queued requests whose age exceeds ``deadline_s`` — they shed
        *before* padding/dispatch, so a stale backlog never spends a device
        step producing answers nobody is waiting for. Submit times are
        monotone, so expired entries are always a queue prefix (O(1)
        amortized per drained request)."""
        if self.cfg.deadline_s is None:
            return
        while self._queue and now - self._queue[0][2] > self.cfg.deadline_s:
            rid, _, _ = self._queue.popleft()
            self.failed[rid] = "deadline"
            self.stats["shed_deadline"] += 1

    def _drain(self) -> list[tuple[int, np.ndarray]]:
        """Collect up to ``max_batch`` requests for one device step.

        The ``max_wait_s`` window is armed when the drain begins; once at
        least one request is in hand the drain honors it — sleeping in
        short slices (never spinning hot) so requests submitted
        concurrently during the window still join the batch. An idle queue
        returns immediately instead of holding the window open. Worst-case
        added latency per request is therefore queue-age at drain entry
        plus ``max_wait_s``.
        """
        out: list[tuple[int, np.ndarray]] = []
        deadline = self._clock() + self.cfg.max_wait_s
        with span("serve.drain"):
            self._take(out)
            if not out or len(out) == self.cfg.max_batch:
                return out  # idle, or full: nothing to hold open for
            with span("serve.hold", self.stats, "hold_s", clock=self._clock):
                while len(out) < self.cfg.max_batch:
                    remaining = deadline - self._clock()
                    if remaining <= 0:
                        break
                    self._sleep(min(remaining, self._POLL_S))
                    self._take(out)
        return out

    def _take(self, out: list[tuple[int, np.ndarray]]) -> None:
        """Move queued requests into ``out`` until it holds ``max_batch``
        or the queue is empty, shedding expired ones first."""
        now = self._clock()
        self._expire(now)
        while len(out) < self.cfg.max_batch and self._queue:
            rid, q, t_submit = self._queue.popleft()
            self.stats["queue_s"] += now - t_submit
            out.append((rid, q))

    def step(self) -> dict[int, tuple[np.ndarray, np.ndarray]]:
        """Serve one batch; returns {request_id: (ids, scores)}."""
        if not self._queue:
            return {}  # idle: no step, and no span
        with span("serve.step", step=self.stats["batches"]) as ann:
            batch = self._drain()
            if not batch:
                return {}
            ann.set_metadata(rid0=batch[0][0], n=len(batch))
            with span("serve.pad"):
                B = self.cfg.max_batch
                dim = batch[0][1].shape[-1]
                padded = np.zeros((B, dim), np.float32)
                for i, (_, q) in enumerate(batch):
                    padded[i] = q
            # one op-IR micro-batch at the pad-stable max_batch shape; the
            # handle resolves (blocks) only when this step's results are
            # needed
            ids, scores = self.session.query(
                padded, k=self.cfg.k, chunk=B).result()
            self.stats["batches"] += 1
            self.stats["requests"] += len(batch)
            with span("serve.scatter"):
                return {rid: (ids[i], scores[i])
                        for i, (rid, _) in enumerate(batch)}


def quorum_merge(
    shard_ids: np.ndarray,     # i32[P, B, k] per-shard top-k (global ids)
    shard_scores: np.ndarray,  # f32[P, B, k]
    arrived: np.ndarray,       # bool[P] which shards answered in time
    k: int,
) -> tuple[np.ndarray, np.ndarray]:
    """Merge only the arrived shards — the straggler-tolerant fan-in.

    Recall loss is bounded by the fraction of ground-truth neighbors living
    on the missing shards (≤ (P-|arrived|)/P in expectation under hashing).
    """
    P, B, kk = shard_ids.shape
    s = np.where(arrived[:, None, None], shard_scores, -np.inf)
    flat_s = np.transpose(s, (1, 0, 2)).reshape(B, P * kk)
    flat_i = np.transpose(shard_ids, (1, 0, 2)).reshape(B, P * kk)
    order = np.argsort(-flat_s, axis=1)[:, :k]
    top_s = np.take_along_axis(flat_s, order, axis=1)
    top_i = np.take_along_axis(flat_i, order, axis=1)
    top_i = np.where(np.isfinite(top_s), top_i, NULL)
    return top_i, top_s
