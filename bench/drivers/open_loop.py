"""Open-loop single-query requests into the program's ``BatchedServer``.

Traffic parameters (bench/traffic/<mix>.json):

    rate_qps       offered rate; the window offers exactly
                   round(rate_qps * seconds) requests
    arrival_seed   the set of inter-arrival gaps (exponential, scaled to
                   span the window exactly) is drawn once from this seed;
                   the run's seed only shuffles their order, so every seed
                   offers the same amount of work
    k, max_batch, max_wait_s   the request's top-k and the server's batching
    trace          {"seconds"}: a traced run profiles the window's first
                   seconds, and its host-side layer metrics read the
                   requests due in them

Requests are held-out draws from the configuration's generator. A submitter
thread hands each request to the server at its due time; the main thread
runs the server's steps. A request's latency runs from its due time to the
moment its answer is on the host, so a stall delays every request due
behind it.
"""
from __future__ import annotations

import sys
import threading
import time

import numpy as np

from harness.cell import Ctx, span

LATE_S = 60.0   # how long past the window's close an answer is waited for


class _TimedSession:
    """The session as the server sees it, with each dispatch timed."""

    def __init__(self, session, ctx: Ctx):
        self._s = session
        self._ctx = ctx
        self.dispatch_t: list[float] = []

    @property
    def recovering(self) -> bool:
        return self._s.recovering

    def query(self, queries, k=None, *, chunk=None):
        t = time.perf_counter()
        with span("dispatch"):
            h = self._s.query(queries, k=k, chunk=chunk)
        self._ctx.tracer.note("query", queries.shape[0], chunk)
        self.dispatch_t.append(t)
        return h


def prepare(ctx: Ctx) -> None:
    t = ctx.traffic
    n = int(round(t["rate_qps"] * ctx.seconds))
    gaps = np.random.default_rng(t["arrival_seed"]).exponential(1.0, n)
    gaps *= ctx.seconds / gaps.sum()
    ctx.records["offsets"] = np.cumsum(ctx.rng.permutation(gaps))
    ctx.records["queries"] = ctx.gen(n)
    ctx.records["warm_queries"] = ctx.gen(t["max_batch"])


def warm(ctx: Ctx) -> None:
    from repro.serving.batcher import BatchedServer, ServeConfig

    t = ctx.traffic
    timed = _TimedSession(ctx.session, ctx)
    server = BatchedServer(timed, ServeConfig(
        max_batch=t["max_batch"], max_wait_s=t["max_wait_s"], k=t["k"]))
    wq = ctx.records["warm_queries"]
    for q in wq:
        server.submit(q)
    done = server.step()
    ids = np.stack([done[r][0] for r in sorted(done)])
    sc = np.stack([done[r][1] for r in sorted(done)])
    ctx.log.append(("query", wq, ids, sc, False))
    ctx.records["server"] = server
    ctx.records["timed"] = timed


def window(ctx: Ctx) -> None:
    t = ctx.traffic
    server, timed = ctx.records["server"], ctx.records["timed"]
    queries, offsets = ctx.records["queries"], ctx.records["offsets"]
    n = queries.shape[0]
    rid0 = server._next_id
    stats0 = dict(server.stats)
    submit_t = np.full(n, np.nan)
    done_t = np.full(n, np.nan)
    batch_of = np.full(n, -1, np.int64)
    ids = np.full((n, t["k"]), -1, np.int64)
    scores = np.full((n, t["k"]), -np.inf, np.float32)
    stop = threading.Event()

    def submitter():
        for i in range(n):
            wait = due[i] - time.perf_counter()
            if wait > 0 and stop.wait(wait):
                return
            server.submit(queries[i])
            submit_t[i] = time.perf_counter()

    th = threading.Thread(target=submitter, name="bench-submitter")
    t_start = time.perf_counter()
    ctx.tracer.start()
    start_ms = (time.perf_counter() - t_start) * 1e3
    t0 = time.perf_counter() + 0.05
    due = t0 + offsets
    t_layer = t0 + (t["trace"]["seconds"] if ctx.tracer.wanted else np.inf)
    stats_layer = None
    th.start()
    answered = 0
    try:
        while answered < n:
            now = time.perf_counter()
            if now > t0 + ctx.seconds + LATE_S:
                break
            if stats_layer is None and now >= t_layer:
                ctx.tracer.stop()
                stats_layer = dict(server.stats)
            with span("serve_step"):
                got = server.step()
            if not got:
                with span("idle"):
                    time.sleep(0.0002)
                continue
            tnow = time.perf_counter()
            b = len(timed.dispatch_t) - 1
            for rid, (i_, s_) in got.items():
                i = rid - rid0
                done_t[i] = tnow
                batch_of[i] = b
                ids[i], scores[i] = i_[:t["k"]], s_[:t["k"]]
            answered += len(got)
    finally:
        stop.set()
        th.join(timeout=LATE_S)
        ctx.tracer.stop()
    late = (submit_t - due) * 1e3
    print(f"generator late: p99 {np.nanpercentile(late, 99):.2f} ms, max "
          f"{np.nanmax(late):.2f} ms"
          + (f"; profiler start {start_ms:.1f} ms" if ctx.tracer.wanted
             else ""), file=sys.stderr)
    stats_layer = stats_layer or dict(server.stats)
    ok = ~np.isnan(done_t)
    ctx.log.append(("query", queries[ok], ids[ok], scores[ok], True))
    disp = np.asarray(timed.dispatch_t)
    ctx.attempted = n
    ctx.failed = int((~ok).sum())
    ctx.records.update(
        window_s=ctx.seconds, t0=t0, due=due,
        done_t=done_t, answered=ok,
        dispatch_of_request=np.where(batch_of >= 0,
                                     disp[np.maximum(batch_of, 0)], np.nan),
        server_stats={k: stats_layer[k] - stats0[k] for k in stats0},
        max_batch=t["max_batch"], dispatched=list(ctx.tracer.dispatched),
        layer_window=due < t_layer)
