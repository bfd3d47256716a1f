"""Closed-loop churn: repeated steps of deletes, inserts and probe queries.

Traffic parameters (bench/traffic/<mix>.json):

    deletes, inserts, probes   per step; deletes are uniform over the live
                               rows, inserts are fresh rows of the
                               configuration's generator
    chunk                      rows per op step (the session's micro-batch)
    probe_noise                a probe is a copy of a row the step just
                               deleted (half) or inserted (half), plus
                               Gaussian noise of this share of the row's
                               RMS component
    max_steps                  fresh rows are generated for this many steps
    k                          the probes' top-k
    trace                      {"step": n}: the traced step of the window

Each step dispatches its deletes, inserts and probes through
``Session.delete/insert/query`` and waits for all three acknowledgements
before the next step is drawn. The window runs whole steps until
``seconds`` have passed; its rate is the items of those steps over their
time.
"""
from __future__ import annotations

import time

import numpy as np

from harness.cell import Ctx, span


def prepare(ctx: Ctx) -> None:
    t = ctx.traffic
    ctx.records["fresh"] = ctx.gen(t["inserts"] * t["max_steps"]
                                   + t["chunk"])


class _Churn:
    def __init__(self, ctx: Ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.t = t
        self.fresh = ctx.add_rows(ctx.records.pop("fresh"))
        self.used = 0
        self.live = np.concatenate([e[1] for e in ctx.log
                                    if e[0] == "insert"])
        self.row_slot = np.full(ctx.corpus.shape[0], -1, np.int64)
        for e in ctx.log:
            if e[0] == "insert":
                self.row_slot[e[1]] = e[2]

    def probes(self, x: np.ndarray, n: int) -> np.ndarray:
        rng = self.ctx.rng
        pick = x[rng.choice(x.shape[0], n, replace=x.shape[0] < n)]
        rms = np.sqrt((pick.astype(np.float64) ** 2).mean(axis=1,
                                                          keepdims=True))
        noise = rng.normal(0.0, 1.0, pick.shape) * rms * self.t["probe_noise"]
        return (pick + noise).astype(np.float32)

    def step(self, n_del: int, n_ins: int, n_probe: int, in_window: bool):
        ctx, s, chunk = self.ctx, self.ctx.session, self.t["chunk"]
        rng = ctx.rng
        pos = rng.choice(self.live.shape[0], n_del, replace=False)
        gone = self.live[pos]
        new_rows = self.fresh[self.used:self.used + n_ins]
        self.used += n_ins
        new = ctx.corpus[new_rows]
        half = n_probe // 2
        q = np.concatenate([self.probes(ctx.corpus[gone], half),
                            self.probes(new, n_probe - half)])
        with span("delete"):
            hd = s.delete(self.row_slot[gone], chunk=chunk)
        ctx.tracer.note("delete", n_del, chunk)
        with span("insert"):
            hi = s.insert(new, chunk=chunk)
        ctx.tracer.note("insert", n_ins, chunk)
        with span("query"):
            hq = s.query(q, k=self.t["k"], chunk=chunk)
        ctx.tracer.note("query", n_probe, chunk)
        with span("wait"):
            hd.result()
            acks = np.asarray(hi.result(), np.int64)
            ids, scores = hq.result()
        self.live = np.delete(self.live, pos)
        self.live = np.concatenate([self.live, new_rows[acks >= 0]])
        self.row_slot[gone] = -1
        self.row_slot[new_rows] = acks
        ctx.log.append(("delete", gone))
        ctx.log.append(("insert", new_rows, acks))
        ctx.log.append(("query", q, ids, scores, in_window))
        return int((acks < 0).sum())


def warm(ctx: Ctx) -> None:
    c = _Churn(ctx)
    n = ctx.traffic["chunk"]
    c.step(n, n, n, False)
    ctx.records["churn"] = c


def window(ctx: Ctx) -> None:
    c: _Churn = ctx.records["churn"]
    t = ctx.traffic
    timers = ctx.session.timers
    disp0 = timers.insert_s + timers.delete_s
    steps, refused = [], 0
    t0 = time.perf_counter()
    while len(steps) < t["max_steps"]:
        if len(steps) == t["trace"]["step"]:
            ctx.tracer.start()
        ts = time.perf_counter()
        refused += c.step(t["deletes"], t["inserts"], t["probes"], True)
        ctx.tracer.stop()
        steps.append((ts, time.perf_counter()))
        if steps[-1][1] - t0 >= ctx.seconds:
            break
    n = len(steps)
    chunks = n * sum(-(-t[x] // t["chunk"]) for x in ("deletes", "inserts"))
    ctx.attempted = n * (t["deletes"] + t["inserts"] + t["probes"])
    ctx.failed = refused
    ctx.records.update(
        window_s=steps[-1][1] - t0,
        items=n * (t["deletes"] + t["inserts"]),
        dispatch_s=timers.insert_s + timers.delete_s - disp0,
        dispatch_chunks=chunks, dispatched=list(ctx.tracer.dispatched))
