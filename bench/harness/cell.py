"""One run of one cell: set-up, the measured window, the check, the metrics.

    set-up   generate the corpus and the traffic from the seed, build the
             index through ``Session.insert``, warm the cell's own shapes
    window   the traffic's driver runs for ``seconds`` (bench/drivers/)
    check    read the device's peak memory, copy the index state to the
             host, free the session, then judge every answer and the state
             against the plain reference (harness/check.py)
    metrics  the cell's end-to-end metrics (untraced run) or per-layer
             metrics (traced run), each from its own file

The program is imported only here and in the drivers; the reference and the
check import nothing of it.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import shutil
import sys
import time
from pathlib import Path

import jax
import numpy as np

from harness import check, device, registry, xplane


def index_params(cfg: dict):
    from repro.core.params import IndexParams, MaintenanceParams, SearchParams

    ix = dict(cfg["index"])
    search = SearchParams(**ix.pop("search", {}))
    ins = ix.pop("insert_search", None)
    return IndexParams(
        search=search,
        insert_search=SearchParams(**ins) if ins is not None else None,
        maintenance=MaintenanceParams(**ix.pop("maintenance", {})), **ix)


class Tracer:
    """Starts and stops the profiler around the slice a driver chooses, and
    keeps the order of the op steps dispatched inside it (one entry per
    ``apply_ops_step`` execution) for matching against the trace."""

    def __init__(self, directory: Path | None):
        self.directory = directory
        self.on = False
        self.dispatched: list[str] = []
        self._ann = None

    @property
    def wanted(self) -> bool:
        return self.directory is not None

    def start(self) -> None:
        if not self.wanted or self.on:
            return
        # device ops and the benchmark's own spans; no Python-call tracing,
        # which slows the host several times over
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.directory), profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(xplane.WINDOW)
        self._ann.__enter__()
        self.on = True

    def stop(self) -> None:
        if not self.on:
            return
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on = False

    def note(self, op: str, rows: int, chunk: int) -> None:
        if self.on:
            self.dispatched += [op] * math.ceil(rows / chunk)


def span(name: str):
    return jax.profiler.TraceAnnotation(f"bench.{name}")


@dataclasses.dataclass
class Ctx:
    """What a driver works with. ``log`` is the benchmark's own record of
    every acknowledged op, in order:

        ("insert", rows, acks)                 rows of ``corpus``, slot ids
        ("delete", rows)
        ("query", queries, ids, scores, in_window)
    """

    cell: registry.Cell
    seed: int
    seconds: float
    tracer: Tracer
    rng: np.random.Generator
    corpus: np.ndarray = None
    session: object = None
    log: list = dataclasses.field(default_factory=list)
    records: dict = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def gen(self, n: int) -> np.ndarray:
        g = self.config["generator"]
        return self.plugin("generators", g["kind"]).make(g, n, self.rng)

    def plugin(self, kind: str, name: str):
        return registry.plugin(kind, name, self.cell.bench_dir)

    def add_rows(self, x: np.ndarray) -> np.ndarray:
        """Append rows to the corpus; returns their row numbers."""
        lo = 0 if self.corpus is None else self.corpus.shape[0]
        self.corpus = x if self.corpus is None else np.concatenate(
            [self.corpus, x])
        return np.arange(lo, lo + x.shape[0])


def program_session(ctx: Ctx):
    from repro.core.session import Session

    return Session(index_params(ctx.config), seed=ctx.seed % 2**31,
                   **ctx.config.get("session", {}))


def build(ctx: Ctx, make_session=program_session) -> None:
    """The index as a deployment holds it: the base rows inserted through
    the program's own ``Session.insert``, in chunks of the configured
    width. ``make_session`` puts something else in the program's place
    (the control runs of bench/calibrate.py)."""
    b = ctx.config["build"]
    rows = ctx.add_rows(ctx.gen(b["rows"]))
    ctx.session = make_session(ctx)
    acks = ctx.session.insert(ctx.corpus[rows], chunk=b["chunk"]).result()
    ctx.log.append(("insert", rows, acks))


def judge(ctx: Ctx, state: dict) -> tuple[dict, np.ndarray]:
    """Replay the op log over the benchmark's own slot->row record and
    judge every query at its point, then the state after the window.

    Returns the numbers and the recall of each in-window query."""
    cap, k = state["alive"].shape[0], ctx.traffic["k"]
    metric = ctx.config["index"]["metric"]
    slot_row = np.full(cap, -1, np.int64)
    live = np.zeros(ctx.corpus.shape[0], bool)
    recall, lost = [], 0
    nums = {"bad_rows": 0, "dead_ids": 0, "score_gap": 0.0, "hits": 0,
            "slots": 0}
    pending: list = []

    def flush_queries():
        if not pending:
            return
        q = np.concatenate([p[1] for p in pending])
        ids = np.concatenate([p[2] for p in pending])[:, :k]
        sc = np.concatenate([p[3] for p in pending])[:, :k]
        win = np.concatenate([np.full(p[1].shape[0], p[4]) for p in pending])
        rows = np.where(ids >= 0, slot_row[np.clip(ids, 0, cap - 1)], -1)
        got, rec = check.answers(ctx.corpus, q, ids, rows, sc, live, k, metric)
        for key in ("bad_rows", "dead_ids", "hits", "slots"):
            nums[key] += got[key]
        nums["score_gap"] = max(nums["score_gap"], got["score_gap"])
        recall.append(rec[win])
        pending.clear()

    for entry in ctx.log:
        if entry[0] == "query":
            pending.append(entry)
            continue
        flush_queries()
        if entry[0] == "insert":
            _, rows, acks = entry
            acks = np.asarray(acks, np.int64)
            ok = (acks >= 0) & (acks < cap)
            # an ack is lost when NULL, out of range, repeated within the
            # op, or a slot that still holds a live row
            taken = slot_row[np.where(ok, acks, 0)] >= 0
            lost += int((~ok).sum() + (ok & taken).sum()
                        + check.repeats(acks[ok]))
            slot_row[acks[ok]] = rows[ok]
            live[rows[ok]] = True
        else:
            _, rows = entry
            live[rows] = False
            slot_row[np.isin(slot_row, rows)] = -1
    flush_queries()
    expect = slot_row >= 0
    out = {"unanswered": ctx.failed,
           "bad_rows": nums["bad_rows"], "dead_ids": nums["dead_ids"],
           "score_gap": nums["score_gap"],
           "miss_rate": 1.0 - nums["hits"] / max(nums["slots"], 1),
           "lost_inserts": lost}
    out.update(check.graph(state["adj"], state["radj"], state["alive"],
                           state["present"], expect))
    return out, (np.concatenate(recall) if recall else np.zeros(0))


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        *, t_start: float, check_device: bool = True,
        keep_trace: bool = False, make_session=program_session,
        report=print, records: dict | None = None) -> dict:
    """One run; returns the result object (the run's last line).
    ``records``, when given, receives what the metrics were read from."""
    cell = registry.resolve(root, workload)
    import repro.core.session  # noqa: F401  (fail before any work without it)

    devices = jax.devices()
    if check_device:
        dev, peaks = device.check(devices, cell.chips)
    else:  # tests drive the rest of a run on the CPU
        dev = {"platform": devices[0].platform,
               "kind": devices[0].device_kind, "count": len(devices)}
        peaks = device.peaks_for("TPU v5 lite")
    trace_dir = cell.bench_dir / "out" / "trace" if trace else None
    if trace_dir is not None and trace_dir.exists():
        shutil.rmtree(trace_dir)
    ctx = Ctx(cell, seed, float(seconds), Tracer(trace_dir),
              np.random.default_rng(seed % 2**64))
    drv = ctx.plugin("drivers", cell.traffic["driver"])

    drv.prepare(ctx)
    build(ctx, make_session)
    drv.warm(ctx)
    # set-up's objects are never scanned again: a collection inside the
    # window walks only what the window allocates
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    report(f"setup_s {setup_s:.3f}", file=sys.stderr)

    pauses = _GcPauses()
    drv.window(ctx)
    ctx.tracer.stop()
    pauses.close()
    gc.unfreeze()
    report(f"gc in window: {pauses.n} collections, longest "
           f"{pauses.longest * 1e3:.1f} ms", file=sys.stderr)
    mem = device.memory_peak(devices)
    st = ctx.session.state
    state = {f: np.asarray(getattr(st, f))
             for f in ("adj", "radj", "alive", "present")}
    ctx.session = st = None
    gc.collect()

    numbers, recall = judge(ctx, state)
    limits = ctx.config.get("limits", {})
    correct, checks = check.verdict(numbers, limits)
    ctx.records.update(setup_s=setup_s, recall=recall, peaks=peaks)
    dev = dict(dev, memory_peak_bytes=mem)
    result = {"correct": correct, "attempted": ctx.attempted,
              "failed": ctx.failed}
    if trace:
        tr = xplane.read(xplane.find(str(trace_dir)))
        ctx.records["trace"] = tr
        metrics = _metrics(ctx, cell.per_layer, "layer_metrics")
        dev.update(busy_s=tr.busy_s(), window_s=tr.window_s)
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.gaps(10)}
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics = _metrics(ctx, cell.end_to_end, "end_to_end")
    result.update(metrics=metrics, device=dev, checks=checks)
    if records is not None:
        records.update(ctx.records)
    for name, c in checks.items():
        report(f"check {name} {c['value']!r} limit {c['limit']!r}",
               file=sys.stderr)
    return result


class _GcPauses:
    """Counts the interpreter's collections and the longest one."""

    def __init__(self):
        self.n, self.longest, self._t = 0, 0.0, 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.n += 1
            self.longest = max(self.longest, time.perf_counter() - self._t)

    def close(self) -> None:
        gc.callbacks.remove(self._cb)


def _metrics(ctx: Ctx, entries: list[dict], kind: str) -> dict:
    out = {}
    for m in entries:
        value = ctx.plugin(kind, m["name"]).read(ctx.records)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
