"""The program's host spans (core/trace.py): what a profiler records around
the batcher, Session dispatch and OpHandle, read back with ProfileData, and
what the helper adds to the timers with no profiler running."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.core.params import IndexParams, MaintenanceParams, SearchParams
from repro.core.session import PhaseTimers, Session
from repro.core.trace import span
from repro.serving.batcher import BatchedServer, ServeConfig

DIM, CHUNK = 8, 16


def _session():
    p = IndexParams(
        capacity=192, dim=DIM, d_out=6,
        search=SearchParams(pool_size=16, max_steps=48, num_starts=2),
        maintenance=MaintenanceParams(strategy="global", insert_chunk=CHUNK,
                                      delete_chunk=CHUNK))
    return Session(p, seed=0)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    return (rng.normal(size=(100, DIM)).astype(np.float32),
            rng.normal(size=(20, DIM)).astype(np.float32))


def _ipgm_spans(trace_dir):
    """[(name, parent name or None, args)] of the ``ipgm.`` spans, in start
    order, nested per thread line."""
    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = sorted((e.start_ns, -e.duration_ns, e.name,
                             dict(e.stats)) for e in line.events
                            if e.name.startswith("ipgm."))
            stack = []
            for start, neg_dur, name, args in events:
                while stack and start >= stack[-1][1]:
                    stack.pop()
                out.append((name, stack[-1][0] if stack else None, args))
                stack.append((name, start - neg_dur))
    return out


def _children(spans, parent):
    return [n for n, p, _ in spans if p == parent]


def test_serve_step_writes_nested_spans(tmp_path, data):
    base, queries = data
    sess = _session()
    sess.insert(base).result()
    srv = BatchedServer(sess, ServeConfig(max_batch=CHUNK, max_wait_s=0.002,
                                          k=5))
    srv.submit(queries[0])
    srv.step()  # compiles the query step outside the trace
    for q in queries[1:4]:
        srv.submit(q)
    with jax.profiler.trace(str(tmp_path)):
        got = srv.step()
    assert len(got) == 3
    spans = _ipgm_spans(tmp_path)
    steps = [(p, a) for n, p, a in spans if n == "ipgm.serve.step"]
    assert steps == [(None, {"step": 1, "rid0": 1, "n": 3})]
    assert _children(spans, "ipgm.serve.step") == [
        "ipgm.serve.drain", "ipgm.serve.pad", "ipgm.session.query",
        "ipgm.handle.wait", "ipgm.handle.fetch", "ipgm.handle.fetch",
        "ipgm.serve.scatter"]
    # three requests in hand, room for sixteen: the drain holds for more
    assert _children(spans, "ipgm.serve.drain") == ["ipgm.serve.hold"]
    assert _children(spans, "ipgm.session.query") == [
        "ipgm.session.enqueue", "ipgm.session.step"]
    assert _children(spans, "ipgm.session.step") == ["ipgm.session.enqueue"]
    (args,) = [a for n, _, a in spans if n == "ipgm.session.step"]
    assert args == {"op": "query", "chunk": 0}


def test_idle_server_step_writes_no_span(tmp_path, data):
    sess = _session()
    sess.insert(data[0]).result()
    srv = BatchedServer(sess, ServeConfig(max_batch=CHUNK, k=5))
    with jax.profiler.trace(str(tmp_path)):
        assert srv.step() == {}
    assert _ipgm_spans(tmp_path) == []


def test_session_ops_write_nested_spans(tmp_path, data):
    base, queries = data
    sess = _session()
    ids = sess.insert(base[:60]).result()
    sess.delete(ids[:20]).result()
    sess.query(queries).result()  # every shape compiled outside the trace
    with jax.profiler.trace(str(tmp_path)):
        new = sess.insert(base[60:]).result()          # 40 rows: 3 steps
        sess.delete(np.concatenate([ids[20:40], new[:4]])).result()  # 2
        sess.query(queries).result()                   # 20 queries: 2
    spans = _ipgm_spans(tmp_path)
    for op, n in (("insert", 3), ("delete", 2), ("query", 2)):
        # the op key's fold is a device call too: an enqueue of its own
        assert _children(spans, f"ipgm.session.{op}") == (
            ["ipgm.session.enqueue"] + ["ipgm.session.step"] * n)
        steps = [a for name, p, a in spans
                 if name == "ipgm.session.step" and a["op"] == op]
        assert [a["chunk"] for a in steps] == list(range(n))
    assert _children(spans, "ipgm.session.step") == (
        ["ipgm.session.enqueue"] * 7)
    roots = [n for n, p, _ in spans if p is None]
    assert roots == (["ipgm.session.insert"] + ["ipgm.handle.wait",
                     "ipgm.handle.fetch"] * 3 + ["ipgm.handle.fetch"]
                     + ["ipgm.session.delete"] + ["ipgm.handle.wait"] * 2
                     + ["ipgm.session.query"] + ["ipgm.handle.wait",
                     "ipgm.handle.fetch"] * 2 + ["ipgm.handle.fetch"])


def test_span_feeds_acc_without_profiler():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    timers = PhaseTimers()
    stats = {"hold_s": 0.0}
    ticks = iter([1.0, 3.5, 10.0, 10.25])
    with span("session.query", timers, "query_s",
              clock=lambda: next(ticks), op="query") as ann:
        ann.set_metadata(n=3)
    with span("serve.hold", stats, "hold_s", clock=lambda: next(ticks)):
        pass
    assert timers.query_s == 2.5 and stats["hold_s"] == 0.25
    with pytest.raises(RuntimeError):
        with span("session.insert", timers, "insert_s"):
            raise RuntimeError("a block that raises adds nothing")
    assert timers.insert_s == 0.0
    with span("journal.append"):  # no accumulator: a span alone
        pass


def test_phase_timers_gain_only_enqueue_s(data):
    base, queries = data
    sess = _session()
    ids = sess.insert(base).result()
    sess.delete(ids[:10]).result()
    sess.query(queries).result()
    t = sess.flush()
    before = {
        "query_s", "insert_s", "delete_s", "rebuild_s", "consolidate_s",
        "grow_s", "merge_s", "refine_s", "flush_s", "wall_s", "n_queries",
        "n_inserts", "n_deletes", "n_consolidated", "n_consolidations",
        "n_refused", "n_grows", "n_rejected", "n_retries", "n_merges",
        "n_merged", "n_refines", "n_refined", "n_ops", "total_s", "n_items",
        "ops_per_s"}
    assert set(t.to_dict()) == before | {"enqueue_s"}
    assert 0 < t.enqueue_s <= t.query_s + t.insert_s + t.delete_s
    assert t.total() == pytest.approx(
        t.query_s + t.insert_s + t.delete_s + t.flush_s, abs=0.0)


def test_traced_handle_returns_what_an_untraced_one_does(tmp_path, data):
    base, queries = data
    plain, traced = _session(), _session()  # one seed: one key chain
    for sess in (plain, traced):
        sess.insert(base).result()
    want = plain.query(queries, k=5).result()
    h = traced.query(queries, k=5)
    with jax.profiler.trace(str(tmp_path)):
        got = h.result()
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    names = [n for n, _, _ in _ipgm_spans(tmp_path)]
    assert names == ["ipgm.handle.wait", "ipgm.handle.fetch"] * 2 + [
        "ipgm.handle.fetch"]
