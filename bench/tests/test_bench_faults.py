"""A run with the timed path broken underneath comes out not correct.

Each fault is planted in the program's op step (``apply_ops_step``, the
session's only device entry) and the rest of a run is driven as the
benchmark drives it, on the CPU at a tiny size, without the look for a
chip. The cells have no exchange between chips, so that fault does not
apply to them.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import pytest

from harness import cell

NULL = -1


def _plant(monkeypatch, fault: str):
    from repro.core import ops

    real = ops.apply_ops_step

    def broken(state, batch, key, params, strategy, static_op=None):
        op = static_op
        if fault == "state_unchanged" and op in (ops.OP_INSERT, ops.OP_DELETE):
            B, K = batch.payload.shape[0], params.search.pool_size
            ids = jnp.full((B, K), NULL, jnp.int32)
            if op == ops.OP_INSERT:   # acknowledged, as a real step would
                ids = ids.at[:, 0].set(jnp.arange(B, dtype=jnp.int32))
            return state, ids, jnp.full((B, K), -jnp.inf, jnp.float32)
        state, ids, scores = real(state, batch, key, params, strategy,
                                  static_op)
        if op == ops.OP_QUERY and fault == "half_batch":
            half = ids.shape[0] // 2
            ids = ids.at[half:].set(NULL)
            scores = scores.at[half:].set(-jnp.inf)
        if op == ops.OP_QUERY and fault == "answer_altered":
            ids = jnp.where(ids >= 0, (ids + 1) % state.capacity, ids)
        return state, ids, scores

    monkeypatch.setattr(ops, "apply_ops_step", broken)


@pytest.mark.parametrize("workload", ["tiny-l2.serve", "tiny-l2.churn"])
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_planted_fault_is_not_correct(tiny_root, monkeypatch, workload,
                                      fault):
    _plant(monkeypatch, fault)
    res = cell.run(tiny_root, workload, 11, 0.5, False,
                   t_start=time.perf_counter(), check_device=False,
                   report=lambda *a, **k: None)
    assert res["correct"] is False
    failing = [k for k, v in res["checks"].items()
               if v["value"] > v["limit"]]
    assert failing, res["checks"]
