"""The plain reference put in the program's place, for the control runs.

``ReferenceSession`` answers the ``Session`` calls the drivers and the
``BatchedServer`` make (insert, delete, query, timers, state) with exact
brute force over its own live slots, in the precision it is given
(harness/reference.py). It imports nothing of the program. bench/calibrate.py
runs whole cells on it:

  control   the reference one rung of precision below the configuration's
            float32 (``high``, ``bf16`` or ``int8``); it has to come out not
            correct, and its smallest reading sets the upper end of a limit
  altered   the reference at full precision with every answer's ids moved
            to other live slots, scores kept: the planted "answer altered"
            fault
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from harness import reference


class _Done:
    def __init__(self, value):
        self._value = value

    def result(self):
        return self._value

    block = result


@dataclasses.dataclass
class _Timers:
    insert_s: float = 0.0
    delete_s: float = 0.0


@dataclasses.dataclass
class _State:
    adj: np.ndarray
    radj: np.ndarray
    alive: np.ndarray
    present: np.ndarray


class ReferenceSession:
    recovering = False

    def __init__(self, capacity: int, dim: int, metric: str, *,
                 precision: str = "highest", altered: bool = False):
        self.rows = np.zeros((capacity, dim), np.float32)
        self.alive = np.zeros(capacity, bool)
        self.metric = metric
        self.precision = precision
        self.altered = altered
        self.timers = _Timers()
        self._dev = None    # device copy of the rows, dropped on a write

    @property
    def state(self) -> _State:
        empty = np.full((self.alive.shape[0], 1), -1, np.int64)
        return _State(empty, empty, self.alive.copy(), self.alive.copy())

    def insert(self, vectors, *, chunk=None):
        v = np.asarray(vectors, np.float32)
        slots = np.flatnonzero(~self.alive)[:v.shape[0]]
        self.rows[slots] = v[:slots.size]
        self.alive[slots] = True
        self._dev = None
        out = np.full(v.shape[0], -1, np.int32)
        out[:slots.size] = slots
        return _Done(out)

    def delete(self, ids, *, chunk=None):
        self.alive[np.asarray(ids, np.int64)] = False
        return _Done(None)

    def query(self, queries, k=None, *, chunk=None):
        if self._dev is None:
            self._dev = jnp.asarray(self.rows)
        s, i = reference.exact_topk(self._dev, self.alive, queries, k,
                                    self.metric, precision=self.precision,
                                    block=max(np.asarray(queries).shape[0], 1))
        if self.altered:
            live = np.flatnonzero(self.alive)
            pos = np.searchsorted(live, i)
            i = live[(pos + 1) % live.size]
        return _Done((i.astype(np.int32), s.astype(np.float32)))
