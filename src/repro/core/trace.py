"""Host spans of the program: one profiler annotation and one timer per block.

``span(name, acc, field, **ids)`` names a block ``ipgm.<name>`` in the
profiler's trace through ``jax.profiler.TraceAnnotation``, which records
nothing unless a profiler is running, and then on the device trace's clock.
Given an accumulator it also adds the block's elapsed seconds to it: the
attribute ``field`` of a ``PhaseTimers``, or the key ``field`` of a stats
dict. A block that raises adds nothing. ``ids`` become the span's
arguments; ``with span(...) as ann`` hands back the annotation, whose
``set_metadata`` adds arguments known only inside the block.
"""
from __future__ import annotations

import time
from typing import Callable

from jax.profiler import TraceAnnotation

PREFIX = "ipgm."


class span:
    __slots__ = ("_ann", "_acc", "_field", "_clock", "_t0")

    def __init__(self, name: str, acc=None, field: str | None = None, *,
                 clock: Callable[[], float] = time.perf_counter, **ids):
        # arguments cost an encoding even when nothing records them
        self._ann = (TraceAnnotation(PREFIX + name, **ids)
                     if ids and TraceAnnotation.is_enabled()
                     else TraceAnnotation(PREFIX + name))
        self._acc = acc
        self._field = field
        self._clock = clock

    def __enter__(self) -> TraceAnnotation:
        self._ann.__enter__()
        self._t0 = self._clock()
        return self._ann

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is None and self._acc is not None:
            dt = self._clock() - self._t0
            if isinstance(self._acc, dict):
                self._acc[self._field] += dt
            else:
                setattr(self._acc, self._field,
                        getattr(self._acc, self._field) + dt)
        self._ann.__exit__(exc_type, exc, tb)
