"""The system under test, built through the entry points users call.

The benchmark takes from the program only what it serves: the compiler
(``compile_bnn``), the lowering, and ``run`` with its ``ExecutionPlan``.
Weights and packets come from the benchmark (``reference.make_weights``,
``traffic/``).
"""
from __future__ import annotations

import time
from typing import Callable, Iterator

import numpy as np


def plan(fields: dict):
    """The cell's ``ExecutionPlan``; ``backend`` defaults to ``auto``."""
    from repro.dataplane import ExecutionPlan

    return ExecutionPlan(**fields)


def lowered(weights):
    """One model's weights compiled and lowered for the executors."""
    from repro.core import compile_bnn
    from repro.dataplane import lower_program

    return lower_program(compile_bnn([np.asarray(w) for w in weights]))


def run(program, stream, run_plan):
    """``repro.dataplane.run``: the entry every cell drives."""
    from repro.dataplane import run as _run

    return _run(program, stream, plan=run_plan)


class Replay:
    """Replays a pool cyclically in slices of ``chunk`` rows.

    ``arrays`` share their first axis (the pool's length, a multiple of
    ``chunk``).  Each slice is a view, so the source adds no copy of its
    own; ``offered`` counts the rows handed out.
    """

    def __init__(self, arrays: tuple, chunk: int, annotate: Callable):
        n = arrays[0].shape[0]
        if n % chunk:
            raise ValueError(f"pool of {n} rows is not a multiple of chunk {chunk}")
        self.arrays = arrays
        self.chunk = chunk
        self.annotate = annotate
        self.offered = 0

    def slices(self, start: int, stop: Callable[[], bool]) -> Iterator:
        """Slices from row ``start`` onward until ``stop()`` is true."""
        n = self.arrays[0].shape[0]
        i = start % n
        while True:
            with self.annotate("source.next"):
                if stop():
                    return
                out = tuple(a[i : i + self.chunk] for a in self.arrays)
                i = (i + self.chunk) % n
                self.offered += self.chunk
            yield out if len(out) > 1 else out[0]


def timed(annotate: Callable, entry: Callable, arg):
    """``entry(arg)`` as the window's one call: its result and wall
    seconds."""
    t0 = time.perf_counter()
    with annotate("entry.run"):
        result = entry(arg)
    return result, time.perf_counter() - t0


def deadline(seconds: float) -> Callable[[], bool]:
    """A stop test that turns true ``seconds`` from now."""
    end = time.perf_counter() + seconds
    return lambda: time.perf_counter() >= end


def after(calls: int) -> Callable[[], bool]:
    """A stop test that turns true on its ``calls + 1``-th call."""
    left = [calls]

    def stop() -> bool:
        left[0] -= 1
        return left[0] < 0

    return stop


def wrong_bits(got, ref: np.ndarray, start: int) -> int:
    """Verdict bits of ``got`` that differ from ``ref`` replayed
    cyclically from row ``start``: compared one pool length at a time, so
    the comparison needs no copy of the whole window's verdicts."""
    got = np.asarray(got)
    n = ref.shape[0]
    if got.ndim != 2 or got.shape[1] != ref.shape[1]:
        return int(ref.shape[1] * got.shape[0]) if got.ndim else 0
    ring = np.concatenate([ref[start % n :], ref[: start % n]])
    wrong = 0
    for lo in range(0, got.shape[0], n):
        part = got[lo : lo + n]
        wrong += int(np.count_nonzero(part != ring[: part.shape[0]]))
    return wrong
