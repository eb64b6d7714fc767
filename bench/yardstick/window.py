"""The closed loop: submit one DAG, wait for its exit outputs, submit the
next, until the window's seconds have passed.  Rate and tail are taken over
every DAG of the window and over all of its time."""

from __future__ import annotations

import dataclasses
import math
import time
import traceback
from typing import Callable


@dataclasses.dataclass
class Window:
    latencies_s: list[float]  # one per submitted DAG, failed ones included
    failed: list[int]  # indices (in submission order) of DAGs that raised
    seconds: float  # first submission to the last DAG's return
    errors: list[str]

    @property
    def attempted(self) -> int:
        return len(self.latencies_s)

    @property
    def completed(self) -> int:
        return self.attempted - len(self.failed)

    def rate(self) -> float:
        return self.completed / self.seconds if self.seconds > 0 else 0.0

    def percentile_ms(self, q: float) -> float:
        return 1e3 * nearest_rank(self.latencies_s, q)


def nearest_rank(values: list[float], q: float) -> float:
    """The q-th percentile by nearest rank: the smallest value with at least
    q percent of the values at or below it."""
    if not values:
        raise ValueError("no values")
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q / 100.0 * len(ordered)) - 1)]


def closed_loop(
    submit: Callable[[int], None],
    seconds: float,
    *,
    first: int = 0,
    prepare: Callable[[int], None] = lambda i: None,
    clock: Callable[[], float] = time.perf_counter,
) -> Window:
    """Call ``submit(i)`` for i = first, first + 1, ... back to back until
    ``seconds`` have passed since the first call.  ``submit`` returns once
    the DAG's exit outputs are ready; one that raises counts as failed.
    ``prepare(i)`` builds DAG i before its latency clock starts; its time
    is in the window's."""
    lat: list[float] = []
    failed: list[int] = []
    errors: list[str] = []
    t0 = clock()
    i = first
    while True:
        if clock() - t0 >= seconds:
            break
        prepare(i)
        s = clock()
        try:
            submit(i)
        except Exception:
            failed.append(len(lat))
            if len(errors) < 3:
                errors.append(traceback.format_exc(limit=6))
        lat.append(clock() - s)
        i += 1
    return Window(lat, failed, clock() - t0, errors)
