"""The one general load generator: closed loop, a fixed number of callers.

Each caller takes the next unit of the mix's sequence, presents it as fresh
program objects (outside the call's own clock, inside the window's), makes the
call and keeps its verdict.  The window closes to new units after ``seconds``;
units in flight are waited for, and their return ends the window's clock.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from dataclasses import dataclass


@dataclass
class Call:
    unit: int                  # index into the mix's sequence
    start: float               # perf_counter at the entry call
    end: float                 # ... and at its return
    verdict: tuple


def span(name: str, on: bool):
    """A host span in the profiler's own trace (traced runs only)."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def run(units, present, call, callers: int, seconds: float,
        traced: bool = False) -> tuple[list[Call], float, float]:
    """Returns ``(calls in unit order, window start, last return)``."""
    calls, errors = [], []
    lock = threading.Lock()
    counter = itertools.count()
    t0 = time.perf_counter()
    deadline = t0 + seconds

    def caller() -> None:
        try:
            while True:
                with lock:
                    i = next(counter)
                    if time.perf_counter() >= deadline:
                        return
                blocks = units(i)
                with span("bench:present", traced):
                    presented = [present(b) for b in blocks]
                start = time.perf_counter()
                with span("bench:entry", traced):
                    verdict = call(blocks, presented)
                end = time.perf_counter()
                with lock:
                    calls.append(Call(i, start, end, verdict))
        except BaseException as e:          # re-raised on the main thread
            errors.append(e)

    with span("bench:window", traced):
        if callers == 1:
            caller()
        else:
            threads = [threading.Thread(target=caller, name=f"bench-caller-{k}")
                       for k in range(callers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
    if errors:
        raise errors[0]
    calls.sort(key=lambda c: c.unit)
    return calls, t0, max((c.end for c in calls), default=t0)


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
