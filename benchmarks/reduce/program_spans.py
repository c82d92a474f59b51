"""The program's own spans, on the trace's clock (no metric of its own).

The program writes its spans (``cometbft_tpu/libs/tracing``: the backend
seam's ``queue / tables / pack / put / launch / readback`` under
``verify_dense``, commit verification's ``rows`` under ``verify``) into its
flight recorder, which records while a profiler session is live.  The harness
and the program are one process, so a reducer reads the ring itself.

The ring's stamps are ``time.monotonic_ns()``, the window's calls
(``ctx["calls"]``) ``time.perf_counter()``: one clock where both are
``clock_gettime(CLOCK_MONOTONIC)``.  The trace's are seconds from the
profiler session's start.  The two meet in the ``bench:entry`` spans, which
exist on both: ``loop.run`` reads the clock, opens the span, makes the call,
closes the span and reads the clock again, so each kept ``bench:entry`` lies
inside its call, and the offset between the clocks lies between the latest
``call.start - entry.start`` and the earliest ``call.end - entry.end``.
``clip_to_record`` drops entries by their end, so the k-th entry to end is
the k-th call to end.  Where that bracket is empty or wider than ``MEET_S``
(lost host events, another clock) nothing is returned: the metrics are left
out and the gap shows.
"""

from __future__ import annotations

import time
from typing import NamedTuple

SEAM, VALIDATION = "crypto.seam", "types.validation"
MEET_S = 50e-6          # how closely the two clocks must be pinned together


class Span(NamedTuple):
    id: int
    parent: int
    sub: str
    name: str
    start: float        # seconds on the trace's clock
    end: float
    attrs: dict


class Spans:
    """The ring's spans that overlap the traced window, and who caused whom."""

    def __init__(self, spans: list, window: tuple, residual_s: float):
        self.all, self.window, self.residual_s = spans, window, residual_s
        self._by_id = {s.id: s for s in spans}

    def of(self, sub: str, *names: str) -> list:
        """The spans of ``sub`` with one of ``names`` (none given: all)."""
        return [s for s in self.all
                if s.sub == sub and (not names or s.name in names)]

    def whole(self, sub: str, *names: str) -> list:
        """Those that lie in the window from start to end."""
        lo, hi = self.window
        return [s for s in self.of(sub, *names) if lo <= s.start and s.end <= hi]

    def root(self, span: Span) -> Span:
        while span.parent in self._by_id:
            span = self._by_id[span.parent]
        return span


def clock_offset(entries: list, calls: list):
    """``(offset, residual)`` with ``call clock - offset = trace clock``, from
    the kept ``bench:entry`` spans ``[(start, end)]`` and the window's calls;
    ``None`` where no offset puts every kept entry inside its call and pins
    the clocks within ``MEET_S``."""
    if not entries or len(entries) > len(calls):
        return None
    entries = sorted(entries, key=lambda se: se[1])
    calls = sorted(calls, key=lambda c: c.end)
    latest = max(c.start - s for (s, _), c in zip(entries, calls))
    earliest = min(c.end - e for (_, e), c in zip(entries, calls))
    if not 0.0 <= earliest - latest <= MEET_S:
        return None
    return (latest + earliest) / 2, earliest - latest


def same_clock() -> bool:
    return time.get_clock_info("perf_counter").implementation \
        == time.get_clock_info("monotonic").implementation


def read(ctx):
    """The :class:`Spans` of this run (read once a run), or ``None``."""
    if "program_spans" not in ctx:
        ctx["program_spans"] = _read(ctx["trace"], ctx["calls"])
    return ctx["program_spans"]


def _read(trace, calls):
    from cometbft_tpu.libs import tracing

    fit = clock_offset(trace.spans_named("bench:entry"), calls) \
        if same_clock() else None
    if fit is None:
        return None
    offset, residual = fit
    lo, hi = trace.window
    spans = []
    for kind, rid, parent, sub, name, _, t0, t1, attrs in tracing.snapshot():
        start, end = t0 * 1e-9 - offset, t1 * 1e-9 - offset
        if kind == "span" and start < hi and end > lo:
            spans.append(Span(rid, parent, sub, name, start, end, attrs))
    return Spans(spans, (lo, hi), residual) if spans else None
