"""Share of the traced window in which the chip ran nothing while one of the
named backend-seam spans was open on the host (``names``), or, with
``outside``, while none of them was: the callers were then in ``rows``, in the
tally, or in the harness.  The seam's spans run on the one device-owner
thread and never overlap, so the shares of disjoint sets of names and the
share outside their union add up to ``device_idle_pct`` of the same window."""

from __future__ import annotations

from benchmarks.reduce import program_spans
from benchmarks.reduce.xplane import Intervals


def reduce(ctx, names, outside=False):
    trace, spans = ctx["trace"], program_spans.read(ctx)
    if spans is None or not trace.chips or not spans.of(program_spans.SEAM):
        return None
    lo, hi = trace.window
    gaps = trace.busy[trace.chips[0]].gaps(lo, hi)
    mine = spans.of(program_spans.SEAM, *names)
    idle = 0.0
    if mine:
        open_ = Intervals([max(s.start, lo) for s in mine],
                          [min(s.end, hi) for s in mine])
        idle = sum(gaps.covered(s, e) for s, e in zip(open_.starts, open_.ends))
    if outside:
        idle = gaps.total() - idle
    return 100.0 * idle / (hi - lo)
