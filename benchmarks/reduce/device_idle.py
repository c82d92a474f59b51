"""Share of the traced window in which no operation ran on the chip."""

from __future__ import annotations


def busy_and_window(trace) -> tuple:
    """(seconds busy, averaged over the chips used; seconds of window)."""
    lo, hi = trace.window
    if not trace.chips:
        return None, hi - lo
    busy = sum(trace.busy[c].covered(lo, hi) for c in trace.chips)
    return busy / len(trace.chips), hi - lo


def reduce(ctx, **args):
    busy, window = busy_and_window(ctx["trace"])
    if busy is None or window <= 0:
        return None
    return 100.0 * (1.0 - busy / window)
