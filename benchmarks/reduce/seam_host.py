"""The backend seam's host time a call: the part of each ``verify_dense``
span in which no operation ran on the chip (padding, coefficient draws,
``device_put``, the hop to the device-owner thread, launching, the readback),
averaged over the window's calls.  Where two callers' spans overlap, the time
the chip spends on the other's window is not this call's host time either."""

from __future__ import annotations


def reduce(ctx, **args):
    trace = ctx["trace"]
    spans = trace.spans_named("bench:verify_dense")
    calls = len(trace.spans_named("bench:entry"))
    if not trace.chips or not spans or not calls:
        return None
    busy = trace.busy[trace.chips[0]]
    return 1e3 * sum((e - s) - busy.covered(s, e) for s, e in spans) / calls
