"""Time in the program's spans ``sub``/``name``, summed a call: over the
entry calls (``types.validation/verify`` spans) that lie whole in the traced
window, the spans they caused, a call.  ``queue`` is the time work waited
for the device-owner thread, ``rows`` the per-commit loop that builds a
dispatch's rows."""

from __future__ import annotations

from benchmarks.reduce import program_spans


def reduce(ctx, sub, name):
    spans = program_spans.read(ctx)
    if spans is None:
        return None
    calls = {s.id for s in spans.whole(program_spans.VALIDATION, "verify")}
    mine = [s for s in spans.of(sub, name) if spans.root(s).id in calls]
    if not calls or not mine:
        return None
    return 1e3 * sum(s.end - s.start for s in mine) / len(calls)
