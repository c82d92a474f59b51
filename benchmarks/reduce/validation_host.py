"""Commit verification's own host time a call: the entry's span minus the
``verify_dense`` spans inside it (scope selection, sign-bytes rows, column
views, the tally), averaged over the window's calls."""

from __future__ import annotations


def reduce(ctx, **args):
    trace = ctx["trace"]
    entries = trace.spans_named("bench:entry")
    if not entries:
        return None
    inner = sum(e - s for s, e in trace.spans_named("bench:verify_dense"))
    return 1e3 * (sum(e - s for s, e in entries) - inner) / len(entries)
