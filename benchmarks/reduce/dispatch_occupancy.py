"""Real lanes over padded lanes of the window's dispatches, counted where the
padding is done: each ``launch`` span carries the ``lanes`` it holds and the
``bucket`` it was padded to, the ``gather`` dispatch that localizes a refuted
batch included (``lane_occupancy_pct`` computes the same from the plan and
the lanes the harness sent, and cannot see those)."""

from __future__ import annotations

from benchmarks.reduce import program_spans


def reduce(ctx, **args):
    spans = program_spans.read(ctx)
    launches = spans.whole(program_spans.SEAM, "launch") if spans else []
    padded = sum(s.attrs["bucket"] for s in launches)
    if not padded:
        return None
    return 100.0 * sum(s.attrs["lanes"] for s in launches) / padded
