"""Real lanes over padded lanes for the window's dispatches.  The padded
shape of a dispatch is the program's plan's business (``crypto/plan.py``:
chunks at the lane cap, each chunk up to its bucket), so this reads the plan
the run had, with the lanes the harness sent."""

from __future__ import annotations


def padded_lanes(lanes: int, lane_cap: int, bucket_of) -> int:
    full, rest = divmod(lanes, lane_cap)
    return full * bucket_of(lane_cap) + (bucket_of(rest) if rest else 0)


def reduce(ctx, **args):
    from cometbft_tpu.crypto import plan

    if not ctx["calls"]:
        return None
    cap = plan.active().lane_buckets[-1]
    real = ctx["lanes_per_call"]
    return 100.0 * real / padded_lanes(
        real, cap, lambda n: plan.chunk_bucket(n, ()))
