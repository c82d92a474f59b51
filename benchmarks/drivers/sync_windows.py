"""Driver ``sync_windows``: ``window_blocks`` consecutive commits a call
through ``verify_commits_light_batched(..., patient=True)``, as the blocksync
reactor's double buffer calls it; every ``tamper_every``-th window presented
holds one tampered commit (block and lane drawn from the seed and the window's
number), applied to a copy as the window is presented.  WHICH windows are
tampered is the same for every seed, so that no seed gives a run more work."""

from __future__ import annotations

import random

from benchmarks import data


class Driver:
    kind = "window"

    def __init__(self, ring: data.Ring, mix: dict, seed: int):
        self.ring, self.mix, self.seed = ring, mix, seed
        self.w = mix["window_blocks"]
        self.n_windows = len(ring.blocks) // self.w
        self.light = data.light_lanes(ring.powers)
        self.lanes_per_call = self.w * self.light
        self.offset = mix["tamper_every"] // 2

    def _tamper_at(self, i: int):
        if i % self.mix["tamper_every"] != self.offset:
            return None
        rng = random.Random(self.seed * 1_000_003 + i)
        return rng.randrange(self.w), rng.randrange(self.light)

    def units(self, i: int) -> list:
        k = (i % self.n_windows) * self.w
        blocks = self.ring.blocks[k:k + self.w]
        hit = self._tamper_at(i)
        if hit is not None:
            blocks[hit[0]] = data.tamper(blocks[hit[0]], hit[1])
        return blocks

    def prime_units(self) -> list:
        return [self.offset + 1, self.offset]

    def warm_lanes(self, lane_cap: int) -> list:
        """A window past the lane cap is dispatched in cap-sized chunks and a
        remainder, each its own compiled shape."""
        n = self.lanes_per_call
        return [n] if n <= lane_cap else [lane_cap] + [n % lane_cap] * (n % lane_cap > 0)

    def call(self, blocks, presented, backend: str) -> tuple:
        from cometbft_tpu.types import validation as V

        try:
            n = V.verify_commits_light_batched(
                self.ring.chain_id, self.ring.vals, presented, backend=backend,
                patient=True)
        except V.ErrBatchItemInvalid as e:
            return ("bad_item", e.height, getattr(e.cause, "idx", None))
        except V.CommitVerificationError as e:
            return ("refused", type(e).__name__)
        return ("ok", n)

    def expected(self, ref, blocks) -> tuple:
        return ref.window(blocks)

    def end_to_end(self, calls, t0: float, seconds: float) -> dict:
        """Blocks of the windows that returned, over the time to the first
        return past ``seconds``.  A window still in flight then has lost the
        caller it overlapped with: it is waited for and judged like the rest,
        but its lone tail (up to a window's length, by the phase the deadline
        happens to fall in) is neither work nor time of the rate."""
        ends = sorted(c.end for c in calls)
        close = next((e for e in ends if e >= t0 + seconds), ends[-1])
        return {"sync_blocks_per_s":
                sum(e <= close for e in ends) * self.w / (close - t0)}

    def rate_line(self, calls, t0: float, t_end: float) -> dict:
        return {"windows": len(calls),
                "window_s_mean": sum(c.end - c.start for c in calls) / len(calls),
                "sigs_per_s_to_last_return":
                    len(calls) * self.lanes_per_call / (t_end - t0),
                "tampered_windows": sum(
                    self._tamper_at(c.unit) is not None for c in calls)}
