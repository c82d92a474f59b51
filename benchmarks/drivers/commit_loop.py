"""Driver ``commit_loop``: one commit a call, through ``VerifyCommitLight`` or
``VerifyCommit`` (the traffic file's ``entry``), over a ring of distinct
commits of which every ``tamper_every``-th has one signature bit flipped."""

from __future__ import annotations

import random

from benchmarks import data, loop

ENTRIES = {"VerifyCommitLight": "light", "VerifyCommit": "full"}


class Driver:
    def __init__(self, ring: data.Ring, mix: dict, seed: int):
        self.ring, self.mix = ring, mix
        self.kind = ENTRIES[mix["entry"]]
        self.lanes_per_call = data.light_lanes(ring.powers) \
            if self.kind == "light" else len(ring.powers)
        rng = random.Random(seed)
        every = mix["tamper_every"]
        self.blocks = list(ring.blocks)
        for k in range(rng.randrange(every), len(self.blocks), every):
            self.blocks[k] = data.tamper(self.blocks[k],
                                         rng.randrange(self.lanes_per_call))

    def units(self, i: int) -> list:
        return [self.blocks[i % len(self.blocks)]]

    def prime_units(self) -> list:
        """One clean and one tampered unit, for the pre-window drive."""
        bad = next(k for k, b in enumerate(self.blocks) if b.tampered_lane >= 0)
        return [(bad + 1) % len(self.blocks), bad]

    def warm_lanes(self, lane_cap: int) -> list:
        return [self.lanes_per_call]

    def call(self, blocks, presented, backend: str) -> tuple:
        from cometbft_tpu.types import validation as V

        bid, height, commit = presented[0]
        try:
            if self.kind == "light":
                V.VerifyCommitLight(self.ring.chain_id, self.ring.vals, bid,
                                    height, commit, backend=backend,
                                    use_cache=False)
            else:
                V.VerifyCommit(self.ring.chain_id, self.ring.vals, bid, height,
                               commit, backend=backend)
        except V.ErrInvalidSignature as e:
            return ("bad_sig", e.idx)
        except V.CommitVerificationError as e:
            return ("refused", type(e).__name__)
        return ("ok", self.lanes_per_call)

    def expected(self, ref, blocks) -> tuple:
        return ref.commit(blocks[0], light=self.kind == "light")

    def end_to_end(self, calls, t0: float, seconds: float) -> dict:
        ms = [(c.end - c.start) * 1e3 for c in calls]
        return {"commit_verify_p50_ms": loop.percentile(ms, 50),
                "commit_verify_p95_ms": loop.percentile(ms, 95)}

    def rate_line(self, calls, t0: float, t_end: float) -> dict:
        return {"calls": len(calls), "commits_per_s": len(calls) / (t_end - t0),
                "tampered_calls": sum(
                    self.units(c.unit)[0].tampered_lane >= 0 for c in calls)}
