"""Driver ``extended_commit_loop``: one stored ExtendedCommit a call through
``VerifyExtendedCommit(patient=True)``, as a node does that starts or leaves
blocksync at a height with vote extensions on, over a ring of distinct
extended commits, each unit a fresh program object.  Every ``tamper_every``-th
unit (offset from the seed) is a copy of its ring commit with one bit flipped
in one of its signatures: the validator from the seed, the kind (vote or
extension) alternating along the ring from a seeded start, and successive
tampered units are the tampered copies of successive ring commits."""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import random

from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

from benchmarks import data, loop
from benchmarks import reference_extended as refx

KINDS = ("vote", "extension")
CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "*.json")


def config_of(chain_id: str) -> dict:
    """The configuration file of the ring's chain: the ``Driver`` interface
    hands a driver the ring and the mix, and the extension size is the
    deployment's."""
    for path in sorted(glob.glob(CONFIGS)):
        with open(path) as f:
            cfg = json.load(f)
        if cfg.get("chain_id") == chain_id:
            return cfg
    raise SystemExit(f"no configuration with chain_id {chain_id!r}")


def extend(ring: data.Ring, seed: int, extension_bytes: int) -> list:
    """The ring's commits with a vote extension and its signature on every
    lane.  ``data.make_ring`` keeps no private keys: they are derived again
    from the seed as it derives them."""
    signers = {}
    for i in range(len(ring.pubs)):
        sk = Ed25519PrivateKey.from_private_bytes(data._h("key", seed, i))
        signers[sk.public_key().public_bytes_raw()] = sk
    signers = [signers[p] for p in ring.pubs]
    blocks = []
    for b in ring.blocks:
        exts = tuple(data._h("ext", seed, b.height, i)[:extension_bytes]
                     for i in range(len(signers)))
        ext_sigs = tuple(
            sk.sign(refx.extension_sign_bytes(ring.chain_id, b.height, ext))
            for sk, ext in zip(signers, exts))
        blocks.append(refx.ExtendedBlock(
            b.height, b.block_hash, b.parts_hash, b.stamps, b.sigs,
            extensions=exts, ext_sigs=ext_sigs))
    return blocks


def program_commit(ring: data.Ring, block: refx.ExtendedBlock):
    """The program's ``ExtendedCommit`` of ``block``, as a decode from the
    store gives: new objects, nothing memoised."""
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.types.commit import (CommitSig, ExtendedCommit,
                                           ExtendedCommitSig)

    bid = BlockID(block.block_hash, PartSetHeader(1, block.parts_hash))
    sigs = [ExtendedCommitSig(CommitSig(block.flag(i), a, ts, s), x, xs)
            if block.flag(i) != refx.FLAG_ABSENT else ExtendedCommitSig()
            for i, (a, ts, s, x, xs) in enumerate(zip(
                ring.addresses, block.stamps, block.sigs, block.extensions,
                block.ext_sigs))]
    return ExtendedCommit(block.height, 0, bid, sigs)


class Driver:
    kind = "extended"       # no kind of reference.control: it answers as "full"

    def __init__(self, ring: data.Ring, mix: dict, seed: int):
        from cometbft_tpu.types import validation

        if not hasattr(validation, mix["entry"]):   # before any warm-up
            raise SystemExit(f"the program has no {mix['entry']}: "
                             "this cell cannot run on it")
        self.ring, self.mix = ring, mix
        n = len(ring.powers)
        self.lanes_per_call = 2 * n
        self.clean = extend(ring, seed, config_of(ring.chain_id)["extension_bytes"])
        rng = random.Random(seed)
        self.every = mix["tamper_every"]
        self.offset = rng.randrange(self.every)
        first = rng.randrange(2)
        self.bad = [refx.tamper(b, rng.randrange(n), KINDS[(first + k) % 2])
                    for k, b in enumerate(self.clean)]
        self._ref = None

    def _tampered(self, i: int) -> bool:
        return i % self.every == self.offset

    def units(self, i: int) -> list:
        """Called outside the call's clock: the unit's commit and its fresh
        program object.  A clean unit ``i`` is ring commit ``i``; the ``j``-th
        tampered unit is the tampered copy of ring commit ``j``, so that
        each falls on the next ring commit (and the other kind)."""
        block = self._block(i)
        unit = dataclasses.replace(
            block, fresh=[program_commit(self.ring, block)])
        return [unit]

    def _block(self, i: int) -> refx.ExtendedBlock:
        if self._tampered(i):
            return self.bad[i // self.every % len(self.bad)]
        return self.clean[i % len(self.clean)]

    def prime_units(self) -> list:
        """One clean and one tampered unit, for the pre-window drive."""
        return [self.offset + 1, self.offset]

    def warm_lanes(self, lane_cap: int) -> list:
        return [self.lanes_per_call]

    def call(self, blocks, presented, backend: str) -> tuple:
        from cometbft_tpu.types import validation as V

        bid, height, _ = presented[0]
        # a unit's object is used once; run.py's host-path line calls one
        # unit several times, and builds its later objects inside its clock
        fresh = blocks[0].fresh
        commit = fresh.pop() if fresh else program_commit(self.ring, blocks[0])
        try:
            V.VerifyExtendedCommit(self.ring.chain_id, self.ring.vals, bid,
                                   height, commit, backend=backend,
                                   patient=self.mix["patient"])
        except V.ErrInvalidExtensionSignature as e:
            return ("bad_sig", e.idx, "extension")
        except V.ErrInvalidSignature as e:
            return ("bad_sig", e.idx, "vote")
        except V.CommitVerificationError as e:
            return ("refused", type(e).__name__)
        return ("ok", self.lanes_per_call)

    def expected(self, ref, blocks) -> tuple:
        """The benchmark's extended reference judges; ``ref`` (the commit
        reference ``run.py`` made) only carries the count of lanes checked
        into the ``reference`` line."""
        if self._ref is None:
            self._ref = refx.Reference(self.ring)
        before = self._ref.lanes_checked
        want = self._ref.commit(blocks[0])
        ref.lanes_checked += self._ref.lanes_checked - before
        return want

    def end_to_end(self, calls, t0: float, seconds: float) -> dict:
        ms = [(c.end - c.start) * 1e3 for c in calls]
        return {"commit_verify_p50_ms": loop.percentile(ms, 50)}

    def rate_line(self, calls, t0: float, t_end: float) -> dict:
        bad = [self._block(c.unit).tampered_kind for c in calls
               if self._tampered(c.unit)]
        return {"calls": len(calls), "commits_per_s": len(calls) / (t_end - t0),
                "sigs_per_s": len(calls) * self.lanes_per_call / (t_end - t0),
                "tampered_calls": len(bad),
                "tampered_kinds": sorted(set(bad))}
