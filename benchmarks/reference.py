"""The plain reference: commit verification as upstream CometBFT states it.

Straightforward and independent of the program: one OpenSSL Ed25519 check per
lane in commit order over this benchmark's own sign-bytes (``data.py``), and
the tally rules of ``types/validation.go`` (VerifyCommit: every signature,
more than two thirds of the power; VerifyCommitLight: commit-flag lanes in
order, stopping once the tally passes two thirds).  It imports nothing of the
program.  A verdict is a tuple:

    ("ok", lanes_verified)          the entry must return normally
    ("bad_sig", lane)               ErrInvalidSignature naming that lane
    ("bad_item", height, lane)      ErrBatchItemInvalid naming that height

``control(...)`` gives a stand-in for the program's entries that breaks ONE of
the configuration's guarantees; a run that drives it must come out as not
correct (``--control``, tests/benchmark).
"""

from __future__ import annotations

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey

from benchmarks import data


class Reference:
    def __init__(self, ring: data.Ring, skip_signatures: bool = False):
        self.ring = ring
        self.keys = [Ed25519PublicKey.from_public_bytes(p) for p in ring.pubs]
        self.needed = sum(ring.powers) * 2 // 3
        self.skip_signatures = skip_signatures      # the control's fault
        self.lanes_checked = 0
        self._memo = {}

    def lane_ok(self, block: data.Block, lane: int) -> bool:
        self.lanes_checked += 1
        if self.skip_signatures:
            return True
        msg = data.vote_sign_bytes(self.ring.chain_id, block.height,
                                   block.block_hash, 1, block.parts_hash,
                                   block.stamps[lane])
        try:
            self.keys[lane].verify(block.sigs[lane], msg)
        except InvalidSignature:
            return False
        return True

    def commit(self, block: data.Block, light: bool) -> tuple:
        """Every validator carries the commit flag here (``assumed``), so
        the two rules differ only in where they stop.  A ring's block is
        what its height and its tampered lane say, so each is judged once."""
        key = (block.height, block.tampered_lane, light)
        if key not in self._memo:
            self._memo[key] = self._commit(block, light)
        return self._memo[key]

    def _commit(self, block: data.Block, light: bool) -> tuple:
        tally = lanes = 0
        for lane, power in enumerate(self.ring.powers):
            if not self.lane_ok(block, lane):
                return ("bad_sig", lane)
            tally, lanes = tally + power, lanes + 1
            if light and tally > self.needed:
                break
        return ("ok", lanes) if tally > self.needed else ("no_quorum",)

    def window(self, blocks: list) -> tuple:
        lanes = 0
        for block in blocks:
            got = self.commit(block, light=True)
            if got[0] != "ok":
                return ("bad_item", block.height) + got[1:]
            lanes += got[1]
        return ("ok", lanes)


def control(name: str, ring: data.Ring):
    """``skip_signatures``: the reference with its signature check left out
    (it tallies flags and power only), as ``entry(kind, blocks)`` with ``kind``
    one of ``light`` / ``full`` / ``window``.  The other control,
    ``host_route``, is the program itself pinned to its host path, which
    answers right and verifies nothing on the device (``run.py``)."""
    if name != "skip_signatures":
        raise SystemExit(f"unknown control {name!r}")
    ref = Reference(ring, skip_signatures=True)

    def entry(kind, blocks):
        return ref.window(blocks) if kind == "window" \
            else ref.commit(blocks[0], light=kind == "light")
    return entry
