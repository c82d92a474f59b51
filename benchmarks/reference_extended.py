"""The plain reference for an extended commit, as upstream CometBFT states it.

``types/block.go`` ``ExtendedCommit.ToExtendedVoteSet`` adds every vote of a
stored extended commit through ``types/vote.go`` ``VerifyVoteAndExtension``:
in validator order the vote signature over CanonicalVote, then for a
for-block precommit the extension signature over CanonicalVoteExtension.
Here that is one OpenSSL Ed25519 check per lane over this benchmark's OWN
bytes of both kinds (``data.vote_sign_bytes`` and
:func:`extension_sign_bytes` below), the rule that extensions sit where
extensions-enabled puts them (a signature on every for-block lane, nothing on
a nil or absent one), and ``VerifyCommit``'s tally (for-block power above two
thirds).  It imports nothing of the program.  A verdict is a tuple:

    ("ok", lanes_verified)              the entry must return normally
    ("bad_sig", validator, kind)        ErrInvalidSignature naming that
                                        validator; kind "vote", or "extension"
                                        for ErrInvalidExtensionSignature
    ("refused", error_name)             ErrInvalidCommit / ErrNotEnoughVotingPower
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey

from benchmarks import data

FLAG_ABSENT, FLAG_COMMIT, FLAG_NIL = 1, 2, 3    # BlockIDFlag (types/block.go)


def extension_sign_bytes(chain_id: str, height: int, extension: bytes) -> bytes:
    """The bytes a validator signs for its round-0 vote extension (upstream
    ``types/vote.go`` VoteExtensionSignBytes: a length-prefixed
    CanonicalVoteExtension with extension=1, height=2 and round=3 as sfixed64,
    chain_id=4; empty and zero fields omitted as proto3 does)."""
    body = ((data._bytes_field(1, extension) if extension else b"")
            + data._sfixed64_field(2, height)
            + data._bytes_field(4, chain_id.encode()))
    return data._varint(len(body)) + body


def nil_vote_sign_bytes(chain_id: str, height: int, ts_ns: int) -> bytes:
    """CanonicalVote of a precommit for nil: no block_id field at all."""
    secs, nanos = divmod(ts_ns, 1_000_000_000)
    body = (data._varint_field(1, data.PRECOMMIT)
            + data._sfixed64_field(2, height)
            + data._bytes_field(5, data._varint_field(1, secs)
                                + data._varint_field(2, nanos))
            + data._bytes_field(6, chain_id.encode()))
    return data._varint(len(body)) + body


@dataclass(frozen=True)
class ExtendedBlock(data.Block):
    """A signed extended commit as it comes off the wire: ``data.Block``'s
    vote half (which is what ``data.present`` and ``reference.control``
    read) with the extension half beside it.  ``flags`` empty means every
    lane is a for-block precommit (the configuration's ``assumed``)."""

    extensions: tuple = ()
    ext_sigs: tuple = ()
    tampered_kind: str = ""     # "vote" | "extension" where tampered_lane >= 0
    flags: tuple = ()
    # the program's ExtendedCommit of this presentation, built by the
    # driver's ``units`` outside the call's clock and used once
    fresh: list = field(default_factory=list, compare=False, repr=False)

    def flag(self, lane: int) -> int:
        return self.flags[lane] if self.flags else FLAG_COMMIT


def tamper(block: ExtendedBlock, validator: int, kind: str) -> ExtendedBlock:
    """The same extended commit with one bit of that validator's vote or
    extension signature flipped (``data.tamper`` keeps the vote half only)."""
    half = "sigs" if kind == "vote" else "ext_sigs"
    sigs = getattr(block, half)
    bad = bytearray(sigs[validator])
    bad[7] ^= 1
    return replace(block, tampered_lane=validator, tampered_kind=kind, fresh=[],
                   **{half: sigs[:validator] + (bytes(bad),)
                      + sigs[validator + 1:]})


class Reference:
    def __init__(self, ring: data.Ring):
        self.ring = ring
        self.keys = [Ed25519PublicKey.from_public_bytes(p) for p in ring.pubs]
        self.needed = sum(ring.powers) * 2 // 3
        self.lanes_checked = 0
        self._memo = {}

    def _ok(self, lane: int, msg: bytes, sig: bytes) -> bool:
        self.lanes_checked += 1
        try:
            self.keys[lane].verify(sig, msg)
        except InvalidSignature:
            return False
        return True

    def commit(self, block: ExtendedBlock) -> tuple:
        """A ring's commit is what its height, its tampered validator and
        the tampered kind say, so each distinct one is judged once."""
        key = (block.height, block.tampered_lane, block.tampered_kind,
               block.flags)
        if key not in self._memo:
            self._memo[key] = self._commit(block)
        return self._memo[key]

    def _commit(self, block: ExtendedBlock) -> tuple:
        chain_id, n = self.ring.chain_id, len(self.ring.powers)
        if not (len(block.sigs) == len(block.extensions)
                == len(block.ext_sigs) == n):
            return ("refused", "ErrInvalidCommit")
        for lane in range(n):           # EnsureExtensions, and its converse
            for_block = block.flag(lane) == FLAG_COMMIT
            if (not block.ext_sigs[lane]) if for_block else \
                    (block.extensions[lane] or block.ext_sigs[lane]):
                return ("refused", "ErrInvalidCommit")
        tally = lanes = 0
        for lane, power in enumerate(self.ring.powers):
            flag = block.flag(lane)
            if flag == FLAG_ABSENT:
                continue
            msg = data.vote_sign_bytes(
                chain_id, block.height, block.block_hash, 1, block.parts_hash,
                block.stamps[lane]) if flag == FLAG_COMMIT else \
                nil_vote_sign_bytes(chain_id, block.height, block.stamps[lane])
            if not self._ok(lane, msg, block.sigs[lane]):
                return ("bad_sig", lane, "vote")
            lanes += 1
            if flag != FLAG_COMMIT:
                continue
            if not self._ok(lane, extension_sign_bytes(
                    chain_id, block.height, block.extensions[lane]),
                    block.ext_sigs[lane]):
                return ("bad_sig", lane, "extension")
            tally, lanes = tally + power, lanes + 1
        if tally <= self.needed:
            return ("refused", "ErrNotEnoughVotingPower")
        return ("ok", lanes)
