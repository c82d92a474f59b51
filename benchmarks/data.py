"""Traffic generation: validator keys, signed commits and tampering, from the seed.

Nothing here is taken from the program but the plain containers its entries
take as arguments (``ValidatorSet``, ``Commit``, ``CommitSig``, ``BlockID``).
Keys come from SHA-256 of the seed, signatures from OpenSSL (``cryptography``)
and the signed bytes from this file's own CanonicalVote encoder, so a later PR
to the program's encoder, signer or test helpers cannot move the yardstick.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass

from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

FLAG_COMMIT = 2                 # BlockIDFlagCommit (types/block.go)
PRECOMMIT = 2                   # SignedMsgType PRECOMMIT
BASE_TIME_NS = 1_700_000_000_000_000_000


# ----------------------------------------------- proto3 CanonicalVote, by hand

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _bytes_field(field: int, value: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _varint_field(field: int, value: int) -> bytes:
    return _varint(field << 3) + _varint(value) if value else b""


def _sfixed64_field(field: int, value: int) -> bytes:
    return _varint(field << 3 | 1) + value.to_bytes(8, "little") if value else b""


def vote_sign_bytes(chain_id: str, height: int, block_hash: bytes,
                    parts_total: int, parts_hash: bytes, ts_ns: int) -> bytes:
    """The bytes a validator signs for a round-0 precommit (upstream
    ``types/vote.go`` VoteSignBytes: a length-prefixed CanonicalVote with
    type=1, height=2 and round=3 as sfixed64, block_id=4, timestamp=5 always
    present, chain_id=6; zero scalars omitted as proto3 does)."""
    psh = _varint_field(1, parts_total) + _bytes_field(2, parts_hash)
    bid = _bytes_field(1, block_hash) + _bytes_field(2, psh)
    secs, nanos = divmod(ts_ns, 1_000_000_000)
    body = (_varint_field(1, PRECOMMIT) + _sfixed64_field(2, height)
            + _bytes_field(4, bid)
            + _bytes_field(5, _varint_field(1, secs) + _varint_field(2, nanos))
            + _bytes_field(6, chain_id.encode()))
    return _varint(len(body)) + body


# ------------------------------------------------------------------- the ring

@dataclass(frozen=True)
class Block:
    """One signed commit as it comes off the wire: everything but the
    program's ``Commit`` object, which is rebuilt for every presentation."""

    height: int
    block_hash: bytes
    parts_hash: bytes
    stamps: tuple              # per-lane timestamp, ns
    sigs: tuple                # per-lane 64-byte signature
    tampered_lane: int = -1    # -1: as signed


@dataclass
class Ring:
    chain_id: str
    vals: object               # the program's ValidatorSet (an input container)
    pubs: tuple                # 32-byte public keys in validator-set order
    powers: tuple
    addresses: tuple
    blocks: list


def _h(*parts) -> bytes:
    return hashlib.sha256("/".join(str(p) for p in parts).encode()).digest()


def make_ring(cfg: dict, seed: int, n_blocks: int) -> Ring:
    """``n_blocks`` consecutive commits of one validator set, every validator
    signing for the block (the configuration's ``assumed``), each at its own
    timestamp as live validators do."""
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    from cometbft_tpu.types.validator_set import Validator, ValidatorSet

    n, power, chain_id = cfg["validators"], cfg["voting_power"], cfg["chain_id"]
    privs = {}
    for i in range(n):
        sk = Ed25519PrivateKey.from_private_bytes(_h("key", seed, i))
        privs[sk.public_key().public_bytes_raw()] = sk
    vals = ValidatorSet([Validator(Ed25519PubKey(p), power) for p in privs])
    pubs = tuple(v.pub_key.bytes() for v in vals.validators)
    signers = [privs[p] for p in pubs]
    blocks = []
    for k in range(n_blocks):
        height = k + 1
        bh, ph = _h("block", seed, height), _h("parts", seed, height)
        t0 = BASE_TIME_NS + height * 1_000_000_000
        stamps = tuple(t0 + 1_000_000 * (1 + (i * 7919) % 997) for i in range(n))
        sigs = tuple(sk.sign(vote_sign_bytes(chain_id, height, bh, 1, ph, ts))
                     for sk, ts in zip(signers, stamps))
        blocks.append(Block(height, bh, ph, stamps, sigs))
    return Ring(chain_id, vals, pubs, tuple(v.voting_power for v in vals.validators),
                tuple(v.address for v in vals.validators), blocks)


def tamper(block: Block, lane: int) -> Block:
    """The same commit with one bit of signature ``lane`` flipped."""
    bad = bytearray(block.sigs[lane])
    bad[7] ^= 1
    sigs = block.sigs[:lane] + (bytes(bad),) + block.sigs[lane + 1:]
    return Block(block.height, block.block_hash, block.parts_hash, block.stamps,
                 sigs, tampered_lane=lane)


def present(ring: Ring, block: Block):
    """``(block_id, height, commit)`` with FRESH program objects, as a decode
    from the wire gives: ``Commit.dense_columns()`` memoises on the object, so
    a reused ``Commit`` would skip host work a syncing node pays."""
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.types.commit import Commit, CommitSig

    bid = BlockID(block.block_hash, PartSetHeader(1, block.parts_hash))
    sigs = [CommitSig(FLAG_COMMIT, a, ts, s)
            for a, ts, s in zip(ring.addresses, block.stamps, block.sigs)]
    return bid, block.height, Commit(block.height, 0, bid, sigs)


def light_lanes(powers) -> int:
    """Lanes ``VerifyCommitLight`` has to verify when every validator signed:
    up to and including the one that takes the tally past two thirds."""
    needed, tally = sum(powers) * 2 // 3, 0
    for i, p in enumerate(powers):
        tally += p
        if tally > needed:
            return i + 1
    return len(powers)
