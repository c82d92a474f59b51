"""Commit, CommitSig, ExtendedCommit (reference: ``types/block.go:607-1250``).

A Commit is the aggregated +2/3 precommit for a block: one CommitSig per
validator (by validator-set index), flagged absent / commit / nil.  The
ExtendedCommit additionally carries each precommit's vote extension and
extension signature (ABCI 2.0 vote extensions).
"""

from __future__ import annotations

from dataclasses import dataclass, field

from ..crypto import merkle
from . import canonical, wire
from .block_id import BlockID
from .vote import PRECOMMIT_TYPE, Vote

BLOCK_ID_FLAG_ABSENT = 1
BLOCK_ID_FLAG_COMMIT = 2
BLOCK_ID_FLAG_NIL = 3
# a for-block precommit whose signature was folded into the commit's
# aggregate (Commit.agg_signature): the lane keeps address + timestamp
# but carries NO individual signature — the signer bitmap + one G2 point
# replace the whole cohort's 96-byte lanes
BLOCK_ID_FLAG_AGGREGATE = 4

# max individual signature size: 64 ed25519, 96 bls12_381 G2
MAX_SIGNATURE_SIZE = 96
# widest vote extension ExtendedCommit.dense_columns() lays out as a
# matrix (every lane is padded to the widest); wider ones verify through
# the per-lane loop
MAX_DENSE_EXTENSION_BYTES = 1024


def _byte_rows(items: list, lens, width: int):
    """``items`` (bytes each, ``lens`` their lengths) as an ``(n, width)``
    uint8 matrix: shorter ones zero-padded, a wider one left zero."""
    import numpy as np

    n = len(items)
    if not n or not width:
        return np.zeros((n, width), np.uint8)
    if bool((lens == width).all()):
        blob = b"".join(items)
    else:
        blob = b"".join(x.ljust(width, b"\0") if len(x) <= width
                        else bytes(width) for x in items)
    return np.frombuffer(blob, np.uint8).reshape(n, width)


def signer_bitmap(indices, n: int) -> bytes:
    """Aggregate-signer bitmap: bit i (byte i//8, bit i%8, LSB-first)
    set when validator-set index i signed into the aggregate."""
    buf = bytearray((n + 7) // 8)
    for i in indices:
        if not 0 <= i < n:
            raise ValueError(f"signer index {i} out of range for {n}")
        buf[i // 8] |= 1 << (i % 8)
    return bytes(buf)


def bitmap_indices(bitmap: bytes, n: int) -> list[int] | None:
    """Decode a signer bitmap; None when the length is wrong or a bit
    beyond n is set (a malformed commit, never a silent truncation)."""
    if len(bitmap) != (n + 7) // 8:
        return None
    out = []
    for i, byte in enumerate(bitmap):
        base = i * 8
        while byte:
            low = byte & -byte
            idx = base + low.bit_length() - 1
            if idx >= n:
                return None
            out.append(idx)
            byte ^= low
    return out


@dataclass
class CommitSig:
    block_id_flag: int = BLOCK_ID_FLAG_ABSENT
    validator_address: bytes = b""
    timestamp_ns: int = 0
    signature: bytes = b""

    @classmethod
    def absent(cls) -> "CommitSig":
        return cls()

    def is_absent(self) -> bool:
        return self.block_id_flag == BLOCK_ID_FLAG_ABSENT

    def is_commit(self) -> bool:
        return self.block_id_flag in (BLOCK_ID_FLAG_COMMIT,
                                      BLOCK_ID_FLAG_AGGREGATE)

    def is_aggregate(self) -> bool:
        return self.block_id_flag == BLOCK_ID_FLAG_AGGREGATE

    def for_block(self) -> bool:
        return self.is_commit()

    def block_id(self, commit_block_id: BlockID) -> BlockID:
        """The BlockID this sig actually signed (commit/aggregate -> the
        commit's, nil -> nil, absent -> nil)
        (types/block.go CommitSig.BlockID)."""
        if self.is_commit():
            return commit_block_id
        return BlockID()

    def validate_basic(self) -> str | None:
        if self.block_id_flag not in (BLOCK_ID_FLAG_ABSENT,
                                      BLOCK_ID_FLAG_COMMIT,
                                      BLOCK_ID_FLAG_NIL,
                                      BLOCK_ID_FLAG_AGGREGATE):
            return "unknown block ID flag"
        if self.is_absent():
            if self.validator_address or self.signature:
                return "absent sig with address/signature"
        elif self.is_aggregate():
            if len(self.validator_address) != 20:
                return "invalid validator address size"
            if self.signature:
                return "aggregate lane carries an individual signature"
        else:
            if len(self.validator_address) != 20:
                return "invalid validator address size"
            if not self.signature or len(self.signature) > MAX_SIGNATURE_SIZE:
                return "signature absent or too big"
        return None

    def encode(self) -> bytes:
        return (wire.field_varint(1, self.block_id_flag)
                + wire.field_bytes(2, self.validator_address)
                + wire.field_message(3, canonical.encode_timestamp(
                    self.timestamp_ns), force=True)
                + wire.field_bytes(4, self.signature))


@dataclass
class Commit:
    height: int
    round: int
    block_id: BlockID
    signatures: list[CommitSig] = field(default_factory=list)
    # BLS aggregate-commit fast path: one compressed G2 signature over the
    # zero-timestamp canonical precommit, covering exactly the lanes
    # flagged BLOCK_ID_FLAG_AGGREGATE (agg_signers is their bitmap —
    # see signer_bitmap).  Empty on pure-Ed25519 commits: wire encoding
    # and hash are then byte-identical to the pre-aggregation format.
    agg_signature: bytes = b""
    agg_signers: bytes = b""

    def size(self) -> int:
        return len(self.signatures)

    def vote_sign_bytes(self, chain_id: str, idx: int) -> bytes:
        """Reconstructed canonical vote bytes for signature idx
        (types/block.go:902 VoteSignBytes) — the message the TPU kernel
        verifies.  Uses a per-commit template encoder (only the timestamp
        and the commit-vs-nil block id vary between a commit's sigs)."""
        cs = self.signatures[idx]
        enc = self._sb_encoder(chain_id, cs.is_commit())
        return enc.sign_bytes(cs.timestamp_ns)

    def vote_sign_bytes_for(self, chain_id: str, idx: int,
                            key_type: str) -> bytes:
        """Sign bytes for lane idx as a function of the signer's key
        type: BLS validators sign the zero-timestamp aggregation domain
        (Vote.sign_bytes_for), Ed25519 the reference encoding."""
        cs = self.signatures[idx]
        enc = self._sb_encoder(chain_id, cs.is_commit())
        return enc.sign_bytes(0 if key_type == "bls12_381"
                              else cs.timestamp_ns)

    def aggregate_sign_bytes(self, chain_id: str) -> bytes:
        """THE message under the aggregate signature: every BLS for-block
        precommit in this commit signed these exact bytes (canonical
        precommit for the commit's BlockID, timestamp pinned to zero)."""
        return self._sb_encoder(chain_id, True).sign_bytes(0)

    def has_aggregate(self) -> bool:
        """True when this commit carries an aggregate signature or any
        AGGREGATE-flag lane (cached: commits are immutable once decoded)."""
        h = self.__dict__.get("_has_agg")
        if h is None:
            h = bool(self.agg_signature) or bool(self.agg_signers) or any(
                cs.block_id_flag == BLOCK_ID_FLAG_AGGREGATE
                for cs in self.signatures)
            self.__dict__["_has_agg"] = h
        return h

    def aggregate_lanes(self) -> list[int]:
        """Indices of AGGREGATE-flag lanes, in index order (cached)."""
        lanes = self.__dict__.get("_agg_lanes")
        if lanes is None:
            lanes = [i for i, cs in enumerate(self.signatures)
                     if cs.block_id_flag == BLOCK_ID_FLAG_AGGREGATE]
            self.__dict__["_agg_lanes"] = lanes
        return lanes

    def __deepcopy__(self, memo):
        # derived caches (_dense_cols, _sb_encoders) must not survive a
        # copy: the copy's signatures are routinely mutated (tests,
        # evidence construction) and stale columns would verify the OLD
        # bytes
        import copy as _copy

        return Commit(self.height, self.round,
                      _copy.deepcopy(self.block_id, memo),
                      _copy.deepcopy(self.signatures, memo),
                      self.agg_signature, self.agg_signers)

    def dense_columns(self):
        """Columnar view for the dense VerifyCommit fast path: ``(flags
        uint8 (N,), timestamps int64 (N,), sigs uint8 (N,64))``, cached on
        the commit (commits are immutable once decoded).  Returns None
        when any non-absent signature isn't 64 bytes — the dense path
        doesn't apply and callers use the per-lane loop."""
        cols = self.__dict__.get("_dense_cols", False)
        if cols is not False:
            return cols
        import numpy as np

        sigs = self.signatures
        n = len(sigs)
        # peer-supplied ints can exceed uint8/int64 (the codec does not
        # bound them); the loop path handles such commits, so out-of-range
        # values mean "dense not applicable".  Flags load as int64 first —
        # Python ints beyond int64 raise OverflowError on EVERY numpy
        # major, whereas a direct uint8 conversion silently WRAPS on
        # numpy 1.x (flag 258 -> 2 == COMMIT), which would make dense
        # nodes tally lanes the loop path rejects — a validity divergence
        # between nodes on different numpy majors.  The uint8 range check
        # is then vectorized before the narrowing cast.
        try:
            flags64 = np.fromiter((cs.block_id_flag for cs in sigs),
                                  np.int64, n)
            ts = np.fromiter((cs.timestamp_ns for cs in sigs), np.int64, n)
        except (OverflowError, ValueError, TypeError):
            self.__dict__["_dense_cols"] = None
            return None
        if n and not ((flags64 >= 0) & (flags64 <= 0xFF)).all():
            self.__dict__["_dense_cols"] = None
            return None
        flags = flags64.astype(np.uint8)
        buf = bytearray(n * 64)
        cols = None
        for i, cs in enumerate(sigs):
            # aggregate lanes carry no individual signature — their lane
            # stays zeroed like an absent one (the aggregate is verified
            # up front and dense kernels never select flag-4 lanes)
            if cs.block_id_flag in (BLOCK_ID_FLAG_ABSENT,
                                    BLOCK_ID_FLAG_AGGREGATE):
                continue
            if len(cs.signature) != 64:
                break
            buf[i * 64:(i + 1) * 64] = cs.signature
        else:
            sigmat = np.frombuffer(bytes(buf), np.uint8).reshape(n, 64) \
                if n else np.zeros((0, 64), np.uint8)
            cols = (flags, ts, sigmat)
        self.__dict__["_dense_cols"] = cols
        return cols

    def dense_addresses(self) -> list:
        """Cached per-lane validator addresses (the trusting path looks
        commit sigs up BY ADDRESS in a possibly different valset)."""
        addrs = self.__dict__.get("_dense_addrs")
        if addrs is None:
            addrs = [cs.validator_address for cs in self.signatures]
            self.__dict__["_dense_addrs"] = addrs
        return addrs

    def sign_bytes_templates(self, chain_id: str):
        """(pre_commit, pre_nil, post) body fragments for the native
        sign-bytes builder: everything except the timestamp field, for
        both the commit-BlockID and nil variants."""
        enc_c = self._sb_encoder(chain_id, True)
        enc_n = self._sb_encoder(chain_id, False)
        return enc_c._prefix, enc_n._prefix, enc_c._suffix

    def _sb_encoder(self, chain_id: str, is_commit: bool):
        cache = self.__dict__.setdefault("_sb_encoders", {})
        enc = cache.get((chain_id, is_commit))
        if enc is None:
            bid = self.block_id if is_commit else BlockID()
            enc = canonical.CanonicalVoteEncoder(
                chain_id, PRECOMMIT_TYPE, self.height, self.round, bid)
            cache[(chain_id, is_commit)] = enc
        return enc

    def to_vote(self, idx: int) -> Vote:
        cs = self.signatures[idx]
        return Vote(type=PRECOMMIT_TYPE, height=self.height, round=self.round,
                    block_id=cs.block_id(self.block_id),
                    timestamp_ns=cs.timestamp_ns,
                    validator_address=cs.validator_address,
                    validator_index=idx, signature=cs.signature)

    def hash(self) -> bytes:
        leaves = [cs.encode() for cs in self.signatures]
        if self.agg_signature or self.agg_signers:
            # one extra leaf binds the aggregate signature + bitmap into
            # the header's commit hash; pure-Ed25519 commits append
            # nothing, keeping their hashes byte-identical to the
            # pre-aggregation format
            leaves.append(wire.field_bytes(1, self.agg_signature)
                          + wire.field_bytes(2, self.agg_signers))
        return merkle.hash_from_byte_slices_fast(leaves)

    def validate_basic(self) -> str | None:
        if self.height < 0:
            return "negative height"
        if self.round < 0:
            return "negative round"
        if self.height >= 1:
            if self.block_id.is_nil():
                return "commit cannot be for nil block"
            if not self.signatures:
                return "no signatures in commit"
            for i, cs in enumerate(self.signatures):
                err = cs.validate_basic()
                if err:
                    return f"invalid signature {i}: {err}"
            err = self._validate_aggregate()
            if err:
                return err
        return None

    def _validate_aggregate(self) -> str | None:
        """Structural aggregate checks: the bitmap must name exactly the
        AGGREGATE-flag lanes, and signature/bitmap must come and go
        together.  Cryptographic verification lives in
        types/validation.py; this is pure shape."""
        lanes = self.aggregate_lanes()
        if not self.agg_signature and not self.agg_signers and not lanes:
            return None
        if len(self.agg_signature) != 96:
            return "aggregate signature must be 96 bytes"
        if not lanes:
            return "aggregate signature without aggregate lanes"
        if len(self.agg_signers) != (len(self.signatures) + 7) // 8:
            return "malformed aggregate signer bitmap"
        # one bytes compare against the re-encoded lane set (cached —
        # commits are immutable once decoded) instead of an O(N) decode
        # per call; a stray bit beyond the lanes fails the same way a
        # missing one does
        expect = self.__dict__.get("_agg_bitmap")
        if expect is None:
            expect = signer_bitmap(lanes, len(self.signatures))
            self.__dict__["_agg_bitmap"] = expect
        if self.agg_signers != expect:
            return "aggregate signer bitmap does not match aggregate lanes"
        return None

    def encode(self) -> bytes:
        body = (wire.field_varint(1, self.height)
                + wire.field_varint(2, self.round)
                + wire.field_message(3, self.block_id.encode(), force=True))
        for cs in self.signatures:
            body += wire.field_message(4, cs.encode(), force=True)
        body += (wire.field_bytes(5, self.agg_signature)
                 + wire.field_bytes(6, self.agg_signers))
        return body


def aggregate_commit(commit: Commit, val_set) -> Commit:
    """Fold the BLS for-block cohort of a freshly made commit into one
    aggregate signature + signer bitmap (the proposer-side half of the
    fast path; VoteSet.make_commit calls this).  Deterministic — lanes
    fold in validator-index order — so replays are byte-identical.
    Cohorts smaller than 2 stay as individual lanes (no wire saving);
    NIL votes always stay individual (they sign a different message).
    Ed25519 lanes are untouched."""
    if commit.has_aggregate():
        # already folded (a promoted seen commit after catch-up):
        # re-folding would overwrite the aggregate with a partial one
        return commit
    if not val_set.has_bls():
        return commit
    cohort = []
    sigs = []
    for i, cs in enumerate(commit.signatures):
        if cs.block_id_flag != BLOCK_ID_FLAG_COMMIT:
            continue
        val = val_set.get_by_index(i)
        if val is None or val.pub_key.type() != "bls12_381":
            continue
        cohort.append(i)
        sigs.append(cs.signature)
    if len(cohort) < 2:
        return commit
    from ..crypto import bls12381 as _bls

    # check=False: every input already passed individual vote
    # verification on its way into the VoteSet
    agg = _bls.aggregate_signatures(sigs, check=False)
    new_sigs = list(commit.signatures)
    for i in cohort:
        cs = commit.signatures[i]
        new_sigs[i] = CommitSig(BLOCK_ID_FLAG_AGGREGATE,
                                cs.validator_address, cs.timestamp_ns, b"")
    return Commit(commit.height, commit.round, commit.block_id, new_sigs,
                  agg, signer_bitmap(cohort, len(new_sigs)))


@dataclass
class ExtendedCommitSig:
    commit_sig: CommitSig = field(default_factory=CommitSig)
    extension: bytes = b""
    extension_signature: bytes = b""

    def validate_basic(self) -> str | None:
        err = self.commit_sig.validate_basic()
        if err:
            return err
        if self.commit_sig.is_commit():
            if len(self.extension_signature) > MAX_SIGNATURE_SIZE:
                return "extension signature too big"
        elif self.extension or self.extension_signature:
            return "extension on non-commit vote"
        return None

    def ensure_extension(self, ext_enabled: bool) -> bool:
        """types/block.go EnsureExtensions element check."""
        if not ext_enabled:
            return not self.extension and not self.extension_signature
        if self.commit_sig.is_commit():
            return len(self.extension_signature) > 0
        return True


@dataclass
class ExtendedCommit:
    """Commit + vote extensions (types/block.go:1086)."""

    height: int
    round: int
    block_id: BlockID
    extended_signatures: list[ExtendedCommitSig] = field(default_factory=list)
    # carried through when an already-aggregated commit is promoted
    # (seen-commit path after catch-up): the folded lanes have no
    # individual signatures, so dropping these would make the commit
    # unverifiable
    agg_signature: bytes = b""
    agg_signers: bytes = b""

    def size(self) -> int:
        return len(self.extended_signatures)

    def to_commit(self) -> Commit:
        """Strip extensions (types/block.go:1165 ToCommit)."""
        return Commit(height=self.height, round=self.round,
                      block_id=self.block_id,
                      signatures=[e.commit_sig
                                  for e in self.extended_signatures],
                      agg_signature=self.agg_signature,
                      agg_signers=self.agg_signers)

    def ensure_extensions(self, ext_enabled: bool) -> bool:
        """types/block.go:1154 EnsureExtensions."""
        return all(e.ensure_extension(ext_enabled)
                   for e in self.extended_signatures)

    def to_extended_vote(self, idx: int) -> Vote:
        e = self.extended_signatures[idx]
        v = Commit(self.height, self.round, self.block_id,
                   [x.commit_sig for x in self.extended_signatures]
                   ).to_vote(idx)
        v.extension = e.extension
        v.extension_signature = e.extension_signature
        return v

    def stripped(self) -> Commit:
        """:meth:`to_commit`, made once and kept on the object (an
        extended commit is immutable once decoded): the vote half's
        columns and sign-bytes templates memoise on it."""
        c = self.__dict__.get("_stripped")
        if c is None:
            c = self.__dict__["_stripped"] = self.to_commit()
        return c

    def __deepcopy__(self, memo):
        # as Commit.__deepcopy__: derived caches must not survive a copy
        import copy as _copy

        return ExtendedCommit(self.height, self.round,
                              _copy.deepcopy(self.block_id, memo),
                              _copy.deepcopy(self.extended_signatures, memo),
                              self.agg_signature, self.agg_signers)

    def extension_sign_bytes(self, chain_id: str, idx: int) -> bytes:
        """CanonicalVoteExtension bytes under extension signature ``idx``:
        byte for byte ``to_extended_vote(idx).extension_sign_bytes``."""
        return canonical.canonical_vote_extension_sign_bytes(
            chain_id, self.height, self.round,
            self.extended_signatures[idx].extension)

    def extension_sign_bytes_suffix(self, chain_id: str) -> bytes:
        """What follows the extension field in every lane's
        CanonicalVoteExtension body (height, round, chain id): the body
        of an empty extension, taken from the canonical encoder itself
        so that the dense rows cannot drift from it."""
        whole = canonical.canonical_vote_extension_sign_bytes(
            chain_id, self.height, self.round, b"")
        k = 1                # strip the varint length prefix
        while len(wire.varint(len(whole) - k)) != k:
            k += 1
        return whole[k:]

    def dense_columns(self):
        """Columnar view for ``VerifyExtendedCommit``'s dense path, cached
        on the object as :meth:`Commit.dense_columns` is: ``(flags, ts,
        sigs, ext_lens int64 (N,), exts uint8 (N,W), ext_sig_lens int64
        (N,), ext_sigs uint8 (N,64))``.  The first three are the stripped
        commit's own columns; ``exts`` holds every lane's extension
        zero-padded to the widest, ``ext_sigs`` the 64-byte extension
        signatures (rows of another length stay zero: ``ext_sig_lens``
        tells).  None when the vote columns do not apply or an extension
        is too wide for a dense matrix: callers use the per-lane loop."""
        cols = self.__dict__.get("_dense_cols", False)
        if cols is not False:
            return cols
        import numpy as np

        cols = None
        base = self.stripped().dense_columns()
        es = self.extended_signatures
        n = len(es)
        exts = [e.extension for e in es]
        width = max(map(len, exts), default=0)
        if base is not None and width <= MAX_DENSE_EXTENSION_BYTES:
            xsigs = [e.extension_signature for e in es]
            ext_lens = np.fromiter(map(len, exts), np.int64, n)
            ext_sig_lens = np.fromiter(map(len, xsigs), np.int64, n)
            cols = (*base, ext_lens, _byte_rows(exts, ext_lens, width),
                    ext_sig_lens, _byte_rows(xsigs, ext_sig_lens, 64))
        self.__dict__["_dense_cols"] = cols
        return cols

