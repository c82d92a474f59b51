"""``VerifyExtendedCommit`` against a plain reference inside the program: a
loop of ``Vote.verify_vote_and_extension`` over ``to_extended_vote(i)`` plus
the tally, which is what upstream's ``ExtendedCommit.ToExtendedVoteSet``
does.  Seeded keys, 4-16 validators, the host path (``backend="cpu"``)."""

from __future__ import annotations

import copy
import hashlib

import pytest

from cometbft_tpu.crypto.keys import Ed25519PrivKey
from cometbft_tpu.types import validation as V
from cometbft_tpu.types.block_id import BlockID, PartSetHeader
from cometbft_tpu.types.commit import (BLOCK_ID_FLAG_ABSENT,
                                       BLOCK_ID_FLAG_COMMIT,
                                       BLOCK_ID_FLAG_NIL, CommitSig,
                                       ExtendedCommit, ExtendedCommitSig)
from cometbft_tpu.types.validator_set import Validator, ValidatorSet
from cometbft_tpu.types.vote import PRECOMMIT_TYPE, Vote

CHAIN = "ext-chain"
HEIGHT = 7


def _h(*parts) -> bytes:
    return hashlib.sha256("/".join(map(str, parts)).encode()).digest()


def make_extended(n: int, *, nil=(), absent=(), ext_len=lambda i: 32,
                  powers=None, seed: int = 29):
    """``(vals, block_id, ExtendedCommit)`` signed by seeded keys: lane
    ``i`` a for-block precommit with an extension of ``ext_len(i)`` bytes,
    a nil precommit, or absent."""
    privs = {}
    for i in range(n):
        sk = Ed25519PrivKey.from_secret(_h("key", seed, i))
        privs[sk.pub_key().bytes()] = sk
    vals = ValidatorSet([Validator(sk.pub_key(), (powers or [10] * n)[k])
                         for k, sk in enumerate(privs.values())])
    bid = BlockID(_h("block", seed), PartSetHeader(1, _h("parts", seed)))
    sigs = []
    for i, val in enumerate(vals.validators):
        sk = privs[val.pub_key.bytes()]
        if i in absent:
            sigs.append(ExtendedCommitSig())
            continue
        vote = Vote(PRECOMMIT_TYPE, HEIGHT, 0,
                    BlockID() if i in nil else bid,
                    1_700_000_000_000_000_000 + 1_000_003 * i, val.address,
                    i)
        vote.signature = sk.sign(vote.sign_bytes(CHAIN))
        ext = ext_sig = b""
        if i not in nil:
            vote.extension = ext = _h("ext", seed, i)[:ext_len(i)] \
                if ext_len(i) <= 32 else _h("ext", seed, i) * (ext_len(i) // 32)
            ext_sig = sk.sign(vote.extension_sign_bytes(CHAIN))
        flag = BLOCK_ID_FLAG_NIL if i in nil else BLOCK_ID_FLAG_COMMIT
        sigs.append(ExtendedCommitSig(
            CommitSig(flag, val.address, vote.timestamp_ns, vote.signature),
            ext, ext_sig))
    return vals, bid, ExtendedCommit(HEIGHT, 0, bid, sigs)


def reference(vals, bid, height, ec) -> tuple:
    """Upstream, one vote at a time: ``(error type | None, idx | None)``."""
    if vals.size() != ec.size() or height != ec.height or bid != ec.block_id:
        return V.ErrInvalidCommit, None
    for e in ec.extended_signatures:
        cs = e.commit_sig
        if cs.is_commit() and not e.extension_signature or \
                not cs.is_commit() and (e.extension or e.extension_signature):
            return V.ErrInvalidCommit, None
    tally = 0
    for i, e in enumerate(ec.extended_signatures):
        if e.commit_sig.is_absent():
            continue
        vote, key = ec.to_extended_vote(i), vals.get_by_index(i).pub_key
        if not vote.verify_vote_and_extension(CHAIN, key, True):
            return (V.ErrInvalidSignature if not vote.verify(CHAIN, key)
                    else V.ErrInvalidExtensionSignature), i
        if e.commit_sig.is_commit():
            tally += vals.get_by_index(i).voting_power
    if tally <= vals.total_voting_power() * 2 // 3:
        return V.ErrNotEnoughVotingPower, None
    return None, None


def entry(vals, bid, height, ec, **kw) -> tuple:
    try:
        V.VerifyExtendedCommit(CHAIN, vals, bid, height, ec, backend="cpu",
                               **kw)
    except V.CommitVerificationError as e:
        return type(e), getattr(e, "idx", None)
    return None, None


def flipped(data: bytes, byte: int = 7) -> bytes:
    out = bytearray(data)
    out[byte] ^= 1
    return bytes(out)


def tampered(ec, idx: int, what: str):
    """A fresh ExtendedCommit with one bit of lane ``idx`` flipped."""
    sigs = list(ec.extended_signatures)
    e = sigs[idx]
    cs = e.commit_sig
    if what == "vote_sig":
        e = ExtendedCommitSig(CommitSig(cs.block_id_flag, cs.validator_address,
                                        cs.timestamp_ns, flipped(cs.signature)),
                              e.extension, e.extension_signature)
    elif what == "ext_sig":
        e = ExtendedCommitSig(cs, e.extension, flipped(e.extension_signature))
    else:
        e = ExtendedCommitSig(cs, flipped(e.extension), e.extension_signature)
    sigs[idx] = e
    return ExtendedCommit(ec.height, ec.round, ec.block_id, sigs)


@pytest.fixture(scope="module")
def twelve():
    return make_extended(12)


@pytest.fixture(params=["dense", "loop"])
def path(request, monkeypatch):
    """Both cores of the entry: the dense rows, and the per-lane loop it
    falls back to."""
    if request.param == "loop":
        monkeypatch.setattr(V, "_dense_verify_extended",
                            lambda *a, **k: False)
    return request.param


@pytest.mark.parametrize("n", [4, 7, 16])
def test_a_clean_commit_verifies(path, n):
    vals, bid, ec = make_extended(n)
    assert entry(vals, bid, HEIGHT, ec) == reference(vals, bid, HEIGHT, ec) \
        == (None, None)


@pytest.mark.parametrize("idx", [0, 5, 11])
@pytest.mark.parametrize("what,err", [
    ("vote_sig", V.ErrInvalidSignature),
    ("ext_sig", V.ErrInvalidExtensionSignature),
    ("ext_bytes", V.ErrInvalidExtensionSignature)])
def test_one_flipped_bit_names_validator_and_kind(path, twelve, what, err, idx):
    vals, bid, ec = twelve
    bad = tampered(ec, idx, what)
    assert entry(vals, bid, HEIGHT, bad) == reference(vals, bid, HEIGHT, bad) \
        == (err, idx)
    assert issubclass(err, V.ErrInvalidSignature)   # one catcher gets both


@pytest.mark.parametrize("first,second,want", [
    ((3, "ext_sig"), (9, "vote_sig"), (V.ErrInvalidExtensionSignature, 3)),
    ((9, "vote_sig"), (2, "ext_sig"), (V.ErrInvalidExtensionSignature, 2)),
    ((4, "ext_sig"), (4, "vote_sig"), (V.ErrInvalidSignature, 4)),
    ((6, "vote_sig"), (1, "vote_sig"), (V.ErrInvalidSignature, 1))])
def test_two_bad_lanes_name_the_first_validator_vote_before_extension(
        path, twelve, first, second, want):
    vals, bid, ec = twelve
    bad = tampered(tampered(ec, *first), *second)
    assert entry(vals, bid, HEIGHT, bad) == reference(vals, bid, HEIGHT, bad) \
        == want


def test_nil_and_absent_lanes(path):
    vals, bid, ec = make_extended(10, nil={2}, absent={5})
    assert entry(vals, bid, HEIGHT, ec) == reference(vals, bid, HEIGHT, ec) \
        == (None, None)
    # the nil lane's vote signature is verified too (no early exit)
    bad = tampered(ec, 2, "vote_sig")
    assert entry(vals, bid, HEIGHT, bad) == reference(vals, bid, HEIGHT, bad) \
        == (V.ErrInvalidSignature, 2)


@pytest.mark.parametrize("lane,extension,signature", [
    (2, b"x" * 32, b""),            # an extension on a nil lane
    (2, b"", b"s" * 64),            # an extension signature on a nil lane
    (5, b"x" * 32, b"s" * 64),      # both on an absent lane
    (0, None, b"")])                # a for-block lane with no signature
def test_misplaced_extensions_are_an_invalid_commit(path, lane, extension,
                                                    signature):
    vals, bid, ec = make_extended(10, nil={2}, absent={5})
    sigs = list(ec.extended_signatures)
    e = sigs[lane]
    sigs[lane] = ExtendedCommitSig(
        e.commit_sig, e.extension if extension is None else extension,
        signature)
    bad = ExtendedCommit(ec.height, ec.round, ec.block_id, sigs)
    assert entry(vals, bid, HEIGHT, bad) == reference(vals, bid, HEIGHT, bad) \
        == (V.ErrInvalidCommit, None)


@pytest.mark.parametrize("nil,want", [
    ({0, 1, 2}, V.ErrNotEnoughVotingPower),     # 60 of 90: exactly 2/3
    ({0, 1}, None)])                            # 70 of 90: just above
def test_power_at_exactly_two_thirds_and_just_above(path, nil, want):
    vals, bid, ec = make_extended(9, nil=nil)
    assert entry(vals, bid, HEIGHT, ec) == reference(vals, bid, HEIGHT, ec) \
        == (want, None)


@pytest.mark.parametrize("what", ["height", "block_id", "size"])
def test_wrong_height_block_id_or_size(path, twelve, what):
    vals, bid, ec = twelve
    height = HEIGHT + (what == "height")
    if what == "block_id":
        bid = BlockID(_h("other"), bid.part_set_header)
    if what == "size":
        ec = ExtendedCommit(ec.height, ec.round, ec.block_id,
                            ec.extended_signatures[:-1])
    assert entry(vals, bid, height, ec) == reference(vals, bid, height, ec) \
        == (V.ErrInvalidCommit, None)


@pytest.mark.parametrize("ext_len", [lambda i: 32, lambda i: 0,
                                     lambda i: (0, 5, 32, 200)[i % 4]],
                         ids=["32", "empty", "mixed"])
def test_extension_sign_bytes_equal_the_votes(path, ext_len):
    vals, bid, ec = make_extended(8, ext_len=ext_len)
    for i in range(ec.size()):
        assert ec.extension_sign_bytes(CHAIN, i) == \
            ec.to_extended_vote(i).extension_sign_bytes(CHAIN)
    assert entry(vals, bid, HEIGHT, ec) == (None, None)
    bad = tampered(ec, 7, "ext_sig")
    assert entry(vals, bid, HEIGHT, bad) == reference(vals, bid, HEIGHT, bad) \
        == (V.ErrInvalidExtensionSignature, 7)


def test_dense_extension_rows_equal_the_canonical_encoder():
    import numpy as np

    vals, bid, ec = make_extended(8, ext_len=lambda i: (0, 5, 32, 200)[i % 4])
    _, _, _, ext_lens, extmat, ext_sig_lens, ext_sigmat = ec.dense_columns()
    msgs, lens = V._dense_build_extension_rows(
        ec.extension_sign_bytes_suffix(CHAIN), extmat, ext_lens)
    for i, e in enumerate(ec.extended_signatures):
        assert msgs[i, :lens[i]].tobytes() == ec.extension_sign_bytes(CHAIN, i)
        assert not msgs[i, lens[i]:].any()
        assert ext_sigmat[i].tobytes() == e.extension_signature
    assert list(ext_lens) == [len(e.extension) for e in ec.extended_signatures]
    assert (ext_sig_lens == 64).all() and extmat.dtype == np.uint8
    assert ec.dense_columns() is ec.dense_columns()         # memoised
    assert copy.deepcopy(ec).__dict__.get("_dense_cols") is None


def test_verify_commit_on_the_stripped_commit_is_unchanged(twelve):
    """The vote half alone, through the entry that was there."""
    vals, bid, ec = twelve
    V.VerifyCommit(CHAIN, vals, bid, HEIGHT, ec.to_commit(), backend="cpu")
    with pytest.raises(V.ErrInvalidSignature) as ei:
        V.VerifyCommit(CHAIN, vals, bid, HEIGHT,
                       tampered(ec, 4, "vote_sig").to_commit(), backend="cpu")
    assert ei.value.idx == 4 and \
        not isinstance(ei.value, V.ErrInvalidExtensionSignature)
    # an extension fault is not the plain commit's business
    V.VerifyCommit(CHAIN, vals, bid, HEIGHT,
                   tampered(ec, 4, "ext_sig").to_commit(), backend="cpu")


def test_counters_say_what_was_verified_and_how_it_ended(twelve):
    from cometbft_tpu.libs import metrics

    vals, bid, ec = twelve
    lanes, results = V._extended_metrics()
    before = (lanes.value(kind="vote"), lanes.value(kind="extension"),
              results.value(result="ok"), results.value(result="bad_ext_sig"),
              results.value(result="bad_vote_sig"),
              results.value(result="refused"))
    entry(vals, bid, HEIGHT, ec)
    entry(vals, bid, HEIGHT, tampered(ec, 3, "ext_sig"))
    entry(vals, bid, HEIGHT, tampered(ec, 3, "vote_sig"))
    entry(vals, bid, HEIGHT + 1, ec)
    after = (lanes.value(kind="vote"), lanes.value(kind="extension"),
             results.value(result="ok"), results.value(result="bad_ext_sig"),
             results.value(result="bad_vote_sig"),
             results.value(result="refused"))
    assert [b - a for a, b in zip(before, after)] == [36, 36, 1, 1, 1, 1]
    text = metrics.DEFAULT.collect()
    assert 'types_extended_commit_lanes_total{kind="extension"}' in text
    assert 'types_extended_commit_verify_total{result="ok"}' in text


def test_the_dense_core_handles_an_ed25519_commit(twelve):
    from cometbft_tpu.crypto import _native_ed25519 as nat

    vals, bid, ec = twelve
    assert V._dense_verify_extended(
        CHAIN, vals, ec, vals.total_voting_power() * 2 // 3, "cpu", False,
        None) is nat.available()


def test_a_key_that_is_not_ed25519_takes_the_loop(monkeypatch):
    """One secp256k1 validator: no dense table, the same verdicts."""
    from cometbft_tpu.crypto.secp256k1 import Secp256k1PrivKey

    vals, bid, ec = make_extended(6)
    sk = Secp256k1PrivKey.from_secret(_h("secp"))
    old = vals.validators[2]
    mixed = ValidatorSet([Validator(sk.pub_key(), 10) if v is old else
                          Validator(v.pub_key, 10) for v in vals.validators])
    idx = next(i for i, v in enumerate(mixed.validators)
               if v.pub_key.type() == "secp256k1")
    order = {v.address: e for v, e in zip(vals.validators,
                                          ec.extended_signatures)}
    sigs = []
    for i, v in enumerate(mixed.validators):
        if i != idx:
            sigs.append(order[v.address])
            continue
        vote = Vote(PRECOMMIT_TYPE, HEIGHT, 0, bid, 1_700_000_000_000_000_000,
                    v.address, i, extension=b"secp-ext")
        sigs.append(ExtendedCommitSig(
            CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, vote.timestamp_ns,
                      sk.sign(vote.sign_bytes(CHAIN))),
            vote.extension, sk.sign(vote.extension_sign_bytes(CHAIN))))
    ec = ExtendedCommit(HEIGHT, 0, bid, sigs)
    assert mixed.dense() is None
    assert entry(mixed, bid, HEIGHT, ec) == reference(mixed, bid, HEIGHT, ec) \
        == (None, None)
    bad = tampered(ec, idx, "ext_sig")
    assert entry(mixed, bid, HEIGHT, bad) \
        == reference(mixed, bid, HEIGHT, bad) \
        == (V.ErrInvalidExtensionSignature, idx)


def test_a_traced_call_records_ext_rows_and_stays_on_the_device_route():
    """Through the device entry (``backend="jax"``: whatever JAX has), 8
    validators = 16 lanes in the 16 bucket: the spans the benchmark's
    ``ext_rows_ms.extended`` reads, and no lane on a route that
    ``benchmarks/counters.py`` would count as off the chip."""
    from cometbft_tpu.libs import metrics, tracing

    def routes():
        return {line.split(" ")[0]: float(line.split(" ")[1])
                for line in metrics.DEFAULT.collect().splitlines()
                if line.startswith("crypto_batch_lanes_total{")}

    vals, bid, ec = make_extended(8)
    before = routes()
    tracing.clear()
    tracing.configure(enabled=True)
    try:
        V.VerifyExtendedCommit(CHAIN, vals, bid, HEIGHT, ec, backend="jax",
                               patient=True)
        with pytest.raises(V.ErrInvalidExtensionSignature) as ei:
            V.VerifyExtendedCommit(CHAIN, vals, bid, HEIGHT,
                                   tampered(ec, 6, "ext_sig"), backend="jax",
                                   patient=True)
        recs = tracing.dump()
    finally:
        tracing.configure(enabled=False)
        tracing.clear()
    assert ei.value.idx == 6
    moved = {k: v - before.get(k, 0.0) for k, v in routes().items()
             if v != before.get(k, 0.0)}
    assert moved and all('route="device' in k for k in moved), moved
    assert moved['crypto_batch_lanes_total{route="device"}'] == 32
    spans = {(r["sub"], r["name"]): r for r in recs if r["kind"] == "span"}
    verify = [r for r in recs if r["name"] == "verify"]
    assert [v["attrs"]["ok"] for v in verify] == [True, False]
    assert verify[0]["attrs"] == {"entry": "extended", "height": HEIGHT,
                                  "commits": 1, "lanes": 8, "ext_lanes": 8,
                                  "ok": True}
    ext_rows = spans[("types.validation", "ext_rows")]
    assert ext_rows["attrs"] == {"lanes": 8} and \
        ext_rows["parent"] == verify[1]["id"]
    assert spans[("types.validation", "rows")]["attrs"] == {"commits": 1,
                                                            "lanes": 8}
    dense = spans[("crypto.seam", "verify_dense")]
    assert dense["attrs"] == {"lanes": 16, "patient": True, "route": "device"}


# ------------------------------------------------ the call site in the node


def _stopped_node_with_stored_commit():
    """A validator of a 4-node net with extensions on, stopped after a few
    heights, its own precommit set forgotten as after a restart: what it
    proposes from is the block store's extended commit for ``h - 1``."""
    import asyncio

    from cometbft_tpu.testing import make_inproc_network

    async def main():
        net = await make_inproc_network(4, vote_extensions_height=1)
        try:
            await net.start()
            await net.wait_for_height(3, timeout=60)
        finally:
            await net.stop()
        return net.nodes[0].consensus

    loop = asyncio.new_event_loop()
    try:
        cs = loop.run_until_complete(main())
    finally:
        loop.close()
    cs.rs.last_commit = None
    stored = cs.block_store.load_block_extended_commit(cs.rs.height - 1)
    assert stored is not None and stored.ensure_extensions(True)
    return cs, stored


def test_a_sound_stored_commit_is_verified_once_however_often_asked(
        monkeypatch):
    cs, stored = _stopped_node_with_stored_commit()
    calls = []
    real = V.VerifyExtendedCommit

    def spy(*a, **kw):
        calls.append(kw)
        return real(*a, **kw)
    monkeypatch.setattr(V, "VerifyExtendedCommit", spy)
    for _ in range(3):
        got = cs._last_extended_commit()
        assert got is not None and got.height == stored.height \
            and got.size() == stored.size()
    assert calls == [{"backend": "cpu", "patient": True}]


def test_a_tampered_stored_commit_is_not_proposed_from(monkeypatch):
    import asyncio

    cs, stored = _stopped_node_with_stored_commit()
    lane = next(i for i, e in enumerate(stored.extended_signatures)
                if e.commit_sig.is_commit())
    bad = tampered(stored, lane, "ext_sig")
    monkeypatch.setattr(cs.block_store, "load_block_extended_commit",
                        lambda h: bad if h == bad.height else None)
    logged = []

    class Log:
        def error(self, msg, **kw):
            logged.append((msg, kw))
    cs.log = Log()
    assert cs._last_extended_commit() is None
    assert cs._last_extended_commit() is None       # the verdict is kept
    assert len(logged) == 1 and logged[0][1]["height"] == bad.height \
        and logged[0][1]["validator_index"] == lane \
        and logged[0][1]["signature"] == "extension"
    proposed = []
    cs.broadcast_proposal = proposed.append
    cs.rs.valid_block = None
    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(cs._decide_proposal(cs.rs.height, cs.rs.round))
    finally:
        loop.close()
    assert proposed == []
