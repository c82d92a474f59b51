"""privval: FilePV double-sign protection + remote signer
(reference: ``privval/file_test.go``, ``privval/signer_client_test.go``)."""

import asyncio

import pytest

from cometbft_tpu.privval import (DoubleSignError, FilePV, RemoteSignerError,
                                  SignerClient, SignerServer)
from cometbft_tpu.types.block_id import BlockID
from cometbft_tpu.types.part_set import PartSetHeader
from cometbft_tpu.types.vote import (PRECOMMIT_TYPE, PREVOTE_TYPE, Proposal,
                                     Vote)

pytestmark = pytest.mark.timeout(60)

CHAIN = "pv-chain"


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def _vote(pv, typ=PREVOTE_TYPE, height=5, round_=0, bid=None, ts=1_000):
    return Vote(type=typ, height=height, round=round_,
                block_id=bid if bid is not None else
                BlockID(b"\xaa" * 32, PartSetHeader(1, b"\xbb" * 32)),
                timestamp_ns=ts,
                validator_address=pv.get_pub_key().address(),
                validator_index=0)


def _pv(tmp_path):
    return FilePV.generate(str(tmp_path / "key.json"),
                           str(tmp_path / "state.json"))


def test_filepv_signs_and_persists(tmp_path):
    pv = _pv(tmp_path)
    v = _vote(pv)

    async def main():
        await pv.sign_vote(CHAIN, v, sign_extension=False)
        assert pv.get_pub_key().verify_signature(v.sign_bytes(CHAIN),
                                                 v.signature)
        # reload from disk: state survives
        pv2 = FilePV.load(str(tmp_path / "key.json"),
                          str(tmp_path / "state.json"))
        assert (pv2.height, pv2.round, pv2.step) == (5, 0, 2)
        assert pv2.signature == v.signature
        return True

    assert run(main())


def test_filepv_same_vote_returns_same_signature(tmp_path):
    pv = _pv(tmp_path)

    async def main():
        v1 = _vote(pv)
        await pv.sign_vote(CHAIN, v1, sign_extension=False)
        v2 = _vote(pv)
        await pv.sign_vote(CHAIN, v2, sign_extension=False)
        assert v2.signature == v1.signature
        return True

    assert run(main())


def test_filepv_timestamp_only_change_reuses_signature(tmp_path):
    pv = _pv(tmp_path)

    async def main():
        v1 = _vote(pv, ts=1_000)
        await pv.sign_vote(CHAIN, v1, sign_extension=False)
        v2 = _vote(pv, ts=9_999)
        await pv.sign_vote(CHAIN, v2, sign_extension=False)
        # stored timestamp + stored signature come back
        assert v2.timestamp_ns == 1_000
        assert v2.signature == v1.signature
        return True

    assert run(main())


def test_filepv_refuses_conflicting_vote(tmp_path):
    pv = _pv(tmp_path)

    async def main():
        await pv.sign_vote(CHAIN, _vote(pv), sign_extension=False)
        other = _vote(pv, bid=BlockID(b"\xcc" * 32,
                                      PartSetHeader(1, b"\xdd" * 32)))
        with pytest.raises(DoubleSignError):
            await pv.sign_vote(CHAIN, other, sign_extension=False)
        return True

    assert run(main())


def test_filepv_refuses_hrs_regression(tmp_path):
    pv = _pv(tmp_path)

    async def main():
        await pv.sign_vote(CHAIN, _vote(pv, typ=PRECOMMIT_TYPE, height=5,
                                        round_=2), sign_extension=False)
        # lower height
        with pytest.raises(DoubleSignError):
            await pv.sign_vote(CHAIN, _vote(pv, height=4),
                               sign_extension=False)
        # same height, lower round
        with pytest.raises(DoubleSignError):
            await pv.sign_vote(CHAIN, _vote(pv, height=5, round_=1),
                               sign_extension=False)
        # same height+round, earlier step (prevote after precommit)
        with pytest.raises(DoubleSignError):
            await pv.sign_vote(CHAIN, _vote(pv, typ=PREVOTE_TYPE, height=5,
                                            round_=2), sign_extension=False)
        return True

    assert run(main())


def test_filepv_survives_restart_no_double_sign(tmp_path):
    """Crash after signing: the restarted signer refuses to equivocate."""
    pv = _pv(tmp_path)

    async def main():
        await pv.sign_vote(CHAIN, _vote(pv, typ=PRECOMMIT_TYPE),
                           sign_extension=False)
        # "crash" - reload from disk
        pv2 = FilePV.load(str(tmp_path / "key.json"),
                          str(tmp_path / "state.json"))
        conflicting = _vote(pv2, typ=PRECOMMIT_TYPE,
                            bid=BlockID(b"\xcc" * 32,
                                        PartSetHeader(1, b"\xdd" * 32)))
        with pytest.raises(DoubleSignError):
            await pv2.sign_vote(CHAIN, conflicting, sign_extension=False)
        return True

    assert run(main())


def test_filepv_proposal(tmp_path):
    pv = _pv(tmp_path)

    async def main():
        p = Proposal(height=7, round=0, pol_round=-1,
                     block_id=BlockID(b"\xaa" * 32,
                                      PartSetHeader(1, b"\xbb" * 32)),
                     timestamp_ns=123)
        await pv.sign_proposal(CHAIN, p)
        assert pv.get_pub_key().verify_signature(p.sign_bytes(CHAIN),
                                                 p.signature)
        # signing a vote at the same height/round is fine (step forward)
        await pv.sign_vote(CHAIN, _vote(pv, height=7), sign_extension=False)
        # but another different proposal at the same HRS is refused
        p2 = Proposal(height=7, round=0, pol_round=-1,
                      block_id=BlockID(b"\xcc" * 32,
                                       PartSetHeader(1, b"\xdd" * 32)),
                      timestamp_ns=123)
        with pytest.raises(DoubleSignError):
            await pv.sign_proposal(CHAIN, p2)
        return True

    assert run(main())


def test_remote_signer_roundtrip(tmp_path):
    """SignerServer serves a FilePV over TCP; SignerClient signs through it
    and double-sign refusals surface as RemoteSignerError."""
    pv = _pv(tmp_path)

    async def main():
        server = SignerServer(pv)
        host, port = await server.listen()
        client = await SignerClient.connect(host, port)
        try:
            assert client.get_pub_key().bytes() == pv.get_pub_key().bytes()
            await client.ping()
            v = _vote(client)
            await client.sign_vote(CHAIN, v, sign_extension=False)
            assert client.get_pub_key().verify_signature(
                v.sign_bytes(CHAIN), v.signature)
            conflicting = _vote(client,
                                bid=BlockID(b"\xcc" * 32,
                                            PartSetHeader(1, b"\xdd" * 32)))
            with pytest.raises(RemoteSignerError):
                await client.sign_vote(CHAIN, conflicting,
                                       sign_extension=False)
        finally:
            await client.close()
            await server.close()
        return True

    assert run(main())


def test_signer_listener_dialer_topology(tmp_path):
    """Reference direction (privval/signer_listener_endpoint.go): the node
    listens on priv_validator_laddr, the remote signer dials in and serves
    the key over the dialed connection."""
    from cometbft_tpu.privval.signer import SignerListener, serve_dialer

    pv = _pv(tmp_path)

    async def main():
        listener = SignerListener()
        host, port = await listener.listen()
        dial_task = asyncio.create_task(
            serve_dialer(pv, host, port, max_retries=5))
        try:
            await listener.wait_for_signer(timeout=10)
            assert listener.get_pub_key().bytes() == pv.get_pub_key().bytes()
            await listener.ping()
            v = _vote(listener)
            await listener.sign_vote(CHAIN, v, sign_extension=False)
            assert listener.get_pub_key().verify_signature(
                v.sign_bytes(CHAIN), v.signature)

            # signer restart: the node re-accepts the redial and keeps
            # signing (privval/signer_listener_endpoint.go semantics)
            dial_task.cancel()
            await asyncio.sleep(0)
            dial_task = asyncio.create_task(
                serve_dialer(pv, host, port, max_retries=5))
            v2 = _vote(listener, height=6)
            await listener.sign_vote(CHAIN, v2, sign_extension=False)
            assert listener.get_pub_key().verify_signature(
                v2.sign_bytes(CHAIN), v2.signature)
        finally:
            await listener.close()
            dial_task.cancel()
        return True

    assert run(main())


def test_consensus_runs_on_filepv(tmp_path):
    """The in-proc network commits with FilePV signers: double-sign
    protection is compatible with the live state machine."""
    from cometbft_tpu.testing import make_inproc_network

    async def main():
        def pv_factory(i):
            return FilePV.generate(str(tmp_path / f"k{i}.json"),
                                   str(tmp_path / f"s{i}.json"))

        net = await make_inproc_network(4, pv_factory=pv_factory)
        try:
            await net.start()
            await net.wait_for_height(3, timeout=60)
            hashes = {n.block_store.load_block(3).hash() for n in net.nodes}
            assert len(hashes) == 1
        finally:
            await net.stop()
        return True

    assert run(main())


def test_filepv_secp256k1_key_type(tmp_path):
    """FilePV with a secp256k1 validator key round-trips through the key
    file and signs votes (reference gen-validator --key-type)."""
    from cometbft_tpu.privval import FilePV

    kp, sp = str(tmp_path / "key.json"), str(tmp_path / "state.json")
    pv = FilePV.generate(kp, sp, key_type="secp256k1")
    assert pv.get_pub_key().type() == "secp256k1"
    pv2 = FilePV.load(kp, sp)
    assert pv2.get_pub_key() == pv.get_pub_key()
    # legacy key files without a type field still load as ed25519
    import json as _json

    pv3 = FilePV.generate(str(tmp_path / "k3.json"),
                          str(tmp_path / "s3.json"))
    with open(str(tmp_path / "k3.json")) as f:
        kd = _json.load(f)
    kd.pop("type")
    with open(str(tmp_path / "k3.json"), "w") as f:
        _json.dump(kd, f)
    pv4 = FilePV.load(str(tmp_path / "k3.json"), str(tmp_path / "s3.json"))
    assert pv4.get_pub_key().type() == "ed25519"


def test_filepv_bls_key_roundtrip_and_pop(tmp_path):
    """FilePV with a bls12_381 key persists the proof of possession
    beside the key (the rogue-key gate the aggregate fast path rests
    on) and round-trips both through the key file."""
    import json as _json

    from cometbft_tpu.crypto import bls12381 as _bls
    from cometbft_tpu.privval import FilePV

    kp, sp = str(tmp_path / "key.json"), str(tmp_path / "state.json")
    pv = FilePV.generate(kp, sp, key_type="bls12_381")
    pub = pv.get_pub_key()
    assert pub.type() == "bls12_381"
    assert len(pub.bytes()) == 48

    with open(kp) as f:
        kd = _json.load(f)
    assert kd["type"] == "bls12_381"
    stored_pop = bytes.fromhex(kd["pop"])
    assert _bls.pop_verify(pub.bytes(), stored_pop)
    # the proof is bound to THIS key, not transferable to another
    other = FilePV.generate(str(tmp_path / "k2.json"),
                            str(tmp_path / "s2.json"),
                            key_type="bls12_381")
    assert not _bls.pop_verify(other.get_pub_key().bytes(), stored_pop)

    pv2 = FilePV.load(kp, sp)
    assert pv2.get_pub_key() == pub
    assert pv2.pop() == stored_pop


def test_filepv_bls_signs_aggregation_domain(tmp_path):
    """A BLS FilePV signs votes in the zero-timestamp aggregation domain
    (Vote.sign_bytes_for) — NOT the reference timestamped encoding — so
    its precommits can fold into an aggregate commit."""
    from cometbft_tpu.privval import FilePV

    pv = FilePV.generate(str(tmp_path / "key.json"),
                         str(tmp_path / "state.json"),
                         key_type="bls12_381")
    v = _vote(pv, typ=PRECOMMIT_TYPE, ts=1_000)

    async def main():
        await pv.sign_vote(CHAIN, v, sign_extension=False)
        pub = pv.get_pub_key()
        assert len(v.signature) == 96
        assert pub.verify_signature(
            v.sign_bytes_for(CHAIN, "bls12_381"), v.signature)
        # the timestamped reference encoding is a DIFFERENT message —
        # the signature must not transfer across the domain split
        assert v.sign_bytes(CHAIN) != v.sign_bytes_for(CHAIN, "bls12_381")
        assert not pub.verify_signature(v.sign_bytes(CHAIN), v.signature)
        # double-sign protection still holds in the BLS domain
        other = _vote(pv, typ=PRECOMMIT_TYPE,
                      bid=BlockID(b"\xcc" * 32,
                                  PartSetHeader(1, b"\xdd" * 32)))
        with pytest.raises(DoubleSignError):
            await pv.sign_vote(CHAIN, other, sign_extension=False)
        return True

    assert run(main())


# ----------------------------------------------- sign-state hardening


def test_filepv_corrupt_state_file_raises_typed_error(tmp_path):
    """A corrupt/truncated last-sign-state file must be a typed
    SignStateError carrying the never-auto-reset warning, not a raw
    JSONDecodeError an operator might "fix" with a reset."""
    from cometbft_tpu.privval import SignStateError

    pv = _pv(tmp_path)
    run(pv.sign_vote(CHAIN, _vote(pv), sign_extension=False))
    sp = str(tmp_path / "state.json")
    for payload in ("{not json", "", '{"height": 5, "round": 0}',
                    '{"height": "nan", "round": 0, "step": 2}'):
        with open(sp, "w") as f:
            f.write(payload)
        if payload == "":
            # empty file is still "exists": must refuse, not silently
            # start from a zeroed state
            pass
        with pytest.raises(SignStateError) as ei:
            FilePV.load(str(tmp_path / "key.json"), sp)
        assert "double-sign" in str(ei.value)


def test_privval_state_fsync_eio_withholds_signature(tmp_path):
    """The privval.state.fsync.eio chaos site: a failed sign-state
    persist must NOT release the signature, and the handle goes dead
    (every further sign refuses) — the privval fsyncgate."""
    import errno

    from cometbft_tpu.libs import failures as F
    from cometbft_tpu.privval import SignStateError

    pv = _pv(tmp_path)
    F.configure(enabled=True, seed=3,
                faults=["privval.state.fsync.eio:at=1"])
    try:
        v = _vote(pv)
        with pytest.raises(OSError) as ei:
            run(pv.sign_vote(CHAIN, v, sign_extension=False))
        assert ei.value.errno == errno.EIO
        assert v.signature == b""          # never released
        # dead handle: even with the fault disarmed, no further signing
        F.reset()
        with pytest.raises(SignStateError):
            run(pv.sign_vote(CHAIN, _vote(pv, height=6),
                             sign_extension=False))
        # restart (reload from disk) recovers; the pre-failure state
        # file is intact, so double-sign protection still holds
        pv2 = FilePV.load(str(tmp_path / "key.json"),
                          str(tmp_path / "state.json"))
        v2 = _vote(pv2, height=6)
        run(pv2.sign_vote(CHAIN, v2, sign_extension=False))
        assert v2.signature
    finally:
        F.reset()


# ------------------------------------------------- signer liveness


def test_signer_client_round_trip_times_out(tmp_path):
    """signer.round_trip.hang chaos site: a wedged signer trips the
    deadline with a typed SignerTimeoutError + counter instead of
    blocking forever."""
    from cometbft_tpu.libs import failures as F
    from cometbft_tpu.libs import metrics as m
    from cometbft_tpu.privval import SignerTimeoutError

    pv = _pv(tmp_path)

    async def main():
        F.configure(enabled=True, seed=7,
                    faults=["signer.round_trip.hang:at=1:delay=30"])
        server = SignerServer(pv)
        host, port = await server.listen()
        client = await SignerClient.connect(host, port, timeout_s=0.3)
        before = m.counter("privval_signer_timeouts_total").value()
        try:
            with pytest.raises(SignerTimeoutError):
                await client.sign_vote(CHAIN, _vote(client),
                                       sign_extension=False)
            assert m.counter("privval_signer_timeouts_total").value() \
                == before + 1
            # at=1 exhausted: the next round trip answers (the stream
            # is in an undefined frame state after an abandoned
            # request, so reconnect first like the listener does)
            client2 = await SignerClient.connect(host, port, timeout_s=5)
            v = _vote(client2)
            await client2.sign_vote(CHAIN, v, sign_extension=False)
            assert client2.get_pub_key().verify_signature(
                v.sign_bytes(CHAIN), v.signature)
            await client2.close()
        finally:
            await client.close()
            await server.close()
            F.reset()
        return True

    assert run(main())


def test_signer_listener_timeout_reconnects_and_retries(tmp_path):
    """A hung round trip through the SignerListener behaves exactly
    like a dropped connection: close + re-accept the signer's redial +
    retry once — consensus sees a signed vote, not a wedge."""
    from cometbft_tpu.libs import failures as F
    from cometbft_tpu.privval.signer import SignerListener, serve_dialer

    pv = _pv(tmp_path)

    async def main():
        F.configure(enabled=True, seed=7,
                    faults=["signer.round_trip.hang:at=1:delay=30"])
        listener = SignerListener(timeout_s=0.3)
        host, port = await listener.listen()
        dial_task = asyncio.create_task(
            serve_dialer(pv, host, port, max_retries=50,
                         retry_interval=0.05))
        try:
            await listener.wait_for_signer(timeout=10)
            v = _vote(listener)
            # first attempt hangs -> timeout -> reconnect -> retry OK
            await listener.sign_vote(CHAIN, v, sign_extension=False)
            assert listener.get_pub_key().verify_signature(
                v.sign_bytes(CHAIN), v.signature)
            assert any(e["site"] == "signer.round_trip.hang"
                       for e in F.events())
        finally:
            await listener.close()
            dial_task.cancel()
            F.reset()
        return True

    assert run(main())
