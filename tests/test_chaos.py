"""Deterministic fault-injection plane (libs/failures) + seeded chaos
acceptance.

Fast tier: plane semantics (seeded schedules, same-seed reproducibility,
spec parsing, env arming, phased arm/disarm), the per-site behavior of
the MConnection send/recv faults, the device dispatch hang/raise
rehearsal, and the fsyncgate halt-and-recover contract on a real node.

Slow tier: the 4-node mixed-fault acceptance run — partition, message
corruption, a device hang, and an fsync-EIO crash on one seeded
schedule, asserting safety (identical hashes), liveness (progress after
faults stop), a watchdog incident bundle for the halt, clean recovery of
the crashed node through the existing replay path, and that re-running
the same seed reproduces the identical fault event log.
"""

import asyncio
import errno
import os
import time

import pytest

from cometbft_tpu.libs import failures as F


@pytest.fixture(autouse=True)
def _clean_plane():
    """No chaos leaks into (or out of) any test."""
    F.reset()
    yield
    F.reset()


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


# ------------------------------------------------------------ plane: unit


def test_disabled_plane_is_a_noop():
    assert not F.is_enabled()
    assert F.fire("wal.fsync.eio") is None
    assert F.events() == [] and F.signature() == []
    assert F.stats() == {"enabled": False}


def test_rule_triggers_at_count_every_after_max():
    F.configure(enabled=True, seed=1, faults=[
        "a:at=2:at=5", "b:count=3", "c:every=3:max=2", "d:after=2:count=2"])
    fired = {s: [] for s in "abcd"}
    for n in range(1, 10):
        for s in "abcd":
            if F.fire(s) is not None:
                fired[s].append(n)
    assert fired["a"] == [2, 5]
    assert fired["b"] == [1, 2, 3]
    assert fired["c"] == [3, 6]            # every=3, bounded by max=2
    assert fired["d"] == [3, 4]            # offset by after=2


def test_same_seed_reproduces_identical_event_log():
    """The acceptance property in miniature: two same-seed drives of the
    same call pattern (including a probabilistic site) produce the
    identical fault event log."""

    def drive():
        F.configure(enabled=True, seed=99, faults=[
            "p.q:prob=0.25:max=6", "r.s:every=7", "t.u:at=11:delay=2.5"])
        for _ in range(40):
            F.fire("p.q")
            F.fire("r.s", chan="vote")
            F.fire("t.u")
        return F.signature(), [(e["site"], e["n"], e.get("delay"))
                               for e in F.events()]

    sig1, ev1 = drive()
    sig2, ev2 = drive()
    assert sig1 and sig1 == sig2
    assert ev1 == ev2
    assert ("t.u", 11, 2.5) in ev1          # params ride the event
    # a different seed moves the probabilistic fires
    F.configure(enabled=True, seed=100, faults=["p.q:prob=0.25:max=6"])
    for _ in range(40):
        F.fire("p.q")
    assert F.signature() != [s for s in sig1 if s[0] == "p.q"]


def test_fault_spec_parsing_and_errors():
    r = F.parse_fault_spec("wal.fsync.eio:at=40")
    assert r.site == "wal.fsync.eio" and r.at == {40}
    r = F.parse_fault_spec("x:prob=0.5:max=3:delay=1.5:cut=header")
    assert r.prob == 0.5 and r.max_fires == 3
    assert r.params == {"delay": 1.5, "cut": "header"}
    for bad in ("", "prob=1", "x:notakv", "x:prob=2", "x:at=abc"):
        with pytest.raises(F.FaultSpecError):
            F.parse_fault_spec(bad)
    # config validation surfaces spec errors at load time
    from cometbft_tpu.config import Config, ConfigError

    cfg = Config()
    cfg.chaos.enable = True
    cfg.chaos.faults = ["x:prob=2"]
    with pytest.raises(ConfigError):
        cfg.validate()


def test_env_var_arms_plane_over_config(monkeypatch):
    from cometbft_tpu.config import ChaosConfig

    monkeypatch.setenv(F.ENV_VAR,
                       "seed=9;log=4096;wal.fsync.eio:at=2;p.q:prob=0.1")
    F.configure_from_config(ChaosConfig())        # section disabled
    assert F.is_enabled()
    st = F.stats()
    assert st["seed"] == 9 and set(st["sites"]) == {"wal.fsync.eio", "p.q"}
    monkeypatch.delenv(F.ENV_VAR)
    F.reset()
    # without the env var, a disabled section leaves the plane down
    F.configure_from_config(ChaosConfig())
    assert not F.is_enabled()
    # and an enabled section arms it
    F.configure_from_config(ChaosConfig(enable=True, seed=3,
                                        faults=["a.b:at=1"]))
    assert F.is_enabled() and F.stats()["seed"] == 3


def test_phased_arm_disarm_keeps_log_and_counters():
    F.configure(enabled=True, seed=4, faults=["a:at=1"])
    assert F.fire("a") is not None
    F.arm("b:at=2")
    with pytest.raises(F.FaultSpecError):
        F.arm("b:at=3")                    # double-arm refused
    assert F.fire("b") is None and F.fire("b") is not None
    F.disarm("b")
    assert F.fire("b") is None
    # the log kept everything from before the disarm
    assert F.signature() == [("a", 1, 1), ("b", 2, 1)]


# -------------------------------------------------------- p2p conn sites


async def _mconn_net(descs):
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    from cometbft_tpu.p2p.conn import MConnection
    from cometbft_tpu.p2p.secret_connection import handshake

    accepted = asyncio.get_running_loop().create_future()

    async def on_conn(r, w):
        accepted.set_result((r, w))

    server = await asyncio.start_server(on_conn, "127.0.0.1", 0)
    host, port = server.sockets[0].getsockname()[:2]
    r1, w1 = await asyncio.open_connection(host, port)
    r2, w2 = await accepted
    c1, c2 = await asyncio.gather(
        handshake(r1, w1, Ed25519PrivKey.generate()),
        handshake(r2, w2, Ed25519PrivKey.generate()))
    got1, got2 = [], []
    m1 = MConnection(c1, descs, lambda ch, m: got1.append((ch, m)),
                     lambda e: got1.append(("err", e)))
    m2 = MConnection(c2, descs, lambda ch, m: got2.append((ch, m)),
                     lambda e: got2.append(("err", e)))
    m1.start(), m2.start()
    return server, m1, m2, got1, got2


async def _drain(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "condition never met"
        await asyncio.sleep(0.01)


def test_mconn_send_drop_and_recv_corrupt():
    from cometbft_tpu.p2p.reactor import ChannelDescriptor

    async def main():
        descs = [ChannelDescriptor(0x20, name="vote")]
        server, m1, m2, got1, got2 = await _mconn_net(descs)
        # first data packet dropped: the message silently vanishes
        F.configure(enabled=True, seed=7, faults=["p2p.send.drop:at=1"])
        assert m1.send(0x20, b"swallowed")
        await asyncio.sleep(0.3)
        assert got2 == []
        # next message passes (at=1 exhausted)
        assert m1.send(0x20, b"alive")
        await _drain(lambda: len(got2) >= 1)
        assert got2 == [(0x20, b"alive")]
        ev = F.events()
        assert [(e["site"], e["chan"]) for e in ev] == \
            [("p2p.send.drop", "vote")]
        # receive-side corruption: delivered, same length, wrong bytes
        F.arm("p2p.recv.corrupt:at=2")     # 2nd complete message POST-arm
        m1.send(0x20, b"ok-2")
        m1.send(0x20, b"corrupt-me")
        await _drain(lambda: len(got2) >= 3)
        assert got2[1] == (0x20, b"ok-2")
        chan, msg = got2[2]
        assert len(msg) == len(b"corrupt-me") and msg != b"corrupt-me"
        await m1.stop(), await m2.stop()
        server.close()
        return True

    assert run(main())


def test_mconn_duplicate_and_reorder():
    from cometbft_tpu.p2p.reactor import ChannelDescriptor

    async def main():
        descs = [ChannelDescriptor(0x20, name="vote")]
        server, m1, m2, got1, got2 = await _mconn_net(descs)
        # duplicate the first packet: one send, two deliveries
        F.configure(enabled=True, seed=7,
                    faults=["p2p.send.duplicate:at=1"])
        m1.send(0x20, b"twice")
        await _drain(lambda: len(got2) >= 2)
        assert got2 == [(0x20, b"twice"), (0x20, b"twice")]
        F.disarm("p2p.send.duplicate")
        # reorder: packet A held, B released first, then A
        got2.clear()
        F.arm("p2p.send.reorder:at=1")
        m1.send(0x20, b"A")
        m1.send(0x20, b"B")
        await _drain(lambda: len(got2) >= 2)
        assert got2 == [(0x20, b"B"), (0x20, b"A")]
        await m1.stop(), await m2.stop()
        server.close()
        return True

    assert run(main())


def test_mconn_reorder_flushes_held_packet_at_idle():
    """A reordered packet with no follow-up traffic must still arrive
    (released at wire idle), or a quiet channel would lose its tail."""
    from cometbft_tpu.p2p.reactor import ChannelDescriptor

    async def main():
        descs = [ChannelDescriptor(0x20, name="vote")]
        server, m1, m2, got1, got2 = await _mconn_net(descs)
        F.configure(enabled=True, seed=7, faults=["p2p.send.reorder:at=1"])
        m1.send(0x20, b"lonely")
        await _drain(lambda: len(got2) >= 1, timeout=3.0)
        assert got2 == [(0x20, b"lonely")]
        await m1.stop(), await m2.stop()
        server.close()
        return True

    assert run(main())


def test_fuzzer_routes_through_fault_plane():
    """Armed p2p.fuzz.* sites override the fuzzer's local probability
    draw, so connection fuzzing composes with chaos schedules (and its
    decisions land in the shared event log)."""
    from cometbft_tpu.p2p.fuzz import FuzzConnConfig, _Fuzzer

    class _W:
        closed = False

        def close(self):
            self.closed = True

    async def main():
        F.configure(enabled=True, seed=5,
                    faults=["p2p.fuzz.drop:at=2", "p2p.fuzz.kill:at=3"])
        # local probabilities all zero: only the plane can fire
        w = _W()
        fz = _Fuzzer(FuzzConnConfig(prob_drop_rw=0.0, start_after_s=0.0,
                                    seed=1), w)
        assert await fz.fuzz() is False
        assert await fz.fuzz() is True          # plane drop
        assert await fz.fuzz() is True and w.closed   # plane kill
        assert [e["site"] for e in F.events()] == \
            ["p2p.fuzz.drop", "p2p.fuzz.kill"]
        return True

    assert run(main())


# ----------------------------------------------------- device + storage


def test_device_dispatch_hang_and_raise_degrade_to_host():
    from cometbft_tpu.crypto import batch as B

    gauge, abandoned = B._device_health()
    before = abandoned.value()
    old_wait = B._DEVICE_WAIT_S
    B.set_device_wait(0.1)
    try:
        F.configure(enabled=True, seed=3,
                    faults=["device.dispatch.hang:at=1:delay=0.4",
                            "device.dispatch.raise:at=2"])
        # 1) hang past the bounded wait: abandoned, degraded gauge up
        assert B._device_call(lambda: 11) is None
        assert gauge.value() == 1
        assert abandoned.value() == before + 1
        time.sleep(0.5)                 # let the wedged future drain
        # 2) raise: same degrade path, NEVER an exception to the caller
        assert B._device_call(lambda: 12) is None
        assert abandoned.value() == before + 2
        # 3) recovered: next dispatch answers and clears the gauge
        assert B._device_call(lambda: 13) == 13
        assert gauge.value() == 0
        assert [(e["site"], e["n"]) for e in F.events()] == \
            [("device.dispatch.hang", 1), ("device.dispatch.raise", 2)]
    finally:
        B.set_device_wait(old_wait)


@pytest.mark.parametrize("fault", ["hang", "raise"])
def test_multi_chunk_patient_call_degrades_to_host_once(monkeypatch, fault):
    """A patient call of three chunks whose dispatch hangs or raises:
    the host's (right) answers, ONE abandonment however many chunks were
    in flight, and the next call rides the device again.  The device's
    programs are stand-ins that call every lane valid, so a lane named
    bad can only have come from the host."""
    import dataclasses

    import numpy as np

    from cometbft_tpu.crypto import batch as B
    from cometbft_tpu.crypto import plan as P
    from cometbft_tpu.crypto import rlc_finish
    from cometbft_tpu.crypto.keys import Ed25519PrivKey

    k, bad = 40, 20
    keys = [Ed25519PrivKey.from_secret(b"chaos-chunk-%d" % i)
            for i in range(k)]
    msgs = np.frombuffer(b"".join(b"m%031d" % i for i in range(k)),
                         np.uint8).reshape(k, 32)
    sigs = np.frombuffer(b"".join(
        key.sign(msgs[i].tobytes()) for i, key in enumerate(keys)),
        np.uint8).reshape(k, 64).copy()
    sigs[bad, 40] ^= 1
    pubs = np.frombuffer(b"".join(key.pub_key().bytes() for key in keys),
                         np.uint8).reshape(k, 32)
    call = dict(pubs=pubs, sigs=sigs, msgs=msgs,
                lens=np.full((k,), 32, np.int64), valset_pubs=pubs,
                scope=np.arange(k, dtype=np.int64), patient=True)

    launched = []
    monkeypatch.setattr(B, "_compiled_prepare_tables", lambda: lambda p: (
        np.zeros((1,), np.int32), np.ones((p.shape[0],), bool)))
    monkeypatch.setattr(
        B, "_compiled_rlc_gather",
        lambda: lambda *a: launched.append(int(a[2][0]))
        or rlc_finish.verdict(True))
    monkeypatch.setattr(B, "_DEVICE_INFLIGHT", None)
    saved, old_wait = P.active(), B._DEVICE_WAIT_S
    P.set_plan(dataclasses.replace(saved, lane_buckets=(16,),
                                   rlc_min_lanes=4), push_min_lanes=False)
    B.set_device_wait(0.1)
    gauge, abandoned = B._device_health()
    lanes = B._metrics()[1]
    before = abandoned.value(), lanes.value(route="device")
    try:
        F.configure(enabled=True, seed=3, faults=[
            "device.dispatch.hang:at=1:delay=1.0" if fault == "hang"
            else "device.dispatch.raise:at=1"])
        ok, oks = B.verify_dense("jax", **call)
        assert not ok and np.flatnonzero(~oks).tolist() == [bad]
        assert abandoned.value() == before[0] + 1 and gauge.value() == 1
        assert lanes.value(route="device") == before[1]
        if fault == "hang":
            assert launched == []
            # the abandoned call's chunks run out on the device-owner
            # thread afterwards and touch nobody's result
            deadline = time.monotonic() + 60
            while not B._DEVICE_INFLIGHT.done() \
                    and time.monotonic() < deadline:
                time.sleep(0.05)
            assert launched == [0, 16, 32]
            assert np.flatnonzero(~oks).tolist() == [bad]
        del launched[:]
        ok, oks = B.verify_dense("jax", **call)
        assert ok and oks.all()         # the stand-ins' answer: the device
        assert launched == [0, 16, 32]
        assert abandoned.value() == before[0] + 1 and gauge.value() == 0
        assert lanes.value(route="device") == before[1] + k
    finally:
        B.set_device_wait(old_wait)
        P.set_plan(saved, push_min_lanes=False)
        B._VALSET_TABLES.pop((id(pubs), ()), None)


def test_logdb_enospc_fails_handle_closed(tmp_path):
    from cometbft_tpu.storage.db import LogDB

    F.configure(enabled=True, seed=1, faults=["db.append.enospc:at=2"])
    db = LogDB(str(tmp_path / "kv.db"))
    db.set(b"a", b"1")
    with pytest.raises(OSError) as ei:
        db.set(b"b", b"2")
    assert ei.value.errno == errno.ENOSPC
    # fsyncgate: the handle is dead, no retry on the same fd
    with pytest.raises(OSError):
        db.set(b"c", b"3")
    db.close()
    F.reset()
    # restart replays the intact prefix: 'a' survived, 'b' never landed
    db2 = LogDB(str(tmp_path / "kv.db"))
    assert db2.get(b"a") == b"1" and db2.get(b"b") is None
    db2.set(b"d", b"4")                 # and the fresh handle writes
    db2.close()


# ------------------------------------------------ fsyncgate on a live node


def _genesis(n, chain_id, secret=b"chaos"):
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
    from cometbft_tpu.types.priv_validator import MockPV

    pvs = [MockPV.from_secret(secret + b"%d" % i) for i in range(n)]
    doc = GenesisDoc(chain_id=chain_id,
                     validators=[GenesisValidator(pv.get_pub_key(), 10)
                                 for pv in pvs])
    return doc, pvs


async def _mk_node(doc, pv, i, *, home=None, watchdog=False,
                   name_prefix="chaos", tweak=None, fast_sync=False):
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.config import Config, test_consensus_config
    from cometbft_tpu.node import Node
    from cometbft_tpu.p2p import NodeKey

    cfg = Config(consensus=test_consensus_config())
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = ""
    cfg.base.signature_backend = "cpu"
    if watchdog:
        cfg.instrumentation.watchdog_stall_threshold_s = 2.0
        cfg.instrumentation.watchdog_check_interval_s = 0.25
    else:
        cfg.instrumentation.watchdog_stall_threshold_s = 0.0
    if tweak is not None:
        tweak(cfg)
    node = await Node.create(
        doc, KVStoreApplication(), priv_validator=pv, config=cfg,
        node_key=NodeKey.from_secret(b"%s-%d" % (name_prefix.encode(), i)),
        home=home, name=f"{name_prefix}{i}", fast_sync=fast_sync)
    await node.start()
    return node


async def _wait_height(nodes, h, timeout=60.0):
    deadline = time.monotonic() + timeout
    while not all(n.height() >= h for n in nodes):
        assert time.monotonic() < deadline, \
            f"heights {[n.height() for n in nodes]} stuck below {h}"
        await asyncio.sleep(0.05)


def _find_bundle(inc_dir, reason, deadline_s=10.0):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        try:
            names = [n for n in os.listdir(inc_dir) if reason in n
                     and n.endswith(".json")]
        except OSError:
            names = []
        if names:
            return names[0]
        time.sleep(0.1)
    return None


@pytest.mark.timeout(120)
def test_wal_fsync_eio_halts_fatally_and_recovers_on_restart(tmp_path):
    """The fsyncgate regression (via the ``wal.fsync.eio`` site): an
    injected fsync failure halts consensus with ``fatal_error`` set (the
    watchdog bundles it) instead of being swallowed by the generic
    handler-error counter; a restart on the same home replays the WAL
    and keeps committing."""
    home = str(tmp_path / "solo")
    doc, pvs = _genesis(1, "fsyncgate-net", secret=b"fg")

    async def crash_phase():
        F.configure(enabled=True, seed=11, faults=["wal.fsync.eio:at=10"])
        node = await _mk_node(doc, pvs[0], 0, home=home, watchdog=True)
        try:
            deadline = time.monotonic() + 30
            while node.consensus.fatal_error is None:
                assert time.monotonic() < deadline, "never went fatal"
                await asyncio.sleep(0.05)
            err = node.consensus.fatal_error
            assert isinstance(err, OSError) and err.errno == errno.EIO
            # the WAL is dead: no retry on the same fd
            from cometbft_tpu.consensus.wal import WALError

            with pytest.raises(WALError):
                node.consensus.wal.flush_and_sync()
            # the watchdog turns the halt into an incident bundle
            bundle = await asyncio.to_thread(
                _find_bundle, node.incident_dir(), "consensus_fatal_error")
            assert bundle is not None, "no incident bundle for the halt"
            return node.height()
        finally:
            await node.stop()

    h_crash = run(crash_phase())
    F.reset()

    async def recover_phase():
        node = await _mk_node(doc, pvs[0], 0, home=home, watchdog=True)
        try:
            await _wait_height([node], h_crash + 2, timeout=60)
            assert node.consensus.fatal_error is None
        finally:
            await node.stop()
        return True

    assert run(recover_phase())


# ------------------------------------------------------- slow acceptance


async def _acceptance_scenario(base_dir: str) -> list[tuple]:
    """One seeded mixed-fault run; returns the fault-log signature.
    Phases: healthy start -> partition+heal -> message-corruption window
    -> device hang -> fsync-EIO crash -> restart/recover -> safety."""
    from cometbft_tpu.crypto import batch as B

    F.reset()
    F.configure(enabled=True, seed=2026,
                faults=["sched.dispatch.raise:at=1"])
    doc, pvs = _genesis(4, "chaos-net")
    victim_home = os.path.join(base_dir, "victim")
    nodes = []
    for i in range(4):
        nodes.append(await _mk_node(
            doc, pvs[i], i,
            home=victim_home if i == 3 else None,
            watchdog=(i == 3)))
    try:
        # mesh: node1's links are non-persistent so the partition below
        # stays down until explicitly healed; everything else reconnects
        for i, a in enumerate(nodes):
            for j in range(i + 1, 4):
                if 1 in (i, j):
                    continue
                await a.dial_peer(nodes[j].listen_addr, persistent=True)
        for j in (0, 2, 3):
            await nodes[1].dial_peer(nodes[j].listen_addr,
                                     persistent=False)
        await _wait_height(nodes, 3)

        # --- partition: node1 drops off; the 3/4 majority stays live
        for peer in list(nodes[1].switch.peers.values()):
            await nodes[1].switch.stop_peer_gracefully(peer)
        h0 = max(n.height() for n in nodes)
        others = [nodes[0], nodes[2], nodes[3]]
        await _wait_height(others, h0 + 3)
        assert nodes[1].height() < h0 + 3, "partition did not isolate"
        # heal (persistent now: later fault-induced teardowns reconnect)
        for j in (0, 2, 3):
            await nodes[1].dial_peer(nodes[j].listen_addr,
                                     persistent=True)
        await _wait_height(nodes, max(n.height() for n in nodes) + 2)

        # --- message-corruption window: every 15th delivered message,
        # 10 total; codec/signature rejection and reconnects absorb it
        F.arm("p2p.recv.corrupt:every=15:max=10")
        deadline = time.monotonic() + 45
        while sum(1 for e in F.events()
                  if e["site"] == "p2p.recv.corrupt") < 10:
            assert time.monotonic() < deadline, "corruption never drained"
            await asyncio.sleep(0.1)
        await _wait_height(nodes, max(n.height() for n in nodes) + 2)

        # --- scheduler dispatch failure: force one micro-batch through
        # the armed site (in-proc nets cache-hit around natural
        # batches); the injected raise must still demux REAL per-item
        # verdicts to every batchmate
        from cometbft_tpu.crypto import scheduler as vsched
        from cometbft_tpu.crypto.keys import gen_priv_key

        sched = vsched.get_scheduler()
        assert sched is not None and sched.is_running
        privs = [gen_priv_key() for _ in range(3)]
        msgs = [b"chaos-acc-%d" % i for i in range(3)]
        sigs = [p.sign(m) for p, m in zip(privs, msgs)]
        sigs[1] = bytes(64)
        oks = await asyncio.gather(*[
            sched.verify(p.pub_key(), m, s)
            for p, m, s in zip(privs, msgs, sigs)])
        assert oks == [True, False, True], oks
        assert any(e["site"] == "sched.dispatch.raise"
                   for e in F.events())

        # --- device hang (CPU rehearsal): the bounded wait abandons the
        # dispatch, verification degrades to host, then recovers
        F.arm("device.dispatch.hang:at=1:delay=0.4")
        old_wait = B._DEVICE_WAIT_S
        B.set_device_wait(0.1)
        try:
            gauge, _ = B._device_health()
            assert B._device_call(lambda: 7) is None
            assert gauge.value() == 1
            await asyncio.sleep(0.5)
            assert B._device_call(lambda: 7) == 7
            assert gauge.value() == 0
        finally:
            B.set_device_wait(old_wait)

        # --- fsync EIO on the victim: fatal halt + incident bundle,
        # while the 3/4 majority keeps committing
        F.arm("wal.fsync.eio:at=3")
        deadline = time.monotonic() + 30
        while nodes[3].consensus.fatal_error is None:
            assert time.monotonic() < deadline, "victim never halted"
            await asyncio.sleep(0.05)
        err = nodes[3].consensus.fatal_error
        assert isinstance(err, OSError) and err.errno == errno.EIO
        h2 = max(n.height() for n in others)
        await _wait_height([nodes[0], nodes[2]], h2 + 3)
        bundle = await asyncio.to_thread(
            _find_bundle, nodes[3].incident_dir(), "consensus_fatal_error")
        assert bundle is not None, "no watchdog bundle for the halt"

        # --- recovery: restart the victim from the same home (WAL torn
        # tail truncated, replay, rejoin, catch up)
        F.disarm("wal.fsync.eio")
        await nodes[3].stop()
        nodes[3] = await _mk_node(doc, pvs[3], 3, home=victim_home,
                                  watchdog=True)
        for j in (0, 1, 2):
            await nodes[3].dial_peer(nodes[j].listen_addr,
                                     persistent=True)
        target = max(n.height() for n in nodes[:3]) + 2
        await _wait_height(nodes, target, timeout=90)
        assert nodes[3].consensus.fatal_error is None

        # --- safety: every height every node holds is the same block
        common = min(n.height() for n in nodes)
        assert common >= target - 1
        for h in range(1, common + 1):
            hashes = {n.block_store.load_block(h).hash() for n in nodes
                      if n.block_store.load_block(h) is not None}
            assert len(hashes) == 1, f"fork at height {h}: {hashes}"

        return F.signature()
    finally:
        for n in nodes:
            try:
                await n.stop()
            except Exception:
                pass
        F.reset()


@pytest.mark.slow
@pytest.mark.timeout(500)
def test_chaos_acceptance_4node_mixed_faults(tmp_path):
    sig1 = run(_acceptance_scenario(str(tmp_path / "run1")))
    sig2 = run(_acceptance_scenario(str(tmp_path / "run2")))
    # same seed, same scenario -> the identical fault event log
    assert sig1 == sig2
    assert ("wal.fsync.eio", 3, 1) in sig1
    assert ("device.dispatch.hang", 1, 1) in sig1
    assert ("sched.dispatch.raise", 1, 1) in sig1
    corrupts = [s for s in sig1 if s[0] == "p2p.recv.corrupt"]
    assert len(corrupts) == 10
    # every=15 fires at exact call indices — the deterministic schedule
    assert [n for _, n, _ in corrupts] == [15 * k for k in range(1, 11)]


# --------------------------------------------------------------------------
# PR 9 acceptance: the chaos plane as forcing function for the peer-quality
# defense layer — a seeded 3-node run where ONE peer's links are armed with
# p2p.send.corrupt (node=<name> selector): the victim scores it down, issues
# a timed ban, keeps committing off the good peer, and readmits the peer
# after the ban expires; the fault log reproduces identically across two
# same-seed runs.

BADPEER_SEED = 90210
BADPEER_MAX_FIRES = 8
BADPEER_SPEC = f"p2p.send.corrupt:node=bqbad0:every=2:max={BADPEER_MAX_FIRES}"


async def _badpeer_scenario() -> tuple:
    from cometbft_tpu.libs import metrics as m
    from cometbft_tpu.rpc.core import Environment, net_info

    doc, pvs = _genesis(2, "badpeer-net", secret=b"badpeer")
    F.reset()
    F.configure(enabled=True, seed=BADPEER_SEED, faults=[BADPEER_SPEC])

    def victim_tweak(cfg):
        # two scoring events (weight >= 1.5 each) ban; short TTL so the
        # readmission leg fits the test budget
        cfg.p2p.quality_disconnect_score = 1.5
        cfg.p2p.quality_ban_score = 3.5
        cfg.p2p.quality_ban_ttl_s = 1.5
        cfg.p2p.quality_half_life_s = 600.0

    victim = await _mk_node(doc, pvs[0], 0, name_prefix="bq",
                            tweak=victim_tweak)
    good = await _mk_node(doc, pvs[1], 1, name_prefix="bq")
    # the corrupting node: a non-validator observer whose OUTBOUND links
    # are armed via the node= selector (name "bqbad0" = chaos scope)
    bad = await _mk_node(doc, None, 0, name_prefix="bqbad")
    nodes = [victim, good, bad]
    try:
        await good.dial_peer(victim.listen_addr, persistent=True)
        # persistent FROM the bad node's side: it keeps re-dialing after
        # every disconnect/ban, which is what exercises readmission (on
        # the VICTIM's side it is inbound and fully bannable)
        await bad.dial_peer(victim.listen_addr, persistent=True)
        bad_id = bad.node_key.id
        vsw = victim.switch

        await _wait_height([victim, good], 2, timeout=30.0)

        # --- score decay -> timed ban ---------------------------------
        deadline = time.monotonic() + 45
        while vsw.scorer.bans_total < 1:
            assert time.monotonic() < deadline, \
                f"no ban; scorer={vsw.scorer.snapshot()} " \
                f"chaos={F.stats()['sites']}"
            await asyncio.sleep(0.05)
        bans_metric = sum(
            m.counter("p2p_peer_bans_total").value(
                node=victim.node_key.id[:8], reason=r)
            for r in ("malformed_frame", "protocol_error", "invalid_vote",
                      "invalid_part", "invalid_proposal"))
        assert bans_metric >= 1
        ni = await net_info(Environment(victim))
        if vsw.scorer.is_banned(bad_id):     # may already have expired
            assert any(b["node_id"] == bad_id for b in ni["bans"])

        # --- liveness off the good peer THROUGH the ban ---------------
        h_ban = victim.height()
        await _wait_height([victim, good], h_ban + 3, timeout=45.0)

        # --- schedule drains; peer readmitted after expiry ------------
        deadline = time.monotonic() + 60
        while True:
            fired = F.stats()["sites"]["p2p.send.corrupt"]["fired"]
            if fired >= BADPEER_MAX_FIRES and \
                    not vsw.scorer.is_banned(bad_id) and \
                    bad_id in vsw.peers:
                break
            assert time.monotonic() < deadline, \
                f"no readmission: fired={fired} " \
                f"banned={vsw.scorer.is_banned(bad_id)} " \
                f"connected={bad_id in vsw.peers}"
            await asyncio.sleep(0.1)
        # readmitted peer carries its quality history in /net_info
        snap = {p["node_id"]: p for p in vsw.peer_snapshot()}
        assert snap[bad_id]["quality"]["ban_count"] >= 1

        # --- fork-free at every common height -------------------------
        common = min(victim.height(), good.height())
        hashes = []
        for h in range(1, common + 1):
            hs = {n.block_store.load_block(h).hash()
                  for n in (victim, good)
                  if n.block_store.load_block(h) is not None}
            assert len(hs) == 1, f"fork at {h}"
            hashes.append(hs.pop().hex())
        return F.signature(), hashes
    finally:
        for n in nodes:
            try:
                await n.stop()
            except Exception:
                pass
        F.reset()


@pytest.mark.slow
@pytest.mark.timeout(400)
def test_badpeer_acceptance_score_ban_readmit():
    sig1, hashes1 = run(_badpeer_scenario())
    sig2, hashes2 = run(_badpeer_scenario())
    # same seed -> identical fault-log signature across the two runs
    assert sig1 == sig2
    corrupts = sorted(s for s in sig1 if s[0] == "p2p.send.corrupt")
    assert len(corrupts) == BADPEER_MAX_FIRES
    # every=2 over the BAD node's send stream only (node= selector):
    # exact call indices, independent of the other nodes' traffic
    assert [n for _, n, _ in corrupts] == \
        [2 * k for k in range(1, BADPEER_MAX_FIRES + 1)]
    assert len(hashes1) >= 5


# --------------------------------------------------------------------------
# PR 10: storage integrity doctor + privval/signer hardening


@pytest.mark.timeout(120)
def test_privval_state_eio_halts_fatally_with_bundle(tmp_path):
    """The privval fsyncgate satellite (via ``privval.state.fsync.eio``):
    a failed sign-state persist must NOT release the signature — the
    node halts fatally (watchdog bundles it) instead of signing on top
    of an unknown on-disk guard; a restart on the same home recovers."""
    from cometbft_tpu.privval import FilePV, SignStateError

    home = str(tmp_path / "solo")
    key_path = str(tmp_path / "pvkey.json")
    state_path = os.path.join(home, "data", "priv_validator_state.json")
    pv = FilePV.generate(key_path, state_path)
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator

    doc = GenesisDoc(chain_id="pv-eio-net",
                     validators=[GenesisValidator(pv.get_pub_key(), 10)])

    async def crash_phase():
        F.configure(enabled=True, seed=5,
                    faults=["privval.state.fsync.eio:at=5"])
        node = await _mk_node(doc, pv, 0, home=home, watchdog=True)
        try:
            deadline = time.monotonic() + 30
            while node.consensus.fatal_error is None:
                assert time.monotonic() < deadline, "never went fatal"
                await asyncio.sleep(0.05)
            err = node.consensus.fatal_error
            assert isinstance(err, OSError) and err.errno == errno.EIO
            # the privval handle is dead: every further sign refuses
            from cometbft_tpu.types.block_id import BlockID
            from cometbft_tpu.types.vote import PREVOTE_TYPE, Vote

            dead_probe = Vote(
                type=PREVOTE_TYPE, height=99, round=0,
                block_id=BlockID(), timestamp_ns=1,
                validator_address=pv.get_pub_key().address(),
                validator_index=0)
            with pytest.raises(SignStateError):
                await pv.sign_vote(doc.chain_id, dead_probe,
                                   sign_extension=False)
            assert dead_probe.signature == b""    # never released
            bundle = await asyncio.to_thread(
                _find_bundle, node.incident_dir(), "consensus_fatal_error")
            assert bundle is not None, "no incident bundle for the halt"
            return node.height()
        finally:
            await node.stop()

    h_crash = run(crash_phase())
    F.reset()

    async def recover_phase():
        # restart reloads the sign state that DID land: double-sign
        # protection intact, consensus resumes
        pv2 = FilePV.load(key_path, state_path)
        node = await _mk_node(doc, pv2, 0, home=home, watchdog=True)
        try:
            await _wait_height([node], h_crash + 2, timeout=60)
            assert node.consensus.fatal_error is None
        finally:
            await node.stop()
        return True

    assert run(recover_phase())


# --------------------------------------------------------------------------
# PR 10 acceptance: seeded mid-log blockstore corruption -> boot-time
# detection (salvage + doctor deep scan) -> repair (truncate to last
# verified height) -> blocksync re-fetch -> fork-free, run twice with
# identical fault signatures.  The victim is a REAL FilePV validator: its
# persisted last-sign-state is what makes the mid-round rejoin
# equivocation-free (re-signs return the stored signature).

DOCTOR_SEED = 77010
DOCTOR_SPEC = "db.replay.corrupt:file=blockstore.db:at=1:frac=0.5"


async def _doctor_scenario(base_dir: str) -> tuple:
    from cometbft_tpu.privval import FilePV
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
    from cometbft_tpu.types.priv_validator import MockPV

    F.reset()
    victim_home = os.path.join(base_dir, "victim")
    pvs = [MockPV.from_secret(b"drv%d" % i) for i in range(2)]
    victim_pv = FilePV.generate(
        os.path.join(base_dir, "victim_key.json"),
        os.path.join(victim_home, "data", "priv_validator_state.json"))
    pvs.append(victim_pv)
    doc = GenesisDoc(chain_id="doctor-acc-net",
                     validators=[GenesisValidator(pv.get_pub_key(), 10)
                                 for pv in pvs])
    nodes = []
    for i in range(3):
        nodes.append(await _mk_node(
            doc, pvs[i], i, home=victim_home if i == 2 else None,
            name_prefix="dr"))
    try:
        for i in range(3):
            for j in range(i + 1, 3):
                await nodes[i].dial_peer(nodes[j].listen_addr,
                                         persistent=True)
        await _wait_height(nodes, 6, timeout=45)
        h_stop = nodes[2].height()
        await nodes[2].stop()

        # ---- arm the seeded bit-flip for the victim's NEXT blockstore
        # open (at-rest bit-rot, file-selected so the other stores'
        # opens don't consume the schedule)
        F.configure(enabled=True, seed=DOCTOR_SEED, faults=[DOCTOR_SPEC])
        victim_pv2 = FilePV.load(
            os.path.join(base_dir, "victim_key.json"),
            os.path.join(victim_home, "data",
                         "priv_validator_state.json"))
        victim = await _mk_node(doc, victim_pv2, 2, home=victim_home,
                                name_prefix="dr", fast_sync=True)
        nodes[2] = victim

        # ---- boot-time detection: salvage fired, the doctor deep scan
        # gated the salvaged store and repaired it
        rep = victim.doctor_report.to_dict()
        assert rep["salvage"].get("blockstore", {}).get(
            "salvaged_this_open"), rep
        assert rep["deep_scan"] is not None, rep
        assert rep["ok"] and rep["refused"] is None, rep
        repaired = rep["deep_scan"].get("truncated_to") is not None or \
            any("ahead" in a for a in rep["actions"])
        assert repaired or rep["deep_scan"]["ok"], rep
        assert not victim.block_store.is_dirty()     # verified or rebuilt

        # ---- blocksync re-fetch + consensus rejoin: all three advance
        for j in (0, 1):
            await victim.dial_peer(nodes[j].listen_addr, persistent=True)
        target = max(h_stop, max(n.height() for n in nodes[:2])) + 2
        await _wait_height(nodes, target, timeout=90)
        assert victim.consensus.fatal_error is None

        # ---- fork-free at EVERY common height
        common = min(n.height() for n in nodes)
        hashes = []
        for h in range(1, common + 1):
            hs = {n.block_store.load_block(h).hash() for n in nodes
                  if n.block_store.load_block(h) is not None}
            assert len(hs) == 1, f"fork at height {h}: {hs}"
            hashes.append(hs.pop().hex())
        return F.signature(), rep["deep_scan"].get("truncated_to"), hashes
    finally:
        for n in nodes:
            try:
                await n.stop()
            except Exception:
                pass
        F.reset()


@pytest.mark.slow
@pytest.mark.timeout(400)
def test_doctor_acceptance_corrupt_restart_repair_catchup(tmp_path):
    sig1, trunc1, hashes1 = run(_doctor_scenario(str(tmp_path / "run1")))
    sig2, trunc2, hashes2 = run(_doctor_scenario(str(tmp_path / "run2")))
    # same seed -> the identical fault signature, at the exact call index
    assert sig1 == sig2 == [("db.replay.corrupt", 1, 1)]
    assert len(hashes1) >= 6 and len(hashes2) >= 6
