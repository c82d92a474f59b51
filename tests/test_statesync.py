"""Statesync: a fresh node restores an application snapshot from peers —
verified against the light client — instead of replaying the chain, then
follows via blocksync + consensus (reference: ``statesync/syncer_test.go``
and the node-startup handoff)."""

import asyncio

import pytest

from cometbft_tpu.abci.kvstore import KVStoreApplication
from cometbft_tpu.config import Config
from cometbft_tpu.config import test_consensus_config as _tcc
from cometbft_tpu.light import Client, LocalNodeProvider, TrustOptions
from cometbft_tpu.node import Node
from cometbft_tpu.p2p import NodeKey
from cometbft_tpu.statesync import StateProvider
from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
from cometbft_tpu.types.priv_validator import MockPV

pytestmark = pytest.mark.timeout(150)

PERIOD = 3600 * 1_000_000_000


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def _config() -> Config:
    cfg = Config(consensus=_tcc())
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    return cfg


def test_statesync_bootstraps_fresh_node():
    async def main():
        pvs = [MockPV.from_secret(b"ssnode%d" % i) for i in range(3)]
        doc = GenesisDoc(chain_id="ss-net",
                         validators=[GenesisValidator(pv.get_pub_key(), 10)
                                     for pv in pvs])
        nodes = []
        for i, pv in enumerate(pvs):
            n = await Node.create(
                doc, KVStoreApplication(), priv_validator=pv,
                config=_config(),
                node_key=NodeKey.from_secret(b"ssk%d" % i), name=f"ss{i}")
            nodes.append(n)
            await n.start()
        for i, a in enumerate(nodes):
            for b in nodes[i + 1:]:
                await a.dial_peer(b.listen_addr, persistent=True)

        async def reach(h, who):
            while not all(n.height() >= h for n in who):
                await asyncio.sleep(0.02)

        try:
            # build history with some app state
            for i in range(4):
                await nodes[0].mempool.check_tx(b"sk%d=sv%d" % (i, i))
            await asyncio.wait_for(reach(8, nodes), 60)

            # the joining node trusts a recent header out of band
            trust_h = 2
            trust_hash = nodes[0].block_store.load_block(trust_h).hash()
            light = Client(
                "ss-net", TrustOptions(PERIOD, trust_h, trust_hash),
                LocalNodeProvider(nodes[0].block_store,
                                  nodes[0].state_store),
                backend="cpu")
            provider = StateProvider(light, doc)

            fresh = await Node.create(
                doc, KVStoreApplication(), config=_config(),
                node_key=NodeKey.from_secret(b"ssk9"),
                state_sync_provider=provider, name="ssfresh")
            nodes.append(fresh)
            await fresh.start()
            for a in nodes[:3]:
                await fresh.dial_peer(a.listen_addr, persistent=True)

            # must state-sync (no history below the snapshot), then follow
            target = max(n.height() for n in nodes[:3]) + 3
            await asyncio.wait_for(reach(target, [fresh]), 90)
            assert fresh.block_store.base() > 1, \
                "node replayed from genesis instead of state syncing"
            # restored app state contains pre-snapshot keys
            q = await fresh.app_conns.query.query("/key", b"sk0", 0, False)
            assert q.value == b"sv0"
            # chain agreement at the target height
            hashes = {n.block_store.load_block(target).hash()
                      for n in nodes if n.block_store.load_block(target)}
            assert len(hashes) == 1
        finally:
            for n in nodes:
                try:
                    await n.stop()
                except Exception:
                    pass
        return True

    assert run(main())


def test_syncer_honors_reject_senders_and_refetch():
    """The full ApplySnapshotChunkResponse shape (abci
    ApplySnapshotChunkResponse): an app naming a bad sender gets that
    peer banned and the chunk refetched from the remaining peer; restore
    completes from the honest data."""
    from cometbft_tpu.abci import types as abci_t
    from cometbft_tpu.abci.types import Snapshot
    from cometbft_tpu.statesync.syncer import Syncer

    class StubSnapshotConn:
        def __init__(self):
            self.applied = {}
            self.banned = False

        async def offer_snapshot(self, snapshot, app_hash):
            return abci_t.OFFER_SNAPSHOT_ACCEPT

        async def apply_snapshot_chunk(self, index, chunk, sender):
            if chunk.startswith(b"EVIL"):
                self.banned = True
                return abci_t.ApplySnapshotChunkResponse(
                    result=abci_t.APPLY_CHUNK_ACCEPT,   # result ignored:
                    refetch_chunks=[index],             # chunk re-pulled
                    reject_senders=["evil"])
            self.applied[index] = chunk
            return abci_t.APPLY_CHUNK_ACCEPT            # bare-int form

    class StubQueryConn:
        def __init__(self, h, app_hash):
            self._h, self._hash = h, app_hash

        async def info(self):
            from cometbft_tpu.abci.types import InfoResponse

            return InfoResponse(last_block_height=self._h,
                                last_block_app_hash=self._hash)

    class StubProvider:
        async def app_hash(self, h):
            return b"\xab" * 32

        async def state(self, h):
            return "STATE"

        async def commit(self, h):
            return "COMMIT"

    class StubReactor:
        def __init__(self, syncer_ref):
            self.syncer_ref = syncer_ref
            self.requests = []

        def request_chunk(self, peer, height, format_, index, h):
            self.requests.append((peer, index))
            # deliver async like the network would
            data = (b"EVIL-%d" % index) if peer == "evil" \
                else (b"GOOD-%d" % index)

            async def deliver():
                self.syncer_ref[0].add_chunk(peer, height, format_,
                                             index, data, h)

            asyncio.get_event_loop().create_task(deliver())

    async def main():
        class Conns:
            pass

        conns = Conns()
        snap_conn = StubSnapshotConn()
        conns.snapshot = snap_conn
        conns.query = StubQueryConn(5, b"\xab" * 32)
        ref = [None]
        reactor = StubReactor(ref)
        syncer = Syncer(conns, StubProvider(), reactor=reactor)
        ref[0] = syncer
        snapshot = Snapshot(height=5, format=1, chunks=3,
                            hash=b"\xcd" * 32, metadata=b"")
        # the EVIL peer is first in the rotation, so chunk 0 comes bad
        syncer.add_snapshot("evil", snapshot)
        syncer.add_snapshot("good", snapshot)

        state, commit = await syncer._restore(
            syncer._snapshots[(5, 1, b"\xcd" * 32)])
        assert state == "STATE" and commit == "COMMIT"
        assert snap_conn.banned
        # all three chunks ultimately applied from the honest peer
        assert set(snap_conn.applied) == {0, 1, 2}
        assert all(v.startswith(b"GOOD") for v in snap_conn.applied.values())
        # the banned peer got no further requests after the rejection:
        # its only request is the initial round-robin one for chunk 0
        evil_req_positions = [k for k, (p, _) in
                              enumerate(reactor.requests) if p == "evil"]
        good_req_positions = [k for k, (p, _) in
                              enumerate(reactor.requests) if p == "good"]
        assert len(good_req_positions) >= 3
        assert evil_req_positions, "evil never even asked once"
        # evil can appear only in the initial round-robin pass over the
        # 3 chunks (at most 2 of 3 with 2 peers); everything after the
        # ban goes to good
        assert len(evil_req_positions) <= 2, \
            "banned peer kept receiving requests"
        return True

    assert run(main())


def test_syncer_offer_reject_format_and_sender():
    """OFFER_SNAPSHOT_REJECT_FORMAT skips every snapshot of that format;
    REJECT_SENDER distrusts the advertising peers (syncer.go:208-212)."""
    from cometbft_tpu.abci import types as abci_t
    from cometbft_tpu.abci.types import Snapshot
    from cometbft_tpu.statesync.syncer import StatesyncError, Syncer

    offers = []

    class SnapConn:
        async def offer_snapshot(self, snapshot, app_hash):
            offers.append((snapshot.height, snapshot.format))
            if snapshot.format == 9:
                return abci_t.OFFER_SNAPSHOT_REJECT_FORMAT
            return abci_t.OFFER_SNAPSHOT_REJECT_SENDER

    class Provider:
        async def app_hash(self, h):
            return b"\x01" * 32

    async def main():
        class Conns:
            pass

        conns = Conns()
        conns.snapshot = SnapConn()
        syncer = Syncer(conns, Provider())

        async def advertise():
            # sync() clears the pool at round start; deliver the offers
            # during the discovery window like the reactor would
            await asyncio.sleep(0.05)
            for h in (10, 20):
                syncer.add_snapshot("pA", Snapshot(height=h, format=9,
                                                   chunks=1, hash=b"\x09",
                                                   metadata=b""))
                syncer.add_snapshot("pB", Snapshot(height=h, format=1,
                                                   chunks=1, hash=b"\x01",
                                                   metadata=b""))

        adv = asyncio.get_event_loop().create_task(advertise())
        with pytest.raises(StatesyncError):
            await syncer.sync(discovery_time=0.2, rounds=1)
        await adv

        # format 9 was offered exactly once (highest height), then the
        # whole format was skipped; format-1 offers hit REJECT_SENDER so
        # both peers end up distrusted
        f9 = [o for o in offers if o[1] == 9]
        assert f9 == [(20, 9)], offers
        assert any(o[1] == 1 for o in offers)
        assert "pB" in syncer._banned
        return True

    assert run(main())


def test_concurrent_chunk_fetch_scales_with_peers():
    """per-peer in-flight caps make restore bandwidth
    scale with the number of serving peers — doubling peers roughly
    halves wall-clock — while no peer ever holds more than
    MAX_INFLIGHT_PER_PEER outstanding requests."""
    import time

    from cometbft_tpu.abci import types as abci_t
    from cometbft_tpu.abci.types import InfoResponse, Snapshot
    from cometbft_tpu.statesync.syncer import (MAX_INFLIGHT_PER_PEER,
                                               Syncer)

    N_CHUNKS = 16
    SERVE_DELAY = 0.02          # per-chunk service time per peer

    class SnapConn:
        async def offer_snapshot(self, snapshot, app_hash):
            return abci_t.OFFER_SNAPSHOT_ACCEPT

        async def apply_snapshot_chunk(self, index, chunk, sender):
            return abci_t.APPLY_CHUNK_ACCEPT

    class QueryConn:
        async def info(self):
            return InfoResponse(last_block_height=7,
                                last_block_app_hash=b"\xab" * 32)

    class Provider:
        async def app_hash(self, h):
            return b"\xab" * 32

        async def state(self, h):
            return "S"

        async def commit(self, h):
            return "C"

    class SerialPeerReactor:
        """Each peer is a serial worker: one chunk every SERVE_DELAY —
        models per-peer bandwidth, so aggregate throughput is
        proportional to peer count only if requests spread out."""

        def __init__(self, syncer_ref):
            self.syncer_ref = syncer_ref
            self.queues: dict[str, asyncio.Queue] = {}
            self.max_inflight: dict[str, int] = {}
            self.inflight: dict[str, int] = {}
            self.workers = []

        def request_chunk(self, peer, height, format_, index, h):
            self.inflight[peer] = self.inflight.get(peer, 0) + 1
            self.max_inflight[peer] = max(self.max_inflight.get(peer, 0),
                                          self.inflight[peer])
            if peer not in self.queues:
                self.queues[peer] = asyncio.Queue()
                self.workers.append(asyncio.get_event_loop().create_task(
                    self._serve(peer)))
            self.queues[peer].put_nowait((height, format_, index, h))

        async def _serve(self, peer):
            while True:
                height, format_, index, h = await self.queues[peer].get()
                await asyncio.sleep(SERVE_DELAY)
                self.inflight[peer] -= 1
                self.syncer_ref[0].add_chunk(peer, height, format_, index,
                                             b"DATA-%d" % index, h)

    async def restore_with(n_peers: int) -> tuple[float, dict]:
        class Conns:
            pass

        conns = Conns()
        conns.snapshot = SnapConn()
        conns.query = QueryConn()
        ref = [None]
        reactor = SerialPeerReactor(ref)
        syncer = Syncer(conns, Provider(), reactor=reactor)
        ref[0] = syncer
        snapshot = Snapshot(height=7, format=1, chunks=N_CHUNKS,
                            hash=b"\xcd" * 32, metadata=b"")
        for k in range(n_peers):
            syncer.add_snapshot(f"peer{k}", snapshot)
        t0 = time.perf_counter()
        await syncer._restore(syncer._snapshots[(7, 1, b"\xcd" * 32)])
        dt = time.perf_counter() - t0
        for w in reactor.workers:
            w.cancel()
        return dt, reactor.max_inflight

    t1, m1 = run(restore_with(1))
    t2, m2 = run(restore_with(2))
    t4, m4 = run(restore_with(4))
    for m in (m1, m2, m4):
        assert all(v <= MAX_INFLIGHT_PER_PEER for v in m.values()), m
    # 2 peers ~halve, 4 peers ~quarter (generous slack for event-loop
    # jitter; the unscaled ratio would be ~1.0)
    assert t2 < t1 * 0.7, (t1, t2)
    assert t4 < t1 * 0.45, (t1, t4)


def test_chunk_store_spools_to_disk(tmp_path, monkeypatch):
    """Chunks live on disk while awaiting the sequential applier
    (reference chunks.go), are freed as they apply, and the spool dir is
    removed after a successful restore."""
    import os
    import tempfile

    from cometbft_tpu.statesync.syncer import _ChunkStore

    # pytest owns cleanup even if an assertion below fails mid-test
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    store = _ChunkStore()
    assert store._dir is None                 # lazy: no dir until a write
    store[2] = (b"C2" * 100, "p1")
    store[0] = (b"C0" * 100, "p2")
    d = store._dir
    assert d and len(os.listdir(d)) == 2      # bytes live on disk...
    assert 0 in store and 1 not in store
    assert store[2] == (b"C2" * 100, "p1")
    assert store.indices_from("p2") == [0]
    store.pop(0)
    assert len(os.listdir(d)) == 1            # ...freed on apply
    store.close()
    assert not os.path.exists(d)


def test_add_chunk_rejects_malicious_indices():
    """A chunk index off the wire becomes a spool FILENAME: non-int,
    negative, out-of-range, and bool indices must all be dropped (path
    traversal / orphan-file defense)."""
    from cometbft_tpu.abci.types import Snapshot
    from cometbft_tpu.statesync.syncer import Syncer, _PendingSnapshot

    async def main():
        sy = Syncer(app_conns=None, state_provider=None)
        snap = Snapshot(height=7, format=1, chunks=4, hash=b"\xcd" * 32,
                        metadata=b"")
        sy._current = _PendingSnapshot(snap)
        for bad in ("../../etc/x", -1, 4, 10**9, True, None, 2.0):
            sy.add_chunk("p", 7, 1, bad, b"data", b"\xcd" * 32)
        await asyncio.sleep(0.05)      # let any (wrong) spool task land
        assert sy._chunks._senders == {}
        assert sy._chunks._dir is None, "a bad index touched the disk"
        # a GOOD index still spools
        sy.add_chunk("p", 7, 1, 2, b"data", b"\xcd" * 32)
        await asyncio.sleep(0.05)
        assert 2 in sy._chunks
        sy._chunks.close()
        return True

    assert run(main())
