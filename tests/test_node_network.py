"""Tier-2 tests: full Node assemblies talking over REAL localhost TCP —
the reference's e2e tier shrunk to one machine (``test/e2e/README.md``,
SURVEY §4 "three tiers").  Exercises the whole stack: transport secret
connections, MConnection channels, consensus + mempool reactors, gossip,
WAL, handshake."""

import asyncio

import pytest

from cometbft_tpu.abci.kvstore import KVStoreApplication
from cometbft_tpu.config import Config
from cometbft_tpu.config import test_consensus_config as make_test_consensus_config
from cometbft_tpu.node import Node
from cometbft_tpu.p2p import NodeKey
from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
from cometbft_tpu.types.priv_validator import MockPV

# live multi-node TCP nets — tier-2 with the other net suites.
pytestmark = [pytest.mark.timeout(150), pytest.mark.slow]


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def _genesis(n: int, chain_id="tcp-net"):
    pvs = [MockPV.from_secret(b"tcpnode%d" % i) for i in range(n)]
    doc = GenesisDoc(chain_id=chain_id,
                     validators=[GenesisValidator(pv.get_pub_key(), 10)
                                 for pv in pvs])
    return doc, pvs


def _config() -> Config:
    cfg = Config(consensus=make_test_consensus_config())
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    return cfg


async def _make_net(n: int, homes=None):
    doc, pvs = _genesis(n)
    nodes = []
    for i in range(n):
        node = await Node.create(
            doc, KVStoreApplication(), priv_validator=pvs[i],
            config=_config(), node_key=NodeKey.from_secret(b"nk%d" % i),
            home=(homes[i] if homes else None), name=f"tnode{i}")
        nodes.append(node)
    for node in nodes:
        await node.start()
    # full mesh: i dials j for i < j
    for i, a in enumerate(nodes):
        for b in nodes[i + 1:]:
            await a.dial_peer(b.listen_addr, persistent=True)
    return nodes


async def _wait_height(nodes, h, timeout=90.0):
    async def all_reached():
        while not all(n.height() >= h for n in nodes):
            await asyncio.sleep(0.02)

    await asyncio.wait_for(all_reached(), timeout)


async def _stop_all(nodes):
    for n in nodes:
        try:
            await n.stop()
        except Exception:
            pass


def test_four_nodes_commit_over_tcp():
    """4 single-process nodes on localhost TCP commit 10+ blocks with txs
    gossiped via the mempool channel."""

    async def main():
        nodes = await _make_net(4)
        try:
            # txs injected on ONE node must reach proposers via gossip
            for i in range(4):
                await nodes[0].mempool.check_tx(b"gk%d=gv%d" % (i, i))
            await _wait_height(nodes, 10)
            for h in range(1, 11):
                hashes = {n.block_store.load_block(h).hash() for n in nodes}
                assert len(hashes) == 1, f"fork at height {h}"
            committed = set()
            for h in range(1, nodes[1].height() + 1):
                for tx in nodes[1].block_store.load_block(h).data.txs:
                    committed.add(bytes(tx))
            want = {b"gk%d=gv%d" % (i, i) for i in range(4)}
            assert want <= committed, f"missing gossiped txs: {want - committed}"
            # the app state converged everywhere
            for n in nodes:
                assert n.app_conns is not None
        finally:
            await _stop_all(nodes)
        return True

    assert run(main())


def test_vote_extensions_over_tcp():
    """Tier-2 version of the in-proc extensions test: 4 real nodes over
    TCP with vote_extensions_enable_height=1 store extended commits whose
    extensions the kvstore app produced and verified across the wire."""

    async def main():
        doc, pvs = _genesis(4, chain_id="ext-net")
        doc.consensus_params.feature.vote_extensions_enable_height = 1
        nodes = []
        for i in range(4):
            node = await Node.create(
                doc, KVStoreApplication(), priv_validator=pvs[i],
                config=_config(), node_key=NodeKey.from_secret(b"ek%d" % i),
                name=f"ext{i}")
            nodes.append(node)
        try:
            for node in nodes:
                await node.start()
            for i, a in enumerate(nodes):
                for b in nodes[i + 1:]:
                    await a.dial_peer(b.listen_addr, persistent=True)
            await _wait_height(nodes, 4)
            for n in nodes:
                ext = n.block_store.load_block_extended_commit(3)
                if ext is None:
                    continue        # only the proposer path must store it
                assert ext.ensure_extensions(True)
                n_with_ext = sum(1 for e in ext.extended_signatures
                                 if e.commit_sig.is_commit()
                                 and e.extension_signature)
                assert n_with_ext >= 3, "extensions missing over TCP"
                break
            else:
                raise AssertionError("no node stored an extended commit")
        finally:
            await _stop_all(nodes)
        return True

    assert run(main())


def test_node_joins_late_and_catches_up_votes():
    """A 4th validator connecting after the others started still joins
    consensus (vote catch-up via gossip; no blocksync needed when it
    connects within the first height)."""

    async def main():
        doc, pvs = _genesis(4)
        nodes = []
        for i in range(4):
            node = await Node.create(
                doc, KVStoreApplication(), priv_validator=pvs[i],
                config=_config(), node_key=NodeKey.from_secret(b"lk%d" % i),
                name=f"late{i}")
            nodes.append(node)
        try:
            for node in nodes[:3]:
                await node.start()
            for i, a in enumerate(nodes[:3]):
                for b in nodes[i + 1:3]:
                    await a.dial_peer(b.listen_addr, persistent=True)
            await _wait_height(nodes[:3], 1)
            # now bring up the 4th and connect it
            await nodes[3].start()
            for a in nodes[:3]:
                await nodes[3].dial_peer(a.listen_addr, persistent=True)
            target = max(n.height() for n in nodes[:3]) + 3
            await _wait_height(nodes, target)
            hashes = {n.block_store.load_block(target).hash()
                      for n in nodes}
            assert len(hashes) == 1
        finally:
            await _stop_all(nodes)
        return True

    assert run(main())
