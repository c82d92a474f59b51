"""Observability: metrics registry + exposition, structured logger,
tx/block indexers and their RPC routes (reference: ``libs/metrics``,
``libs/log``, ``state/txindex``)."""

import asyncio
import io
import json

import pytest

from cometbft_tpu.libs import log as tmlog
from cometbft_tpu.libs.metrics import Counter, Gauge, Histogram, Registry

pytestmark = pytest.mark.timeout(120)


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def test_metrics_counter_gauge_histogram_exposition():
    reg = Registry()
    c = reg.register(Counter("test_total", "a counter"))
    g = reg.register(Gauge("test_gauge", "a gauge"))
    h = reg.register(Histogram("test_seconds", "a histogram",
                               buckets=(0.1, 1.0, 10.0)))
    c.inc()
    c.inc(2, route="device")
    g.set(42, node="n0")
    h.observe(0.05)
    h.observe(0.5)
    h.observe(100.0)
    text = reg.collect()
    assert "# TYPE test_total counter" in text
    assert "test_total 1.0" in text
    assert 'test_total{route="device"} 2.0' in text
    assert 'test_gauge{node="n0"} 42.0' in text
    assert 'test_seconds_bucket{le="0.1"} 1' in text
    assert 'test_seconds_bucket{le="1.0"} 2' in text
    assert 'test_seconds_bucket{le="+Inf"} 3' in text
    assert "test_seconds_count 3" in text
    # registering the same name returns the same instance
    assert reg.register(Counter("test_total")) is c


def test_register_type_mismatch_raises():
    """Re-registering a name as a DIFFERENT metric type must fail loudly
    (regression: it used to hand back the existing Counter to code that
    asked for a Gauge, breaking far from the offending registration)."""
    reg = Registry()
    c = reg.register(Counter("dup_metric", "counter first"))
    with pytest.raises(ValueError, match="dup_metric"):
        reg.register(Gauge("dup_metric", "now a gauge"))
    with pytest.raises(ValueError):
        reg.register(Histogram("dup_metric"))
    # same type still dedups to the original
    assert reg.register(Counter("dup_metric")) is c


def test_help_text_escaped_per_exposition_spec():
    """Backslashes and newlines in HELP text must be escaped — a raw
    multi-line help string corrupts the whole scrape."""
    reg = Registry()
    reg.register(Counter("esc_total",
                         "line one\nline two with a \\ backslash"))
    text = reg.collect()
    assert "# HELP esc_total line one\\nline two with a \\\\ backslash" \
        in text
    # no naked continuation line leaked into the exposition
    assert "\nline two" not in text
    # and every line still parses as comment/series
    for line in text.splitlines():
        assert not line or line.startswith("# ") or " " in line


def test_label_cardinality_cap_evicts_oldest():
    """Per-metric label sets are capped: the oldest labeled child is
    evicted to admit a new one, the eviction is counted, and the
    unlabeled series survives — per-peer labels cannot grow the registry
    unboundedly as peers churn."""
    reg = Registry()
    c = reg.register(Counter("cap_total", "capped", max_label_sets=4))
    c.inc()                                   # unlabeled series
    for i in range(8):
        c.inc(1, peer=f"p{i}")
    assert c.label_sets() == 4                # cap held
    assert c.evicted_total == 5               # 9 inserts - 4 kept
    assert c.value() == 1.0                   # unlabeled never evicted
    assert c.value(peer="p7") == 1.0          # newest kept
    assert c.value(peer="p0") == 0.0          # oldest gone
    text = reg.collect()
    assert "# TYPE metrics_label_evictions_total counter" in text
    assert 'metrics_label_evictions_total{metric="cap_total"} 5' in text
    # an uncapped sibling metric exports no eviction series
    reg2 = Registry()
    reg2.register(Counter("free_total")).inc(route="x")
    assert "metrics_label_evictions_total" not in reg2.collect()


def test_label_cap_applies_to_bound_children_and_other_types():
    """Bound children go through the same guard, and Gauge/Histogram are
    capped like Counter (set/add/observe paths)."""
    reg = Registry()
    c = reg.register(Counter("bcap_total", max_label_sets=3))
    bound = [c.bind(peer=f"b{i}") for i in range(6)]
    for b in bound:
        b.inc()
    assert c.label_sets() == 3
    # an evicted bound child transparently re-inserts (counter resets,
    # which Prometheus rate() treats as a restart)
    bound[0].inc()
    assert c.value(peer="b0") == 1.0
    assert c.label_sets() == 3

    g = reg.register(Gauge("bcap_gauge", max_label_sets=3))
    for i in range(6):
        g.set(i, peer=f"g{i}")
    for i in range(6):
        g.add(1, peer=f"ga{i}")
    assert g.label_sets() == 3

    h = reg.register(Histogram("bcap_seconds", buckets=(1.0,),
                               max_label_sets=3))
    for i in range(6):
        h.observe(0.5, peer=f"h{i}")
    assert len(h._counts) == 3
    assert len(h._sums) == 3 and len(h._totals) == 3   # evicted together
    assert h.count(peer="h5") == 1 and h.count(peer="h0") == 0
    # exposition stays parseable after evictions
    for line in reg.collect().splitlines():
        assert not line or line.startswith("# ") or " " in line


def test_gauge_remove_drops_labeled_child():
    """Gauge.remove lets the switch drop a departed peer's series so it
    does not report its last value forever."""
    reg = Registry()
    g = reg.register(Gauge("rm_gauge"))
    g.set(7, peer="x")
    g.set(9, peer="y")
    g.remove(peer="x")
    g.remove(peer="ghost")                    # absent: no-op
    text = reg.collect()
    assert 'rm_gauge{peer="y"} 9.0' in text
    assert 'peer="x"' not in text


def test_gauge_and_histogram_bind():
    """Gauge.bind()/Histogram.bind() mirror Counter.bind(): pre-resolved
    label sets that skip the per-call sort on hot paths but land in the
    same series."""
    reg = Registry()
    g = reg.register(Gauge("bind_gauge"))
    bg = g.bind(node="n1")
    bg.set(5)
    bg.add(2.5)
    assert g.value(node="n1") == 7.5
    g.set(1, node="n2")                   # unbound path coexists
    assert g.value(node="n2") == 1.0

    h = reg.register(Histogram("bind_seconds", buckets=(0.1, 1.0)))
    bh = h.bind(route="fast")
    bh.observe(0.05)
    bh.observe(0.5)
    h.observe(0.5, route="slow")
    assert h.count(route="fast") == 2
    assert h.sum(route="fast") == 0.55
    assert h.count(route="slow") == 1
    text = reg.collect()
    assert 'bind_seconds_bucket{le="0.1",route="fast"} 1' in text
    assert 'bind_seconds_count{route="fast"} 2' in text


def test_device_abandonment_flips_health_metrics(monkeypatch):
    """A stalled device dispatch must be VISIBLE:
    crypto_device_degraded goes 1 and the abandonment counter ticks when
    _device_call times out; a completing dispatch clears the gauge."""
    import threading

    from cometbft_tpu.crypto import batch as cb

    gauge, abandoned = cb._device_health()
    before = abandoned.value()
    monkeypatch.setattr(cb, "_DEVICE_WAIT_S", 0.05)
    # a fresh pool + inflight slot so a previous test's state can't leak
    monkeypatch.setattr(cb, "_DEVICE_POOL", None)
    monkeypatch.setattr(cb, "_DEVICE_INFLIGHT", None)
    monkeypatch.setattr(cb, "_DEGRADED_LOGGED", False)

    release = threading.Event()
    assert cb._device_call(lambda: release.wait(5)) is None  # abandoned
    assert abandoned.value() == before + 1
    assert gauge.value() == 1
    # while the stuck call occupies the worker, later calls see degraded
    assert cb._device_call(lambda: 42) is None
    assert gauge.value() == 1
    release.set()                      # the wedge resolves
    cb._DEVICE_INFLIGHT.result(timeout=5)
    assert cb._device_call(lambda: 42) == 42
    assert gauge.value() == 0


def test_overload_shed_rejects_broadcast_under_loop_lag():
    """Flood admission control: when the loop watchdog reports lag above
    rpc.overload_shed_lag_s, broadcast_tx_* reject with a retryable
    RPCError instead of queueing more CheckTx work (the one-core testnet
    stall scenario); normal lag admits."""
    from cometbft_tpu.config import Config
    from cometbft_tpu.rpc import core as rpc_core

    class FakeWatchdog:
        last_lag_s = 0.0

    class FakeMempool:
        async def check_tx(self, raw):
            return None

    class FakeNode:
        config = Config()
        loop_watchdog = FakeWatchdog()
        mempool = FakeMempool()

    node = FakeNode()
    node.config.rpc.overload_shed_lag_s = 2.0
    env = rpc_core.Environment(node)

    node.loop_watchdog.last_lag_s = 0.05
    res = run(rpc_core.broadcast_tx_sync(env, tx=b"ok".hex()))
    assert res["code"] == 0

    node.loop_watchdog.last_lag_s = 5.0
    with pytest.raises(rpc_core.RPCError) as ei:
        run(rpc_core.broadcast_tx_sync(env, tx=b"ok".hex()))
    assert "overloaded" in str(ei.value)
    with pytest.raises(rpc_core.RPCError):
        run(rpc_core.broadcast_tx_async(env, tx=b"ok".hex()))

    # 0 disables shedding entirely
    node.config.rpc.overload_shed_lag_s = 0.0
    res = run(rpc_core.broadcast_tx_sync(env, tx=b"ok".hex()))
    assert res["code"] == 0


def test_structured_logger_levels_and_format():
    buf = io.StringIO()
    tmlog.set_sink(buf)
    try:
        lg = tmlog.logger("testmod", node="n1")
        tmlog.set_level("testmod", "warn")
        lg.info("should not appear")
        lg.warn("warned", height=5)
        tmlog.set_level("testmod", "debug")
        lg.debug("now visible")
        out = buf.getvalue()
        assert "should not appear" not in out
        assert "warned" in out and "height=5" in out and "node=n1" in out
        assert "now visible" in out
        # json format
        buf2 = io.StringIO()
        tmlog.set_sink(buf2)
        tmlog.set_format("json")
        lg.error("boom", code=7)
        rec = json.loads(buf2.getvalue())
        assert rec["level"] == "error" and rec["code"] == 7
    finally:
        tmlog.set_format("plain")
        tmlog.set_sink(__import__("sys").stderr)
        tmlog.set_level("testmod", "info")


def test_tx_indexer_index_get_search():
    from cometbft_tpu.abci.types import Event, EventAttribute, ExecTxResult
    from cometbft_tpu.indexer import TxIndexer
    from cometbft_tpu.mempool.mempool import TxKey

    ti = TxIndexer()
    res = ExecTxResult(code=0, data=b"ok", log="",
                       events=[Event("transfer",
                                     [EventAttribute("sender", "alice")])])
    ti.index(5, 0, b"tx-one", res, {"tx.hash": TxKey(b"tx-one").hex()})
    ti.index(6, 0, b"tx-two", ExecTxResult(), {})

    got = ti.get(TxKey(b"tx-one"))
    assert got["height"] == 5 and bytes.fromhex(got["tx"]) == b"tx-one"

    r = ti.search("transfer.sender='alice'")
    assert r["total_count"] == 1
    assert r["txs"][0]["height"] == 5

    r2 = ti.search("tx.height='6'")
    assert r2["total_count"] == 1 and r2["txs"][0]["height"] == 6

    # intersection of clauses
    r3 = ti.search("transfer.sender='alice' AND tx.height='6'")
    assert r3["total_count"] == 0


def test_block_indexer_search():
    from cometbft_tpu.abci.types import Event, EventAttribute
    from cometbft_tpu.indexer import BlockIndexer

    bi = BlockIndexer()
    bi.index(3, [Event("epoch", [EventAttribute("id", "9")])])
    bi.index(4, [])
    assert bi.has(3) and bi.has(4) and not bi.has(5)
    assert bi.search("epoch.id='9'")["heights"] == [3]
    assert bi.search("block.height='4'")["heights"] == [4]


@pytest.mark.slow   # live node over RPC
def test_node_indexes_and_serves_tx_routes():
    """Live node: a committed tx becomes queryable via tx / tx_search /
    block_search, and /metrics exposes consensus gauges."""
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.config import Config
    from cometbft_tpu.config import test_consensus_config as tcc
    from cometbft_tpu.node import Node
    from cometbft_tpu.p2p import NodeKey
    from cometbft_tpu.rpc import HTTPClient
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
    from cometbft_tpu.types.priv_validator import MockPV

    def cfg():
        c = Config(consensus=tcc())
        c.p2p.laddr = "tcp://127.0.0.1:0"
        c.rpc.laddr = "tcp://127.0.0.1:0"
        return c

    async def main():
        pvs = [MockPV.from_secret(b"obs%d" % i) for i in range(4)]
        doc = GenesisDoc(chain_id="obs-net",
                         validators=[GenesisValidator(pv.get_pub_key(), 10)
                                     for pv in pvs])
        nodes = []
        for i, pv in enumerate(pvs):
            n = await Node.create(doc, KVStoreApplication(),
                                  priv_validator=pv, config=cfg(),
                                  node_key=NodeKey.from_secret(b"ok%d" % i),
                                  name=f"obs{i}")
            nodes.append(n)
            await n.start()
        for i, a in enumerate(nodes):
            for b in nodes[i + 1:]:
                await a.dial_peer(b.listen_addr, persistent=True)
        try:
            cli = HTTPClient(*nodes[0].rpc_addr)
            res = await cli.call("broadcast_tx_commit", tx=b"ik=iv".hex())
            h = res["height"]
            txh = res["hash"]
            # the indexer consumes events asynchronously: poll briefly
            for _ in range(100):
                try:
                    got = await cli.call("tx", hash=txh)
                    break
                except Exception:
                    await asyncio.sleep(0.05)
            else:
                raise AssertionError("tx never indexed")
            assert got["height"] == h
            sr = await cli.call("tx_search", query=f"tx.height='{h}'")
            assert sr["total_count"] >= 1
            br = await cli.call("block_search", query=f"block.height='{h}'")
            assert h in br["heights"]

            # prove=True returns a merkle inclusion proof that verifies
            # against the block header's data_hash (rpc/core/tx.go:40)
            from cometbft_tpu.crypto.merkle import Proof
            from cometbft_tpu.types.header import tx_hash as _txh

            proved = await cli.call("tx", hash=txh, prove=True)
            pf = proved["proof"]["proof"]
            proof = Proof(total=pf["total"], index=pf["index"],
                          leaf_hash=bytes.fromhex(pf["leaf_hash"]),
                          aunts=[bytes.fromhex(a) for a in pf["aunts"]])
            blk = await cli.call("block", height=h)
            data_hash = bytes.fromhex(blk["block"]["hdr"]["dh"]["~b"])
            assert bytes.fromhex(proved["proof"]["root_hash"]) == data_hash
            assert proof.verify(data_hash, _txh(b"ik=iv"))

            # order_by governs result ordering; bad values are rejected
            sr2 = await cli.call("tx_search", query="tx.height > 0",
                                 order_by="desc")
            hs = [r["height"] for r in sr2["txs"]]
            assert hs == sorted(hs, reverse=True)
            from cometbft_tpu.rpc import RPCError
            import pytest as _pytest
            with _pytest.raises(RPCError):
                await cli.call("tx_search", query="tx.height > 0",
                               order_by="sideways")

            # commit-verification metrics need a block with a last commit
            while nodes[0].height() < 3:
                await asyncio.sleep(0.05)

            # /metrics exposition over the RPC port
            reader, writer = await asyncio.open_connection(
                *nodes[0].rpc_addr)
            writer.write(b"GET /metrics HTTP/1.1\r\nHost: x\r\n\r\n")
            await writer.drain()
            status = await reader.readline()
            assert b"200" in status
            headers = {}
            while True:
                line = await reader.readline()
                if line in (b"\r\n", b""):
                    break
                k, _, v = line.decode().partition(":")
                headers[k.strip().lower()] = v.strip()
            text = (await reader.readexactly(
                int(headers["content-length"]))).decode()
            writer.close()
            assert "consensus_height{" in text
            assert "crypto_batch_verify_seconds" in text
        finally:
            for n in nodes:
                try:
                    await n.stop()
                except Exception:
                    pass
        return True

    assert run(main())


def test_base_service_lifecycle():
    """libs.service.BaseService: double-start refusal, failed-start reset,
    idempotent stop, waitable termination — exercised through its two
    adopters (Pruner, IndexerService)."""
    from cometbft_tpu.libs.service import BaseService, ServiceError

    class Boom(BaseService):
        async def on_start(self):
            raise RuntimeError("nope")

    class Ok(BaseService):
        def __init__(self):
            super().__init__("ok")
            self.events = []

        async def on_start(self):
            self.events.append("start")

        async def on_stop(self):
            self.events.append("stop")

    async def main():
        s = Ok()
        await s.start()
        assert s.is_running
        with pytest.raises(ServiceError):
            await s.start()
        waiter = asyncio.create_task(s.wait())
        await s.stop()
        await s.stop()                      # idempotent
        await asyncio.wait_for(waiter, 1)
        assert s.events == ["start", "stop"]

        b = Boom()
        with pytest.raises(RuntimeError):
            await b.start()
        assert not b.is_running
        await asyncio.wait_for(b.wait(), 1)   # failed start releases waiters

        # the real adopters run on it
        from cometbft_tpu.sm.pruner import Pruner
        from cometbft_tpu.storage import BlockStore, MemDB, StateStore

        p = Pruner(StateStore(MemDB()), BlockStore(MemDB()))
        await p.start()
        assert p.is_running
        await p.stop()
        assert not p.is_running
        return True

    assert run(main())


def test_prometheus_standalone_listener():
    """instrumentation.prometheus serves the dedicated scrape port
    (reference node/node.go Prometheus server)."""
    import asyncio

    from cometbft_tpu.node.node import _serve_prometheus
    from cometbft_tpu.libs import metrics

    async def main():
        server = await _serve_prometheus("tcp://127.0.0.1:0")
        port = server.sockets[0].getsockname()[1]
        metrics.counter("obs_test_total", "test counter").inc(3)
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(b"GET /metrics HTTP/1.1\r\nHost: x\r\n\r\n")
        await writer.drain()
        # read the whole body (the shared registry grows with the suite;
        # a single read() caps at 64KB and truncates late metrics)
        status = await asyncio.wait_for(reader.readline(), 5)
        assert b"200 OK" in status
        headers = {}
        while True:
            line = await asyncio.wait_for(reader.readline(), 5)
            if line in (b"\r\n", b""):
                break
            k, _, v = line.decode().partition(":")
            headers[k.strip().lower()] = v.strip()
        raw = await asyncio.wait_for(
            reader.readexactly(int(headers["content-length"])), 10)
        assert b"obs_test_total" in raw
        writer.close()
        server.close()
        return True

    assert run(main())


def test_loop_watchdog_detects_stall():
    """The loop watchdog (libs/loopwatch) reports synchronous work that
    froze the event loop — the asyncio analogue of deadlock detection."""
    import asyncio
    import time as _time

    from cometbft_tpu.libs.loopwatch import LoopWatchdog

    async def main():
        wd = LoopWatchdog(asyncio.get_running_loop(),
                          interval_s=0.05, stall_threshold_s=0.2,
                          name="wdtest")
        wd.start()
        try:
            await asyncio.sleep(0.2)     # healthy: no stalls
            healthy = wd.stalls
            _time.sleep(0.8)             # synchronous block ON the loop
            await asyncio.sleep(0.3)     # let the beat land
            return healthy, wd.stalls, wd.worst_stall_s
        finally:
            wd.stop()

    healthy, stalls, worst = run(main())
    assert healthy == 0
    assert stalls >= 1
    assert worst >= 0.5
