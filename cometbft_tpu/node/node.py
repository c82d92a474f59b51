"""Node assembly (reference: ``node/node.go:275,303-576`` NewNode +
OnStart): wires DBs -> state/genesis -> ABCI connections + handshake ->
mempool -> consensus (+WAL) -> reactors -> transport/switch.

The reference's two-phase construction (create everything, then OnStart
starts services in dependency order) is kept; RPC attaches on top via
``rpc.server`` when configured.
"""

from __future__ import annotations

import asyncio
import os

from ..abci.application import Application
from ..blocksync.reactor import BlocksyncReactor
from ..config import Config, test_consensus_config
from ..evidence import EvidencePool, EvidenceReactor
from ..consensus.reactor import ConsensusReactor
from ..consensus.replay import Handshaker
from ..consensus.state import ConsensusState
from ..consensus.wal import WAL
from ..libs.pubsub import EventBus
from ..mempool.clist_mempool import CListMempool
from ..mempool.reactor import MempoolReactor
from ..p2p import AddrBook, NodeInfo, NodeKey, PexReactor, Switch, Transport
from ..proxy.multi_app_conn import (AppConns, local_client_creator,
                                    socket_client_creator)
from ..sm.execution import BlockExecutor
from ..storage import BlockStore, LogDB, MemDB, State, StateStore
from ..types.genesis import GenesisDoc
from ..types.priv_validator import PrivValidator


def _parse_laddr(laddr: str) -> tuple[str, int]:
    addr = laddr.removeprefix("tcp://")
    host, _, port = addr.rpartition(":")
    return host or "127.0.0.1", int(port)


async def _serve_prometheus(laddr: str):
    """Standalone Prometheus exposition listener (reference:
    ``node/node.go`` Prometheus server on instrumentation.prometheus);
    the JSON-RPC server also serves ``GET /metrics``, this is the
    dedicated scrape port."""
    import asyncio as _aio

    from ..libs import metrics as _metrics

    host, port = _parse_laddr(laddr)

    async def handle(reader, writer):
        try:
            # bounded reads: a silent client must not pin the handler
            await _aio.wait_for(reader.readline(), 10)   # request line
            while (await _aio.wait_for(reader.readline(), 10)).strip():
                pass                                     # drain headers
            body = _metrics.DEFAULT.collect().encode()
            writer.write(b"HTTP/1.1 200 OK\r\nContent-Type: text/plain; "
                         b"version=0.0.4\r\nContent-Length: "
                         + str(len(body)).encode() + b"\r\n\r\n" + body)
            await writer.drain()
        except (ConnectionError, _aio.IncompleteReadError,
                _aio.TimeoutError):
            pass
        finally:
            writer.close()

    return await _aio.start_server(handle, host, port)


class Node:
    def __init__(self):
        # populated by create(); kept flat for introspection/RPC
        self.config: Config | None = None
        self.genesis: GenesisDoc | None = None
        self.block_store: BlockStore | None = None
        self.state_store: StateStore | None = None
        self.app_conns: AppConns | None = None
        self.event_bus: EventBus | None = None
        self.mempool: CListMempool | None = None
        self.block_exec: BlockExecutor | None = None
        self.consensus: ConsensusState | None = None
        self.consensus_reactor: ConsensusReactor | None = None
        self.mempool_reactor: MempoolReactor | None = None
        self.blocksync_reactor: BlocksyncReactor | None = None
        self.evidence_pool: EvidencePool | None = None
        self.evidence_reactor: EvidenceReactor | None = None
        self.fast_sync = False
        self.node_key: NodeKey | None = None
        self.transport: Transport | None = None
        self.switch: Switch | None = None
        self.listen_addr: str | None = None
        self.rpc_server = None
        self.rpc_addr: tuple[str, int] | None = None
        self.grpc_server = None
        self.prometheus_server = None
        self.loop_watchdog = None
        self.liveness_watchdog = None
        self.home: str | None = None
        self.tx_indexer = None
        self.block_indexer = None
        self.indexer_service = None
        self.statesync_reactor = None
        self.addr_book = None
        self.pex_reactor = None
        self.pruner = None
        self.syncer = None
        self.statesync_done = None
        self.statesync_error = None
        self.name = "node"
        self.doctor_report = None
        self.compile_bundle_info = None
        self.verify_device_info = None
        self.light_serve = None
        self._started = False
        self._data_lock = None
        self._vote_sched = None

    # ------------------------------------------------------------- create

    @classmethod
    async def create(cls, genesis_doc: GenesisDoc, app: Application,
                     priv_validator: PrivValidator | None = None,
                     config: Config | None = None,
                     node_key: NodeKey | None = None,
                     home: str | None = None,
                     fast_sync: bool = False,
                     state_sync_provider=None,
                     name: str = "node") -> "Node":
        self = cls()
        self.name = name
        self.home = home
        self.fast_sync = fast_sync or state_sync_provider is not None
        cfg = config or Config(consensus=test_consensus_config())
        self.config = cfg
        self.genesis = genesis_doc

        # arm the fault-injection plane BEFORE the stores open: sites
        # that fire at open time (db.replay.corrupt feeding the salvage
        # + doctor pipeline) must see a subprocess node's CMT_CHAOS env
        # — start() would be too late (same process-wide/sticky
        # discipline as tracing)
        from ..libs import failures as _failures

        _failures.configure_from_config(cfg.chaos)

        from ..storage import open_db

        def make_db(filename: str):
            if home is None:
                return MemDB()
            return open_db(cfg.storage.db_backend,
                           os.path.join(home, "data", filename))

        if home is not None:
            from ..storage.db import DataDirLock

            os.makedirs(os.path.join(home, "data"), exist_ok=True)
            # refuse to double-open a home (and make offline tooling
            # refuse while this node runs)
            self._data_lock = DataDirLock(os.path.join(home, "data"))
            wal_path = os.path.join(home, "data", "cs.wal")
        else:
            wal_path = None
        bs_db = make_db("blockstore.db")
        ss_db = make_db("state.db")
        self.block_store = BlockStore(bs_db)
        self.state_store = StateStore(ss_db)

        # storage integrity doctor: cross-store boot consistency (+ the
        # deep hash-chain scan when a store was salvaged) BEFORE the WAL
        # opens — a repair may quarantine WAL segments, and the check
        # must see the on-disk lineage, not a fresh append handle.
        # Raises DoctorError on the dangerous cases (privval sign state
        # ahead of a clean store = double-sign tripwire).
        if cfg.storage.doctor_enable:
            from .doctor import DoctorError, StorageDoctor

            try:
                self.doctor_report = StorageDoctor(
                    self.block_store, self.state_store, wal_path=wal_path,
                    priv_validator=priv_validator,
                    deep_scan_window=cfg.storage.doctor_deep_scan_window,
                    name=name).boot_check(repair=True)
            except DoctorError:
                # refusal: close the store handles and release the home
                # so inspect mode / the doctor CLI (and a fixed retry)
                # can open it without racing two live append handles
                for db_ in (bs_db, ss_db):
                    try:
                        db_.close()
                    except Exception:
                        pass
                if self._data_lock is not None:
                    self._data_lock.release()
                    self._data_lock = None
                raise
        wal = WAL(wal_path) if wal_path is not None else None

        state = self.state_store.load() or State.from_genesis(genesis_doc)

        if app is not None:
            creator = local_client_creator(app)
        elif cfg.base.abci == "socket":
            # out-of-process app over the ABCI socket protocol
            # (proxy/client.go remote creator)
            shost, sport = _parse_laddr(cfg.base.proxy_app)
            creator = socket_client_creator(shost, sport)
        elif cfg.base.abci == "grpc":
            from ..proxy.multi_app_conn import grpc_client_creator

            ghost, gport = _parse_laddr(cfg.base.proxy_app)
            creator = grpc_client_creator(ghost, gport)
        else:
            raise ValueError("no application: pass app or configure "
                             "base.abci='socket'|'grpc' with "
                             "base.proxy_app addr")
        self.app_conns = AppConns(creator, node=self.name)
        await self.app_conns.start()
        self.event_bus = EventBus()
        self.mempool = CListMempool(
            self.app_conns.mempool, max_txs=cfg.mempool.size,
            max_tx_bytes=cfg.mempool.max_tx_bytes,
            max_txs_bytes=cfg.mempool.max_txs_bytes,
            cache_size=cfg.mempool.cache_size,
            keep_invalid_txs_in_cache=cfg.mempool.keep_invalid_txs_in_cache,
            shards=cfg.mempool.shards,
            coalesce_ms=cfg.mempool.coalesce_ms,
            coalesce_max=cfg.mempool.coalesce_max,
            recheck=cfg.mempool.recheck,
            metrics_node=name)
        ev_db = make_db("evidence.db")
        self.evidence_pool = EvidencePool(
            ev_db, state_store=self.state_store,
            block_store=self.block_store,
            backend=cfg.base.signature_backend)
        self.evidence_pool.state = state
        from ..sm.pruner import Pruner

        self.pruner = Pruner(self.state_store, self.block_store, name=name)
        self.block_exec = BlockExecutor(
            self.state_store, self.block_store, self.app_conns.consensus,
            self.mempool, evidence_pool=self.evidence_pool,
            event_bus=self.event_bus,
            backend=cfg.base.signature_backend, pruner=self.pruner)

        self._state_syncing = (state_sync_provider is not None
                               and self.block_store.height() == 0)
        if not self._state_syncing:
            # statesync replaces the handshake: the app gets its state
            # from the snapshot, not InitChain/replay (node/node.go note
            # "the Handshaker is not used when state syncing")
            state = await Handshaker(
                self.state_store, self.block_store, genesis_doc).handshake(
                state, self.app_conns, self.block_exec)

        self.consensus = ConsensusState(
            cfg.consensus, state, self.block_exec, self.block_store,
            wal=wal, priv_validator=priv_validator,
            event_bus=self.event_bus, name=name)
        self.consensus.on_conflicting_vote = \
            self.evidence_pool.report_conflicting_votes

        gossip_sleep = cfg.consensus.peer_gossip_sleep_duration / 1e9
        self.consensus_reactor = ConsensusReactor(
            self.consensus, gossip_sleep=gossip_sleep)
        self.mempool_reactor = MempoolReactor(
            self.mempool, gossip_sleep=gossip_sleep,
            gossip_mode=cfg.mempool.gossip_mode,
            fetch_timeout_s=cfg.mempool.fetch_timeout_s,
            batch_bytes=cfg.mempool.gossip_batch_bytes)

        self.blocksync_reactor = BlocksyncReactor(
            self.block_exec, self.block_store, state,
            fast_sync=self.fast_sync,
            switch_to_consensus=self._switch_to_consensus,
            backend=cfg.base.signature_backend,
            verify_window=cfg.blocksync.verify_window,
            name=f"{name}.bs")
        if self.fast_sync:
            self.consensus_reactor.wait_sync = True

        from ..statesync import StatesyncReactor, Syncer

        self.statesync_reactor = StatesyncReactor(
            self.app_conns, name=f"{name}.ss",
            chunk_cache_bytes=cfg.statesync.chunk_cache_bytes,
            serve_concurrency=cfg.statesync.serve_concurrency,
            serve_queue=cfg.statesync.serve_queue)
        if self._state_syncing:
            self.syncer = Syncer(
                self.app_conns, state_sync_provider,
                reactor=self.statesync_reactor, name=name,
                chunk_timeout=cfg.statesync.chunk_timeout_s,
                max_inflight_per_peer=cfg.statesync.max_inflight_per_peer,
                discovery_time=cfg.statesync.discovery_time_s,
                discovery_rounds=cfg.statesync.discovery_rounds,
                chunk_retries=cfg.statesync.chunk_retries,
                spool_retain_bytes=cfg.statesync.spool_retain_bytes)
            self.statesync_reactor.syncer = self.syncer
            self.blocksync_reactor.hold = True

        self.node_key = node_key or NodeKey.generate()
        fuzz_cfg = None
        if cfg.p2p.test_fuzz:
            from ..p2p.fuzz import FuzzConnConfig

            fuzz_cfg = FuzzConnConfig(
                mode=cfg.p2p.fuzz_mode,
                max_delay_s=cfg.p2p.fuzz_max_delay_s,
                prob_drop_rw=cfg.p2p.fuzz_prob_drop_rw,
                prob_drop_conn=cfg.p2p.fuzz_prob_drop_conn,
                prob_sleep=cfg.p2p.fuzz_prob_sleep,
                start_after_s=cfg.p2p.fuzz_start_after_s,
                seed=cfg.p2p.fuzz_seed)
        self.transport = Transport(self.node_key, self._node_info,
                                   fuzz_config=fuzz_cfg)
        # addrbook before the switch: the peer-quality scorer records
        # its timed bans there (persisted across restarts); without pex
        # the scorer keeps bans in-memory only
        if cfg.p2p.pex:
            book_path = None
            if home is not None:
                book_path = os.path.join(home, cfg.p2p.addr_book_path) \
                    if not os.path.isabs(cfg.p2p.addr_book_path) \
                    else cfg.p2p.addr_book_path
            self.addr_book = AddrBook(book_path)
        from ..p2p.quality import PeerScorer

        scorer = PeerScorer(
            addr_book=self.addr_book,
            enabled=cfg.p2p.quality_enable,
            disconnect_score=cfg.p2p.quality_disconnect_score,
            ban_score=cfg.p2p.quality_ban_score,
            half_life_s=cfg.p2p.quality_half_life_s,
            ban_ttl_s=cfg.p2p.quality_ban_ttl_s,
            ban_ttl_max_s=cfg.p2p.quality_ban_ttl_max_s)
        self.switch = Switch(
            self.transport,
            emulated_latency=cfg.p2p.emulated_latency_ms / 1e3,
            telemetry_interval=cfg.p2p.telemetry_flush_interval_s,
            scorer=scorer, chaos_scope=name)
        if cfg.tx_index.indexer == "kv":
            from ..indexer import BlockIndexer, IndexerService, TxIndexer

            self.tx_indexer = TxIndexer(make_db("tx_index.db"))
            self.block_indexer = BlockIndexer(make_db("block_index.db"))
            self.indexer_service = IndexerService(
                self.event_bus, self.tx_indexer, self.block_indexer,
                name=f"{name}.idx")
        elif cfg.tx_index.indexer == "psql":
            # external SQL sink (state/indexer/sink/psql): same pump,
            # rows instead of kv postings; write-only from the node
            from ..indexer import IndexerService
            from ..indexer.psql import PsqlEventSink

            sink = PsqlEventSink(dsn=cfg.tx_index.psql_conn,
                                 chain_id=genesis_doc.chain_id)
            self.tx_indexer = sink
            self.block_indexer = sink.block_indexer()
            self.indexer_service = IndexerService(
                self.event_bus, sink, self.block_indexer,
                name=f"{name}.idx")

        if cfg.lightserve.enable:
            # light-client serving tier (light/serve.py): passive — no
            # background tasks, read by the light_* RPC routes in worker
            # threads.  Constructed here (not at RPC start) so in-proc
            # tooling can drive it without a listener.
            from ..light.serve import LightServeTier

            ls_cfg = cfg.lightserve
            self.light_serve = LightServeTier(
                self.block_store, self.state_store, genesis_doc.chain_id,
                backend=cfg.base.signature_backend,
                header_cache_size=ls_cfg.header_cache_size,
                header_cache_bytes=ls_cfg.header_cache_bytes,
                proof_cache_blocks=ls_cfg.proof_cache_blocks,
                verify_cache_size=ls_cfg.verify_cache_size,
                trust_period_ns=ls_cfg.trust_period_ns,
                max_batch=ls_cfg.max_batch,
                max_proofs=ls_cfg.max_proofs,
                name=name)

        self.evidence_reactor = EvidenceReactor(self.evidence_pool)
        self.switch.add_reactor("consensus", self.consensus_reactor)
        self.switch.add_reactor("mempool", self.mempool_reactor)
        self.switch.add_reactor("blocksync", self.blocksync_reactor)
        self.switch.add_reactor("evidence", self.evidence_reactor)
        self.switch.add_reactor("statesync", self.statesync_reactor)
        if cfg.p2p.pex:
            self.pex_reactor = PexReactor(
                self.addr_book, self.node_key.id,
                max_outbound=cfg.p2p.max_num_outbound_peers,
                request_interval=cfg.p2p.pex_interval_seconds,
                seed_mode=cfg.p2p.seed_mode)
            self.switch.add_reactor("pex", self.pex_reactor)
        return self

    async def _run_statesync(self) -> None:
        """node.go OnStart startStateSync: snapshot restore -> bootstrap
        stores -> hand off to blocksync."""
        from ..libs import log as tmlog

        lg = tmlog.logger("statesync", node=self.name)
        try:
            state, commit = await self.syncer.sync()
            self.state_store.bootstrap(state)
            self.block_store.bootstrap_statesync(state.last_block_height,
                                                 commit)
            self.evidence_pool.state = state
            self.blocksync_reactor.state = state
        except asyncio.CancelledError:
            raise
        except Exception as e:
            # fall back to syncing from genesis (InitChain was skipped in
            # anticipation of the snapshot: run the handshake now).  If
            # the app already restored part of a snapshot the handshake
            # itself fails — that is unrecoverable without a reset, but
            # it must be LOUD, not a silently-dead task.
            lg.error("statesync failed; falling back to blocksync",
                     err=repr(e))
            try:
                state = State.from_genesis(self.genesis)
                state = await Handshaker(
                    self.state_store, self.block_store,
                    self.genesis).handshake(
                    state, self.app_conns, self.block_exec)
                self.blocksync_reactor.state = state
            except Exception as e2:
                self.statesync_error = e2
                lg.error("statesync fallback failed; node needs "
                         "unsafe-reset-all", err=repr(e2))
                return
        self.blocksync_reactor.hold = False
        await self.blocksync_reactor.start_sync()

    async def _switch_to_consensus(self, state) -> None:
        """Blocksync caught up: adopt the synced state and start consensus
        (reference consensus Reactor.SwitchToConsensus)."""
        self.consensus._update_to_state(state)
        await self.consensus.start()
        self.consensus_reactor.switch_to_consensus()

    def _node_info(self) -> NodeInfo:
        return NodeInfo(
            node_id=self.node_key.id,
            listen_addr=self.listen_addr or "",
            network=self.genesis.chain_id,
            channels=self.switch.channel_ids if self.switch else b"",
            moniker=self.name)

    # ---------------------------------------------------------- lifecycle

    async def start(self) -> None:
        """node.go:579 OnStart: listen, start reactors, start consensus."""
        if self.config.instrumentation.tracing:
            # flip the process-wide flight recorder on BEFORE any
            # subsystem starts so the first height is fully traced;
            # never flipped off at stop (in-proc ensembles share it, and
            # the ring of a stopped node is still dumpable post-mortem)
            from ..libs import tracing as _tracing

            _tracing.configure(
                enabled=True,
                ring_size=self.config.instrumentation.tracing_ring_size)
        # (the fault-injection plane was armed in create(), before the
        # stores opened — open-time sites must see the schedule)
        from ..crypto import batch as cryptobatch

        # resolve the configured backend against the devices of THIS
        # process before anything listens: "tpu" without a TPU refuses
        # to start, "auto" without a chip says (once, and in /status)
        # that it verifies on the host
        self.verify_device_info = cryptobatch.device_info(
            self.config.base.signature_backend)
        host, port = _parse_laddr(self.config.p2p.laddr) \
            if self.config.p2p.laddr else ("127.0.0.1", 0)
        self.listen_addr = await self.transport.listen(host, port)
        await self.switch.start()
        if self.indexer_service is not None:
            await self.indexer_service.start()
        if self.pruner is not None:
            await self.pruner.start()
        if self.config.rpc.laddr:
            from ..rpc import RPCServer

            rhost, rport = _parse_laddr(self.config.rpc.laddr)
            self.rpc_server = RPCServer(self)
            self.rpc_addr = await self.rpc_server.listen(rhost, rport)
        if self.config.rpc.grpc_laddr:
            from ..rpc.grpc import GRPCServer

            ghost, gport = _parse_laddr(self.config.rpc.grpc_laddr)
            self.grpc_server = GRPCServer(self, ghost, gport)
            await self.grpc_server.start()
        if self.config.instrumentation.prometheus:
            self.prometheus_server = await _serve_prometheus(
                self.config.instrumentation.prometheus_listen_addr)
        if self.config.instrumentation.loop_stall_threshold_s > 0:
            from ..libs.loopwatch import LoopWatchdog

            self.loop_watchdog = LoopWatchdog(
                asyncio.get_running_loop(),
                stall_threshold_s=(
                    self.config.instrumentation.loop_stall_threshold_s),
                name=self.name)
            self.loop_watchdog.start()
        elif getattr(self.config.rpc, "overload_shed_lag_s", 0) > 0:
            # shedding reads the watchdog's lag — with the watchdog off
            # the knob is dead, which an operator should hear about once
            from ..libs import log as _tmlog

            _tmlog.logger("node", node=self.name).warn(
                "rpc.overload_shed_lag_s is set but the loop watchdog is "
                "disabled (instrumentation.loop_stall_threshold_s = 0): "
                "overload shedding is inactive")
        from ..crypto import plan as deviceplan

        # the declarative device plan drives the batched verifier AND
        # the coalescing scheduler (and is what the AOT bundle below is
        # keyed by) — config lands here, not in per-module hooks
        deviceplan.configure(
            min_device_lanes=self.config.base.min_device_lanes)
        if self.config.base.device_wait_s > 0:
            cryptobatch.set_device_wait(self.config.base.device_wait_s)
        from ..crypto import merkle as cryptomerkle

        cryptomerkle.set_merkle_kernel_min(
            self.config.base.merkle_kernel_min_leaves)
        if self.config.base.vote_sched_enable:
            # process-wide coalescing vote-verification scheduler:
            # in-proc ensembles share one (refcounted) instance — the
            # verified-signature cache holds universal verdicts and
            # cross-node coalescing only improves batch occupancy
            from ..crypto import scheduler as vsched

            self._vote_sched = await vsched.acquire_scheduler(
                backend=self.config.base.signature_backend,
                max_wait_ms=self.config.base.vote_sched_max_wait_ms,
                max_lanes=self.config.base.vote_sched_max_lanes,
                cache_size=self.config.base.vote_sched_cache_size,
                verify_timeout_s=(
                    self.config.base.vote_sched_verify_timeout_s))

        def _warm_native():
            # build/load the C++ verifiers off the event loop so a fresh
            # checkout's first commit verification doesn't eat a
            # multi-second g++ compile on the consensus hot path
            from ..crypto import _native_ed25519 as nat
            from ..crypto import secp256k1 as secp

            nat.available()
            secp._native_lib()

        asyncio.get_running_loop().run_in_executor(None, _warm_native)
        backend = self.config.base.signature_backend
        if self.verify_device_info["route"] != "device":
            # host-verifying node: nothing to pre-compile
            if backend == "auto":
                self.compile_bundle_info = {"status": "skipped_no_device"}
        elif self.config.base.device_warmup:
            # pre-compile hot bucket shapes off the event loop so the
            # first commit verification doesn't stall consensus
            bundle_on = self.config.base.compile_bundle_enable
            bundle_dir = self.config.base.compile_bundle_dir or None

            def _warm():
                from ..crypto import aotbundle

                # default hot shapes, plus the buckets the CURRENT
                # valset actually dispatches — a large network's first
                # commit must not pay a cold XLA compile.  The same
                # shapes become the plan's warm set so the bundle
                # covers the cached-gather route (the real commit hot
                # path), keyed to this valset's TABLE bucket.
                lanes = {256, 1024}
                vsizes = ()
                st = self.state_store.load()
                n_vals = len(st.validators.validators) \
                    if st is not None else 0
                if n_vals:
                    lanes.update(cryptobatch.buckets_for_batch(n_vals))
                    # the dense Light path dispatches the ~2/3-power
                    # scope, not the full set
                    lanes.update(cryptobatch.buckets_for_batch(
                        (2 * n_vals) // 3 + 1))
                    if n_vals > max(lanes):
                        vsizes = (n_vals,)
                    table = deviceplan.bucket(
                        n_vals, deviceplan.active().table_buckets)
                    deviceplan.configure(
                        warm_lanes=tuple(sorted(lanes)),
                        warm_tables=(table,))
                if bundle_on:
                    # warm boot: load the versioned AOT bundle FIRST so
                    # the warmup below (and the first real commit) finds
                    # pre-compiled executables instead of paying
                    # trace+lower+compile per shape
                    try:
                        self.compile_bundle_info = aotbundle.load(
                            path=aotbundle.default_path(bundle_dir))
                    except Exception as e:
                        self.compile_bundle_info = {"status": "error",
                                                    "error": repr(e)}
                else:
                    self.compile_bundle_info = {"status": "disabled"}
                cryptobatch.warmup_device(
                    lane_buckets=tuple(sorted(lanes)),
                    valset_sizes=vsizes)
                if bundle_on and \
                        self.compile_bundle_info.get("status") != "loaded":
                    # cold machine: build + save the bundle AFTER warmup
                    # (consensus is already served by the jit caches) so
                    # the NEXT boot — or a verify node spun up for a
                    # traffic spike — starts warm
                    try:
                        self.compile_bundle_info = aotbundle.build(
                            path=aotbundle.default_path(bundle_dir))
                    except Exception as e:
                        self.compile_bundle_info = {"status": "error",
                                                    "error": repr(e)}

            warm = asyncio.get_running_loop().run_in_executor(None, _warm)
            if backend == "tpu":
                # "tpu" means the chip verifies: a hot shape its
                # compiler refuses is a start-up failure, and waiting
                # the compiles out here keeps the first commits from
                # being abandoned to the host mid-compile
                await warm
            else:
                warm.add_done_callback(self._warmup_done)
        if self.syncer is not None:
            self.statesync_done = asyncio.create_task(
                self._run_statesync())
        if not self.fast_sync:
            # fast-sync defers consensus start to the blocksync handoff
            await self.consensus.start()
        inst = self.config.instrumentation
        if inst.watchdog_stall_threshold_s > 0:
            incident_dir = self.incident_dir()
            if incident_dir is not None:
                from .watchdog import LivenessWatchdog

                self.liveness_watchdog = LivenessWatchdog(
                    self, incident_dir,
                    stall_threshold_s=inst.watchdog_stall_threshold_s,
                    check_interval_s=inst.watchdog_check_interval_s,
                    min_interval_s=inst.watchdog_min_interval_s,
                    max_bundles=inst.watchdog_max_bundles,
                    wal_tail_records=inst.watchdog_wal_tail)
                await self.liveness_watchdog.start()
        self._started = True

    def _warmup_done(self, fut) -> None:
        """Background warm-up ("jax"/"auto" with a device) ended: a
        shape that failed to compile is logged, not dropped with the
        future — the shape compiles on demand or degrades that dispatch
        to the host on the device-health gauge."""
        if not fut.cancelled() and fut.exception() is not None:
            from ..libs import log as _tmlog

            _tmlog.logger("node", node=self.name).error(
                "device warm-up failed", err=repr(fut.exception()))

    async def stop(self) -> None:
        if self.statesync_done is not None:
            self.statesync_done.cancel()
        if self.liveness_watchdog is not None:
            await self.liveness_watchdog.stop()
            self.liveness_watchdog = None
        if self.rpc_server is not None:
            await self.rpc_server.close()
        if self.grpc_server is not None:
            await self.grpc_server.stop()
        if self.prometheus_server is not None:
            self.prometheus_server.close()
            await self.prometheus_server.wait_closed()
        if self.loop_watchdog is not None:
            self.loop_watchdog.stop()
        if self._data_lock is not None:
            self._data_lock.release()
            self._data_lock = None
        if self.indexer_service is not None:
            await self.indexer_service.stop()
        if self.pruner is not None:
            await self.pruner.stop()
        if self.blocksync_reactor is not None:
            await self.blocksync_reactor.stop()
        if self.consensus is not None:
            await self.consensus.stop()
        if self._vote_sched is not None:
            from ..crypto import scheduler as vsched

            self._vote_sched = None
            await vsched.release_scheduler()
        if self.switch is not None:
            await self.switch.stop()
        if self.app_conns is not None:
            await self.app_conns.stop()
        self._started = False

    def incident_dir(self) -> str | None:
        """Where watchdog incident bundles live (see
        ``watchdog.resolve_incident_dir``)."""
        from .watchdog import resolve_incident_dir

        return resolve_incident_dir(self.config, self.home)

    async def dial_peer(self, addr: str, persistent: bool = True):
        return await self.switch.dial_peer(addr, persistent=persistent)

    # ------------------------------------------------------------- status

    def height(self) -> int:
        return self.block_store.height()
