"""Shared test/bench factories (role of the reference's ``internal/test``
helpers + ``internal/consensus/common_test.go``, SURVEY.md §4):
deterministic signature batches for the device kernel, and the tier-1
in-process multi-validator consensus network (N ConsensusStates wired
queue-to-queue with no real networking)."""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


def dense_signature_batch(bsz: int, msg_len: int = 120, seed: int = 7,
                          n_keys: int | None = None):
    """Build a valid-signature batch shaped like commit verification.

    Returns ``(kernel_args, host_items)``: kernel_args =
    (pubs, rs, ss, blocks, active) ready for ``ops.ed25519.verify_padded``;
    host_items = [(pub_bytes, msg, sig)] for host-side baselines.
    """
    from .crypto import _ed25519_py as ref
    from .ops import sha512

    rng = np.random.default_rng(seed)
    keys = [rng.bytes(32) for _ in range(min(n_keys or bsz, 256))]
    keys = [(s, ref.public_key_from_seed(s)) for s in keys]
    pubs = np.zeros((bsz, 32), np.int32)
    rs = np.zeros((bsz, 32), np.int32)
    ss = np.zeros((bsz, 32), np.int32)
    hin = np.zeros((bsz, 64 + msg_len), np.uint8)
    lens = np.full((bsz,), 64 + msg_len, np.int64)
    host_items = []
    for i in range(bsz):
        sd, pk = keys[i % len(keys)]
        msg = rng.bytes(msg_len)
        sig = ref.sign(sd, msg)
        pubs[i] = np.frombuffer(pk, np.uint8)
        rs[i] = np.frombuffer(sig[:32], np.uint8)
        ss[i] = np.frombuffer(sig[32:], np.uint8)
        hin[i] = np.frombuffer(sig[:32] + pk + msg, np.uint8)
        host_items.append((pk, msg, sig))
    blocks, active = sha512.host_pad(hin, lens, 2)
    return (pubs, rs, ss, blocks, active), host_items


def zip215_edge_cases(seed: int = 7, msg_len: int = 120) -> list[tuple]:
    """ZIP-215 edge cases as ``(label, pub, msg, sig)`` tuples on
    special PUBKEYS (so a valset can carry them as rows):
    a mixed-order key (A' + an order-8 torsion point, signature crafted
    against the mixed encoding), the non-canonical identity (y = 1 + p)
    with [S]B == R, the same key with a small-order R, and with a
    non-canonical (y >= p) R.  Expected verdicts are the oracle's
    (``crypto/_ed25519_py.verify_zip215``) — all four are accepts."""
    import hashlib

    from .crypto import _ed25519_py as ref

    rng = np.random.default_rng(seed)
    while True:                 # a point of exact order 8
        pt = ref.pt_decompress_zip215(rng.bytes(32))
        if pt is None:
            continue
        t8 = ref.pt_mul(ref.L, pt)
        if not ref.pt_equal(ref.pt_mul(4, t8), ref.IDENTITY):
            break
    h0 = hashlib.sha512(rng.bytes(32)).digest()
    a_sc, prefix = ref._clamp(h0[:32]), h0[32:]
    mixed = ref.pt_compress(ref.pt_add(ref.pt_mul(a_sc, ref.BASE), t8))
    m = rng.bytes(msg_len)
    r_sc = ref.sc_reduce64(hashlib.sha512(prefix + m).digest())
    r_enc = ref.pt_compress(ref.pt_mul(r_sc, ref.BASE))
    k_sc = ref.sc_reduce64(hashlib.sha512(r_enc + mixed + m).digest())
    cases = [("mixed_order_key", mixed, m,
              r_enc + ((r_sc + k_sc * a_sc) % ref.L).to_bytes(32, "little"))]
    ident_nc = (1 + ref.P).to_bytes(32, "little")
    r_sc = int.from_bytes(rng.bytes(32), "little") % ref.L
    zero = (0).to_bytes(32, "little")
    cases += [
        ("noncanonical_identity_key", ident_nc, rng.bytes(msg_len),
         ref.pt_compress(ref.pt_mul(r_sc, ref.BASE))
         + r_sc.to_bytes(32, "little")),
        ("small_order_r", ident_nc, rng.bytes(msg_len),
         ref.pt_compress(t8) + zero),
        ("noncanonical_r", ident_nc, rng.bytes(msg_len), ident_nc + zero),
    ]
    for label, pub, msg, sig in cases:
        if not ref.verify_zip215(pub, msg, sig):
            raise ValueError(f"oracle refuses edge case {label}")
    return cases


def bls_priv_from_secret(secret: bytes):
    """Deterministic bls12_381 key for tests/benches (the BLS analog of
    ``Ed25519PrivKey.from_secret``): RFC 9380 KeyGen over the padded
    secret, so the same seed yields the same key on every backend."""
    from .crypto import bls12381 as _bls

    return _bls.Bls12381PrivKey.from_secret(secret)


def make_light_chain(n_blocks: int, n_vals: int = 4, *,
                     chain_id: str = "light-chain", power: int = 10,
                     rotate_every: int = 0, seed: bytes = b"lc",
                     base_time_ns: int = 1_700_000_000_000_000_000,
                     block_interval_ns: int = 1_000_000_000,
                     fork_at: int = 0, fork_skew_ns: int = 0,
                     key_types=None):
    """Deterministic signed header chain for light-client tests/benches
    (role of the reference's ``light/helpers_test.go`` genLightBlocks).

    Returns ``list[LightBlock]`` for heights 1..n_blocks.  With
    ``rotate_every=k`` one validator is replaced every k blocks, so long
    skips eventually lose 1/3 overlap and force bisection.  With
    ``fork_at=f`` (and a nonzero ``fork_skew_ns``), blocks above height
    f get skewed timestamps: two calls differing only in these args
    share an identical, validly-signed prefix through f and diverge
    from f+1 — a real fork for detector tests (the same validator set
    double-signs both branches).

    ``key_types`` mixes key algorithms into the valset: a string applies
    to every validator, a sequence sets validator i's type (shorter
    sequences pad with ed25519).  BLS validators sign the zero-timestamp
    aggregation domain and each commit's BLS cohort is folded into the
    aggregate lane block (``types/commit.aggregate_commit``), exactly as
    ``VoteSet.make_commit`` would."""
    from .crypto.keys import Ed25519PrivKey
    from .light.types import LightBlock
    from .types.block_id import BlockID, PartSetHeader
    from .types.canonical import canonical_vote_sign_bytes
    from .types.commit import (BLOCK_ID_FLAG_COMMIT, Commit, CommitSig,
                               aggregate_commit)
    from .types.header import Header
    from .types.validator_set import Validator, ValidatorSet
    from .types.vote import PRECOMMIT_TYPE

    if key_types is None:
        key_types = ()
    elif isinstance(key_types, str):
        key_types = (key_types,) * n_vals

    def _priv(i: int):
        kt = key_types[i] if i < len(key_types) else "ed25519"
        if kt == "bls12_381":
            return bls_priv_from_secret(seed + b"bls%d" % i)
        return Ed25519PrivKey.from_secret(seed + b"%d" % i)

    privs = [_priv(i) for i in range(n_vals)]
    by_addr = {p.pub_key().address(): p for p in privs}
    vals = ValidatorSet([Validator(p.pub_key(), power) for p in privs])
    next_fresh = n_vals

    blocks: list[LightBlock] = []
    prev_bid = BlockID()
    for h in range(1, n_blocks + 1):
        next_vals = vals.copy()
        if rotate_every and h % rotate_every == 0:
            # replace the lexically-first validator with a fresh key
            new_priv = Ed25519PrivKey.from_secret(seed + b"%d" % next_fresh)
            next_fresh += 1
            by_addr[new_priv.pub_key().address()] = new_priv
            old = next_vals.validators[0]
            next_vals.update_with_change_set(
                [Validator(old.pub_key, 0),
                 Validator(new_priv.pub_key(), power)])
        header = Header(
            chain_id=chain_id, height=h,
            time_ns=base_time_ns + h * block_interval_ns
            + (fork_skew_ns if fork_at and h > fork_at else 0),
            last_block_id=prev_bid,
            validators_hash=vals.hash(),
            next_validators_hash=next_vals.hash(),
            proposer_address=vals.validators[0].address)
        bid = BlockID(header.hash(), PartSetHeader(1, b"\x5a" * 32))
        sigs = []
        for v in vals.validators:
            ts = header.time_ns + 1
            priv = by_addr[v.address]
            # BLS lanes sign the shared zero-timestamp aggregation
            # domain (types/vote.py sign_bytes_for)
            sign_ts = 0 if priv.type() == "bls12_381" else ts
            sb = canonical_vote_sign_bytes(chain_id, PRECOMMIT_TYPE, h, 0,
                                           bid, sign_ts)
            sigs.append(CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, ts,
                                  priv.sign(sb)))
        commit = aggregate_commit(Commit(h, 0, bid, sigs), vals)
        blocks.append(LightBlock(header=header, commit=commit,
                                 validators=vals.copy()))
        vals = next_vals
        prev_bid = bid
    return blocks


@dataclass
class InProcNode:
    name: str
    pv: object
    app: object
    state: object
    consensus: object
    block_store: object
    state_store: object
    mempool: object
    event_bus: object
    wal_path: str | None = None


class InProcNetwork:
    """Tier-1 harness: N validators in one event loop, direct queue wiring
    (the reference's common_test.go ensemble without networking)."""

    def __init__(self, nodes: list[InProcNode], partitions=None):
        self.nodes = nodes
        self.isolated: set[str] = set()      # names cut off from gossip
        self._catchup_task = None
        for node in nodes:
            self._wire(node)

    def _wire(self, node: InProcNode):
        cs = node.consensus

        def broadcast(fn_name, *args, _from=node.name):
            if _from in self.isolated:
                return
            for other in self.nodes:
                if other.name == _from or other.name in self.isolated:
                    continue
                getattr(other.consensus, fn_name)(*args, _from)

        cs.broadcast_proposal = lambda p, _f=node.name: broadcast(
            "feed_proposal", p, _from=_f)
        cs.broadcast_block_part = lambda h, r, part, _f=node.name: broadcast(
            "feed_block_part", h, r, part, _from=_f)
        cs.broadcast_vote = lambda v, _f=node.name: broadcast(
            "feed_vote", v, _from=_f)

    def isolate(self, name: str):
        self.isolated.add(name)

    def heal(self, name: str):
        self.isolated.discard(name)

    async def start(self):
        import asyncio

        for n in self.nodes:
            await n.consensus.start()
        self._catchup_task = asyncio.create_task(self._catchup_routine())

    async def stop(self):
        import asyncio

        if self._catchup_task is not None:
            self._catchup_task.cancel()
            try:
                await self._catchup_task
            except asyncio.CancelledError:
                pass
            self._catchup_task = None
        for n in self.nodes:
            await n.consensus.stop()

    async def _catchup_routine(self):
        """Feed lagging nodes the stored commit votes + block parts for
        their current height — the in-proc stand-in for the consensus
        reactor's catch-up gossip (gossipVotesRoutine earlier-height branch
        + gossipDataForCatchup, internal/consensus/reactor.go:590,646)."""
        import asyncio

        from .consensus.reactor import votes_from_commit

        while True:
            await asyncio.sleep(0.05)
            for lag in self.nodes:
                cs = lag.consensus
                if lag.name in self.isolated or cs._task is None or \
                        cs._task.done():
                    continue
                h = cs.rs.height
                for src in self.nodes:
                    if src is lag or src.name in self.isolated or \
                            src.block_store.height() < h:
                        continue
                    commit = src.block_store.load_block_commit(h)
                    if commit is None:
                        seen = src.block_store.load_seen_commit()
                        if seen is not None and seen.height == h:
                            commit = seen
                    if commit is None:
                        continue
                    for v in votes_from_commit(commit):
                        cs.feed_vote(v, f"catchup:{src.name}")
                    parts = src.block_store.load_block_parts(h)
                    if parts is not None:
                        for i in range(parts.total):
                            cs.feed_block_part(h, commit.round,
                                               parts.get_part(i),
                                               f"catchup:{src.name}")
                    break

    async def wait_for_height(self, height: int, timeout: float = 30.0,
                              nodes=None):
        import asyncio

        targets = nodes or self.nodes
        async def all_reached():
            while True:
                if all(t.block_store.height() >= height for t in targets):
                    return
                await asyncio.sleep(0.01)

        await asyncio.wait_for(all_reached(), timeout)


async def make_inproc_network(n_validators: int = 4, *, chain_id="test-net",
                              app_factory=None, config=None,
                              vote_extensions_height: int = 0,
                              pbts_height: int = 0,
                              wal_dir: str | None = None,
                              backend: str = "cpu",
                              power=None,
                              pv_factory=None) -> InProcNetwork:
    from .abci.kvstore import KVStoreApplication
    from .abci.client import LocalClient
    from .config import test_consensus_config
    from .consensus.state import ConsensusState
    from .consensus.wal import WAL
    from .libs.pubsub import EventBus
    from .mempool.clist_mempool import CListMempool
    from .sm.execution import BlockExecutor
    from .storage import BlockStore, MemDB, State, StateStore
    from .types.genesis import GenesisDoc, GenesisValidator
    from .types.priv_validator import MockPV

    app_factory = app_factory or KVStoreApplication
    cfg = config or test_consensus_config()
    pv_factory = pv_factory or \
        (lambda i: MockPV.from_secret(b"inproc%d" % i))
    pvs = [pv_factory(i) for i in range(n_validators)]
    doc = GenesisDoc(chain_id=chain_id,
                     validators=[GenesisValidator(
                         pv.get_pub_key(),
                         (power[i] if power else 10),
                         pop=getattr(pv, "pop", lambda: b"")())
                         for i, pv in enumerate(pvs)])
    doc.consensus_params.feature.vote_extensions_enable_height = \
        vote_extensions_height
    doc.consensus_params.feature.pbts_enable_height = pbts_height

    from .evidence import EvidencePool

    nodes = []
    for i, pv in enumerate(pvs):
        app = app_factory()
        client = LocalClient(app)
        bus = EventBus()
        bstore = BlockStore(MemDB())
        sstore = StateStore(MemDB())
        mp = CListMempool(LocalClient(app))
        state = State.from_genesis(doc)
        evpool = EvidencePool(state_store=sstore, block_store=bstore,
                              backend=backend)
        evpool.state = state
        execu = BlockExecutor(sstore, bstore, client, mp,
                              evidence_pool=evpool,
                              event_bus=bus, backend=backend)
        # app InitChain
        from .abci import types as abci_t
        await client.init_chain(abci_t.InitChainRequest(
            chain_id=chain_id, initial_height=1, time_ns=0,
            validators=[abci_t.ValidatorUpdate(
                v.pub_key.type(), v.pub_key.bytes(), v.power, pop=v.pop)
                for v in doc.validators],
            app_state_bytes=doc.app_state))
        wal = WAL(f"{wal_dir}/wal{i}.log") if wal_dir else None
        cs = ConsensusState(cfg, state, execu, bstore, wal=wal,
                            priv_validator=pv, event_bus=bus,
                            name=f"node{i}")
        cs.on_conflicting_vote = evpool.report_conflicting_votes
        nodes.append(InProcNode(
            name=f"node{i}", pv=pv, app=app, state=state, consensus=cs,
            block_store=bstore, state_store=sstore, mempool=mp,
            event_bus=bus, wal_path=f"{wal_dir}/wal{i}.log"
            if wal_dir else None))
    return InProcNetwork(nodes)
