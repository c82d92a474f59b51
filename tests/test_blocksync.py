"""Blocksync: cross-block batched commit verification (the flagship
cross-block TPU batching, BASELINE configs[4]) and fast-sync over real TCP
(reference: ``internal/blocksync/{pool,reactor}_test.go``)."""

import asyncio

import pytest

from cometbft_tpu.crypto.keys import Ed25519PrivKey
from cometbft_tpu.types.block_id import BlockID
from cometbft_tpu.types.part_set import PartSetHeader
from cometbft_tpu.types.validation import (ErrBatchItemInvalid,
                                           verify_commits_light_batched)
from cometbft_tpu.types.validator_set import Validator, ValidatorSet

from test_types import CHAIN_ID, make_commit

pytestmark = pytest.mark.timeout(150)


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def _vals(powers):
    privs = [Ed25519PrivKey.from_secret(b"bsv%d" % i)
             for i in range(len(powers))]
    vals = ValidatorSet([Validator(p.pub_key(), pw)
                         for p, pw in zip(privs, powers)])
    by_addr = {p.pub_key().address(): p for p in privs}
    return vals, by_addr


def _bid(h):
    return BlockID(bytes([h]) * 32, PartSetHeader(1, bytes([h ^ 0xFF]) * 32))


def test_batched_multi_commit_verify_ok():
    vals, by_addr = _vals([10] * 4)
    items = []
    for h in range(1, 6):
        commit = make_commit(vals, by_addr, height=h, round_=0, bid=_bid(h))
        items.append((commit.block_id, h, commit))
    n = verify_commits_light_batched(CHAIN_ID, vals, items, backend="cpu")
    assert n > 0


def test_batched_multi_commit_flags_offending_item():
    vals, by_addr = _vals([10] * 4)
    items = []
    for h in range(1, 6):
        bad = {0} if h == 3 else set()
        commit = make_commit(vals, by_addr, height=h, round_=0, bid=_bid(h),
                             bad_at=bad)
        items.append((commit.block_id, h, commit))
    with pytest.raises(ErrBatchItemInvalid) as exc:
        verify_commits_light_batched(CHAIN_ID, vals, items, backend="cpu")
    assert exc.value.item == 2 and exc.value.height == 3


def test_batched_multi_commit_flags_wrong_block_id():
    vals, by_addr = _vals([10] * 4)
    commit = make_commit(vals, by_addr, height=1, round_=0, bid=_bid(1))
    with pytest.raises(ErrBatchItemInvalid) as exc:
        verify_commits_light_batched(
            CHAIN_ID, vals, [(_bid(2), 1, commit)], backend="cpu")
    assert exc.value.item == 0


def test_fast_sync_over_tcp():
    """A late full node block-syncs a committed chain from 3 validators
    over real TCP, then follows via consensus (reactor.go:421-431
    SwitchToConsensus)."""
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.config import Config, test_consensus_config
    from cometbft_tpu.node import Node
    from cometbft_tpu.p2p import NodeKey
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
    from cometbft_tpu.types.priv_validator import MockPV

    def cfg():
        c = Config(consensus=test_consensus_config())
        c.p2p.laddr = "tcp://127.0.0.1:0"
        c.rpc.laddr = "tcp://127.0.0.1:0"
        return c

    async def main():
        pvs = [MockPV.from_secret(b"bsnode%d" % i) for i in range(3)]
        doc = GenesisDoc(chain_id="bs-net",
                         validators=[GenesisValidator(pv.get_pub_key(), 10)
                                     for pv in pvs])
        nodes = []
        for i, pv in enumerate(pvs):
            node = await Node.create(
                doc, KVStoreApplication(), priv_validator=pv, config=cfg(),
                node_key=NodeKey.from_secret(b"bsk%d" % i), name=f"bs{i}")
            nodes.append(node)
        try:
            for n in nodes:
                await n.start()
            for i, a in enumerate(nodes):
                for b in nodes[i + 1:]:
                    await a.dial_peer(b.listen_addr, persistent=True)
            for i in range(4):
                await nodes[0].mempool.check_tx(b"bs%d=x%d" % (i, i))

            async def reach(h, who):
                while not all(n.height() >= h for n in who):
                    await asyncio.sleep(0.02)

            await asyncio.wait_for(reach(6, nodes), 60)

            # late joiner: full node (no validator key), fast-sync mode
            late = await Node.create(
                doc, KVStoreApplication(), priv_validator=None, config=cfg(),
                node_key=NodeKey.from_secret(b"bsk9"), fast_sync=True,
                name="bslate")
            nodes.append(late)
            await late.start()
            for a in nodes[:3]:
                await late.dial_peer(a.listen_addr, persistent=True)

            # must blocksync to (near) tip, switch to consensus, and follow
            target = max(n.height() for n in nodes[:3]) + 3
            await asyncio.wait_for(reach(target, nodes), 90)
            assert late.blocksync_reactor.synced.is_set()
            for h in range(1, target + 1):
                hashes = {n.block_store.load_block(h).hash() for n in nodes}
                assert len(hashes) == 1, f"fork at height {h}"
        finally:
            for n in nodes:
                try:
                    await n.stop()
                except Exception:
                    pass
        return True

    assert run(main())


# ---------------------------------------------------------------------------
# r13 cross-block accumulator: pipelined windows, per-item demux, edges
# ---------------------------------------------------------------------------

from types import SimpleNamespace

from cometbft_tpu.blocksync import reactor as reactor_mod
from cometbft_tpu.blocksync.reactor import BlocksyncReactor


class _Blk:
    """Stub block: just enough surface for the accumulator (header,
    last_commit, hash, evidence); codec/PartSet are monkeypatched so the
    packed parts header matches the _bid() the commits signed."""

    def __init__(self, h, vals_hash, last_commit):
        self.header = SimpleNamespace(height=h, validators_hash=vals_hash)
        self.last_commit = last_commit
        self.evidence = []

    def hash(self):
        return bytes([self.header.height]) * 32


class _Parts:
    def __init__(self, blk):
        self._hdr = PartSetHeader(
            1, bytes([blk.header.height ^ 0xFF]) * 32)

    def header(self):
        return self._hdr


class _FakePool:
    """Deterministic in-memory BlockPool facade: serves a pre-built
    chain, mirrors redo_request's score-and-refetch semantics (the real
    pool reports ``bad_block`` for the serving peer and refetches)."""

    def __init__(self, start_h, blocks, on_peer_error=None,
                 good_blocks=None):
        self.height = start_h
        self.blocks = {b.header.height: b for b in blocks}
        self.good = {b.header.height: b for b in (good_blocks or [])}
        self.on_peer_error = on_peer_error or (lambda p, r, e: None)
        self.peers = {"p1": object()}
        self.redone = []
        self.max_h = max(self.blocks)

    def peek_window(self, n):
        out, h = [], self.height
        while len(out) < n and h in self.blocks:
            out.append((self.blocks[h], None))
            h += 1
        return out

    def pop_request(self):
        self.height += 1

    def redo_request(self, h):
        self.redone.append(h)
        self.on_peer_error(f"peer-of-{h}", f"bad block at {h}",
                           "bad_block")
        if h in self.good:          # the refetch serves an honest copy
            self.blocks[h] = self.good[h]
        return f"peer-of-{h}"

    def is_caught_up(self):
        # the real pool is caught up at the best peer height; the final
        # block (no voucher yet) is consensus's to finish
        return self.height >= self.max_h

    async def stop(self):
        pass


def _chain(vals, by_addr, first_h, last_h, *, bad_commit_for=(),
           wrong_bid_for=()):
    """Blocks first_h..last_h whose last_commit certifies the previous
    height with REAL signatures (the accumulator's items).  The first
    block's own last_commit is irrelevant (never verified)."""
    blocks = []
    vh = vals.hash()
    for h in range(first_h, last_h + 1):
        if h == first_h:
            lc = None
        else:
            prev = h - 1
            bid = _bid(prev + 2) if prev in wrong_bid_for else _bid(prev)
            lc = make_commit(vals, by_addr, height=prev, round_=0,
                             bid=bid,
                             bad_at={0} if prev in bad_commit_for
                             else set())
        blocks.append(_Blk(h, vh, lc))
    return blocks


def _mk_reactor(monkeypatch, vals, pool, verify_window=4,
                valset_after=None):
    """Reactor wired to stubs: real commit verification, no-op
    structural validation/storage, report_peer recorder."""
    monkeypatch.setattr(reactor_mod, "codec",
                        SimpleNamespace(pack=lambda b: b))
    monkeypatch.setattr(reactor_mod, "PartSet",
                        SimpleNamespace(from_data=lambda b: _Parts(b)))
    monkeypatch.setattr(reactor_mod, "validate_block",
                        lambda *a, **k: None)
    state = SimpleNamespace(
        chain_id=CHAIN_ID, validators=vals,
        consensus_params=SimpleNamespace(feature=SimpleNamespace(
            vote_extensions_enabled=lambda h: False)))
    applied = []

    async def apply_block(st, fid, blk, verified=False):
        applied.append(blk.header.height)
        if valset_after and blk.header.height in valset_after:
            return SimpleNamespace(
                chain_id=st.chain_id,
                validators=valset_after[blk.header.height],
                consensus_params=st.consensus_params)
        return st

    block_exec = SimpleNamespace(
        apply_block=apply_block,
        evidence_pool=SimpleNamespace(check_evidence=lambda ev: None))
    block_store = SimpleNamespace(
        save_block=lambda *a: None,
        save_block_with_extended_commit=lambda *a: None,
        height=lambda: pool.height - 1, base=lambda: 0)
    r = BlocksyncReactor(block_exec, block_store, state,
                         backend="cpu", verify_window=verify_window)
    r.pool = pool
    reports = []
    r.switch = SimpleNamespace(
        report_peer=lambda pid, ev, detail=None, disconnect=False:
        reports.append((pid, ev)),
        peers={})
    pool.on_peer_error = r._on_pool_peer_error
    return r, applied, reports


async def _drain(r):
    await asyncio.wait_for(r._apply_routine(), 30)


def test_accumulator_applies_full_chain(monkeypatch):
    """Windows deeper than one dispatch pipeline through: every block
    whose commit a successor vouches for applies."""
    vals, by_addr = _vals([10] * 4)
    blocks = _chain(vals, by_addr, 1, 9)
    pool = _FakePool(1, blocks)
    r, applied, _ = _mk_reactor(monkeypatch, vals, pool, verify_window=3)

    run(_drain(r))
    # block 9 has no voucher in the pool; 1..8 apply in order
    assert applied == list(range(1, 9))
    assert r.synced.is_set()


def test_accumulator_partial_window_flush(monkeypatch):
    """Pool drain: fewer blocks than the window dispatch immediately
    (no waiting for a full buffer)."""
    vals, by_addr = _vals([10] * 4)
    pool = _FakePool(1, _chain(vals, by_addr, 1, 3))
    r, applied, _ = _mk_reactor(monkeypatch, vals, pool,
                                verify_window=32)
    run(_drain(r))
    assert applied == [1, 2]


def test_accumulator_valset_change_mid_window(monkeypatch):
    """A rotation inside the peeked window: the same-valset prefix
    verifies and applies, then the loop re-stages the suffix against
    the post-apply validator set."""
    vals_a, by_a = _vals([10] * 4)
    privs_b = [Ed25519PrivKey.from_secret(b"bsw%d" % i) for i in range(4)]
    vals_b = ValidatorSet([Validator(p.pub_key(), 10) for p in privs_b])
    by_b = {p.pub_key().address(): p for p in privs_b}

    chain_a = _chain(vals_a, by_a, 1, 4)           # blocks 1..4, set A
    chain_b = _chain(vals_b, by_b, 4, 7)[1:]       # blocks 5..7, set B
    for b in chain_b:
        b.header.validators_hash = vals_b.hash()
    # block 5 vouches for 4 with a commit signed by A (the set that
    # committed height 4)
    chain_b[0].last_commit = make_commit(vals_a, by_a, height=4,
                                         round_=0, bid=_bid(4))
    pool = _FakePool(1, chain_a + chain_b)
    r, applied, _ = _mk_reactor(monkeypatch, vals_a, pool,
                                verify_window=16,
                                valset_after={4: vals_b})
    run(_drain(r))
    assert applied == [1, 2, 3, 4, 5, 6]


def test_accumulator_statesync_anchor_window(monkeypatch):
    """A window starting right after the statesync anchor: the anchor
    block itself is never applied, the first fetched block's commit is
    vouched by its successor as usual."""
    vals, by_addr = _vals([10] * 4)
    pool = _FakePool(101, _chain(vals, by_addr, 101, 106))
    r, applied, _ = _mk_reactor(monkeypatch, vals, pool, verify_window=4)
    run(_drain(r))
    assert applied == list(range(101, 106))


def test_accumulator_bad_commit_demux(monkeypatch):
    """One lying peer's block: the proven prefix still applies, exactly
    the bad height (+ its voucher) redoes, the serving peer is scored
    bad_block through Switch.report_peer, and after the honest refetch
    the chain completes."""
    vals, by_addr = _vals([10] * 4)
    bad = _chain(vals, by_addr, 1, 7, bad_commit_for={4})
    good = _chain(vals, by_addr, 1, 7)
    pool = _FakePool(1, bad, good_blocks=good)
    r, applied, reports = _mk_reactor(monkeypatch, vals, pool,
                                      verify_window=8)
    run(_drain(r))
    # neighbors 1..3 applied BEFORE the redo; the refetched 4.. follow
    assert applied == [1, 2, 3, 4, 5, 6]
    assert pool.redone[:2] == [4, 5]
    assert ("peer-of-4", "bad_block") in reports


def test_accumulator_basics_failure_demux(monkeypatch):
    """A pre-dispatch failure (wrong block ID in a voucher commit) must
    not let unproven neighbors ride along: the prefix is re-proven
    separately, applies, and only the offending height redoes."""
    vals, by_addr = _vals([10] * 4)
    bad = _chain(vals, by_addr, 1, 6, wrong_bid_for={3})
    good = _chain(vals, by_addr, 1, 6)
    pool = _FakePool(1, bad, good_blocks=good)
    r, applied, reports = _mk_reactor(monkeypatch, vals, pool,
                                      verify_window=8)
    run(_drain(r))
    assert applied == [1, 2, 3, 4, 5]
    assert pool.redone[:2] == [3, 4]
    assert ("peer-of-3", "bad_block") in reports


def test_stage_window_double_buffers_disjoint_heights(monkeypatch):
    """The second buffer stages the blocks BEHIND the in-flight window —
    disjoint heights, no overlap, packed while the first verifies."""
    vals, by_addr = _vals([10] * 4)
    pool = _FakePool(1, _chain(vals, by_addr, 1, 12))
    r, _, _ = _mk_reactor(monkeypatch, vals, pool, verify_window=4)

    async def main():
        a = r._stage_window(0)
        b = r._stage_window(a.n_blocks)
        assert a.first_height == 1 and a.n_blocks == 4
        assert b.first_height == 5 and b.n_blocks == 4
        pa, ea = await a.task
        pb, eb = await b.task
        assert ea is None and eb is None
        assert [p[0].header.height for p in pa] == [1, 2, 3, 4]
        assert [p[0].header.height for p in pb] == [5, 6, 7, 8]
        return True

    assert run(main())


def test_verify_window_config_knob():
    from cometbft_tpu.config import Config, ConfigError

    cfg = Config()
    assert cfg.blocksync.verify_window == 32
    cfg.blocksync.verify_window = 1
    with pytest.raises(ConfigError):
        cfg.validate()
    cfg.blocksync.verify_window = 8192
    with pytest.raises(ConfigError):
        cfg.validate()
    cfg.blocksync.verify_window = 256
    cfg.validate()
