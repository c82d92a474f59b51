"""WAL segment rotation + background pruner (reference:
``internal/autofile/group_test.go``, ``state/pruner.go``)."""

import asyncio
import os

import pytest

from cometbft_tpu.consensus.wal import WAL

pytestmark = pytest.mark.timeout(60)


def test_wal_rotates_and_replays_across_segments(tmp_path):
    path = str(tmp_path / "cs.wal")
    wal = WAL(path, max_segment_bytes=2048)
    # no sentinels yet: nothing may be pruned, so rotation is observable
    wal.write_sync({"#": "endheight", "h": 0})  # raw record, no pruning
    for h in (3, 4, 5):
        for i in range(20):
            wal.write({"#": "vote", "peer": "", "data": {"h": h, "i": i,
                                                         "pad": "x" * 64}})
        wal.write({"#": "endheight", "h": h})
    wal.flush_and_sync()
    segs = wal._segments()
    assert len(segs) > 1, "no rotation happened"
    # replay after height 3 sees exactly the height 4+5 records,
    # crossing segment boundaries
    recs = wal.records_after_height(3)
    hs = {r["data"]["h"] for r in recs}
    assert hs == {4, 5}, hs
    wal.close()

    # reopen: same answer (cross-segment iteration from disk)
    wal2 = WAL(path, max_segment_bytes=2048)
    recs2 = wal2.records_after_height(3)
    assert len(recs2) == len(recs)
    # checkpointing now prunes segments wholly before the last sentinel
    wal2.write_end_height(6)
    assert len(wal2._segments()) < len(segs) + 1
    assert wal2.records_after_height(6) == []
    wal2.close()


def test_wal_prunes_old_segments(tmp_path):
    path = str(tmp_path / "cs.wal")
    wal = WAL(path, max_segment_bytes=1024)
    for h in range(1, 12):
        for i in range(10):
            wal.write({"#": "vote", "peer": "",
                       "data": {"h": h, "pad": "y" * 64}})
        wal.write_end_height(h)
    # old segments were dropped by the end-height checkpointing, but
    # replay after the LAST height still works
    assert wal.records_after_height(11) == []
    n_before = len(wal._segments())
    assert n_before < 11
    wal.close()


def test_wal_endheight_search_reads_only_tail_segments(tmp_path):
    """``records_after_height`` binary-searches the
    segment list (autofile group.go:34-54 SearchForEndHeight parity)
    instead of decoding every record of every segment — a long-lived
    validator restarting with a big WAL must read O(log n) segment
    heads plus the tail, not the whole log."""
    path = str(tmp_path / "wal.log")
    wal = WAL(path, max_segment_bytes=1500)
    # many heights, padded records so segments rotate often; pruning is
    # deliberately defeated by reopening (prune boundary unknown) so the
    # full history stays on disk
    for h in range(1, 41):
        wal.write({"h": h, "pad": "x" * 300})
        wal.write({"h": h, "msg": "vote", "pad": "y" * 300})
        wal.write_sync({"#": "endheight", "h": h})
        wal._prev_sentinel_seg = None      # keep every segment
    segs = wal._segments()
    assert len(segs) >= 10, f"need many segments, got {len(segs)}"

    read_paths: list[str] = []
    orig = WAL._iter_segment

    def spy(self, p):
        read_paths.append(p)
        return orig(self, p)

    WAL._iter_segment = spy
    try:
        recs = wal.records_after_height(39)
    finally:
        WAL._iter_segment = orig
    # correctness: exactly height 40's records follow EndHeight(39)
    assert [r["h"] for r in recs] == [40, 40]
    # efficiency: probes + tail scan, strictly less than the full log
    assert len(set(read_paths)) < len(segs), (
        f"read {len(set(read_paths))}/{len(segs)} segments")
    import math
    assert len(set(read_paths)) <= 2 * math.ceil(math.log2(len(segs))) + 3
    # the earliest segments were never touched
    assert segs[0] not in read_paths and segs[1] not in read_paths
    # and the verdict matches a full scan
    full = [r for r in wal.iter_records()]
    after = []
    seen = False
    for r in full:
        if r.get("#") == "endheight":
            seen = r["h"] == 39 or (seen and r["h"] > 39)
            continue
        if seen:
            after.append(r)
    assert recs == after
    wal.close()


def test_wal_torn_tail_truncated_on_reopen(tmp_path):
    path = str(tmp_path / "cs.wal")
    wal = WAL(path)
    wal.write_sync({"#": "vote", "peer": "", "data": 1})
    wal.write_end_height(1)
    wal.close()
    with open(path, "ab") as f:
        f.write(b"\x13\x37garbage-torn-tail")
    wal2 = WAL(path)
    recs = list(wal2.iter_records())
    assert len(recs) == 2
    wal2.close()


def _tear_next_write(path, spec, record, **wal_kw):
    """Arm the ``wal.write.torn`` chaos site, write one record (which
    tears), and return the reopened WAL."""
    from cometbft_tpu.consensus.wal import WAL, WALError
    from cometbft_tpu.libs import failures as F

    wal = WAL(path, **wal_kw)
    F.configure(enabled=True, seed=13, faults=[spec])
    try:
        with pytest.raises(WALError):
            wal.write(record)
        # fsyncgate: the torn handle is dead
        with pytest.raises(WALError):
            wal.write({"#": "vote", "n": -1})
    finally:
        F.reset()
        try:
            wal.close()
        except OSError:
            pass
    return WAL(path, **wal_kw)


@pytest.mark.parametrize("cut", ["header", "body"])
def test_wal_torn_write_truncated_on_reopen(tmp_path, cut):
    """Injected truncation mid-header and mid-record (wal.write.torn
    site): reopen keeps every intact record, drops the torn tail, and
    the WAL stays appendable."""
    from cometbft_tpu.consensus.wal import WAL

    path = str(tmp_path / "cs.wal")
    wal = WAL(path)
    for i in range(5):
        wal.write_sync({"#": "vote", "n": i, "pad": "x" * 40})
    wal.close()
    size_before = os.path.getsize(path)

    wal2 = _tear_next_write(path, f"wal.write.torn:at=1:cut={cut}",
                            {"#": "vote", "n": 99, "pad": "y" * 40})
    # the torn bytes hit the disk, but reopen truncated them: only the
    # 5 intact records remain and the file is back to its clean length
    recs = list(wal2.iter_records())
    assert [r["n"] for r in recs] == [0, 1, 2, 3, 4]
    assert os.path.getsize(path) == size_before
    wal2.write_sync({"#": "vote", "n": 100})
    assert [r["n"] for r in wal2.iter_records()][-1] == 100
    wal2.close()


def test_wal_torn_write_across_segment_boundary(tmp_path):
    """A torn record in a freshly-rotated segment: reopen truncates ONLY
    the new segment's tail; every earlier segment and the replay index
    (records_after_height) stay intact."""
    from cometbft_tpu.consensus.wal import WAL

    path = str(tmp_path / "cs.wal")
    wal = WAL(path, max_segment_bytes=1024)
    for h in (1, 2):
        for i in range(12):
            wal.write({"#": "vote", "peer": "",
                       "data": {"h": h, "i": i, "pad": "z" * 48}})
        wal.write_sync({"#": "endheight", "h": h})
        wal._prev_sentinel_seg = None       # keep every segment
    wal.flush_and_sync()
    segs = wal._segments()
    assert len(segs) > 1, "no rotation happened"
    wal.close()

    wal2 = _tear_next_write(path, "wal.write.torn:at=1:cut=body",
                            {"#": "vote", "peer": "", "data": {"h": 3}},
                            max_segment_bytes=1024)
    # replay after height 1 still yields exactly height 2's records,
    # crossing the intact segment boundary; the torn record is gone
    recs = wal2.records_after_height(1)
    assert {r["data"]["h"] for r in recs if "data" in r} == {2}
    assert wal2.records_after_height(2) == []
    # the earlier segments were untouched by the truncation
    assert wal2._segments()[:len(segs) - 1] == segs[:len(segs) - 1]
    wal2.close()


def test_wal_fsync_eio_site_kills_handle_not_file(tmp_path):
    """``wal.fsync.eio``: the failing fsync raises OSError(EIO), every
    later operation on the handle raises WALError (fsyncgate: no retry
    on the same fd), and a fresh open replays everything that landed."""
    import errno

    from cometbft_tpu.consensus.wal import WAL, WALError
    from cometbft_tpu.libs import failures as F

    path = str(tmp_path / "cs.wal")
    wal = WAL(path)
    wal.write_sync({"#": "vote", "n": 1})
    F.configure(enabled=True, seed=3, faults=["wal.fsync.eio:at=1"])
    try:
        with pytest.raises(OSError) as ei:
            wal.write_sync({"#": "vote", "n": 2})
        assert ei.value.errno == errno.EIO
        for op in (lambda: wal.flush_and_sync(),
                   lambda: wal.write({"#": "vote", "n": 3})):
            with pytest.raises(WALError):
                op()
    finally:
        F.reset()
    wal2 = WAL(path)
    # record 2's buffered write landed before the injected fsync failure
    assert [r["n"] for r in wal2.iter_records()] == [1, 2]
    wal2.close()


def test_pruner_honors_min_of_app_and_companion(tmp_path):
    from cometbft_tpu.sm.pruner import Pruner
    from cometbft_tpu.storage import BlockStore, MemDB, StateStore
    from cometbft_tpu.testing import make_light_chain
    from cometbft_tpu.types import codec
    from cometbft_tpu.types.part_set import PartSet

    bstore = BlockStore(MemDB())
    sstore = StateStore(MemDB())
    # synthesize a stored chain (structure only; pruning needs no sigs)
    from cometbft_tpu.types.header import Block, Data

    chain = make_light_chain(10, n_vals=2)
    prev_commit = None
    for lb in chain:
        block = Block(header=lb.header, data=Data(txs=[]),
                      evidence=[], last_commit=prev_commit)
        parts = PartSet.from_data(codec.pack(block))
        bstore.save_block(block, parts, lb.commit)
        prev_commit = lb.commit

    pruner = Pruner(sstore, bstore)
    assert bstore.base() == 1
    pruner.set_app_retain_height(8)
    assert pruner.prune_once() == 0 or bstore.base() == 8
    # companion lags at 5: effective retain is min(8, 5)
    bstore2 = bstore
    pruner.set_companion_retain_height(5)
    assert pruner.effective_retain_height() == 5
    pruner.set_companion_retain_height(0)        # companion detaches
    pruner.set_app_retain_height(9)
    pruned = pruner.prune_once()
    assert bstore2.base() == 9
    assert bstore2.load_block(8) is None
    assert bstore2.load_block(9) is not None


def test_pruner_via_rpc_route():
    from cometbft_tpu.rpc.core import (retain_heights,
                                       set_companion_retain_height,
                                       Environment)

    class FakePruner:
        def __init__(self):
            self.app, self.dc = 7, 0

        def retain_heights(self):
            return self.app, self.dc

        def effective_retain_height(self):
            return min(self.app, self.dc) if self.app and self.dc \
                else self.app or self.dc

        def set_companion_retain_height(self, h):
            self.dc = h

    class FakeStore:
        def base(self):
            return 3

    class FakeNode:
        pruner = FakePruner()
        block_store = FakeStore()

    env = Environment(FakeNode())

    async def main():
        r = await retain_heights(env)
        assert r["app_retain_height"] == 7 and r["store_base"] == 3
        await set_companion_retain_height(env, height=4)
        r2 = await retain_heights(env)
        assert r2["data_companion_retain_height"] == 4
        assert r2["effective"] == 4
        return True

    assert asyncio.run(main())
