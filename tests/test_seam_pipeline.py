"""The backend seam's pipelined chunk loop (``crypto/batch.py``
``device_verify_ed25519_cached``): every chunk of a call is packed and
launched before any is read back, a patient caller that is going to queue
packs on its own thread, and a fail-fast call of one chunk runs the
sequence it always ran.

Nothing is compiled: the table, RLC and per-lane programs are stand-ins
that judge a lane by a marker byte of its ``s`` half, so what is tested is
the loop (order, threads, which program sees which chunk, how verdicts are
put together), not the kernels."""

import dataclasses
import threading

import numpy as np
import pytest

from cometbft_tpu.crypto import batch as B
from cometbft_tpu.crypto import plan as P
from cometbft_tpu.crypto import rlc_finish
from cometbft_tpu.libs import tracing

BUCKET = 16
BAD = 255               # first byte of a tampered lane's ``s`` half
OWNER = "tpu-verify"    # the device-owner thread's name prefix


class Programs:
    """Stand-ins for the three compiled programs, recording who ran what."""

    def __init__(self, monkeypatch):
        self.launched = []          # (kind, first table row, thread name)
        self.packed_on = []         # thread name of every pack
        monkeypatch.setattr(B, "_compiled_prepare_tables",
                            lambda: self._tables)
        monkeypatch.setattr(B, "_compiled_rlc_gather", lambda: self._rlc)
        monkeypatch.setattr(B, "_compiled_verify_gather",
                            lambda devices: self._gather)
        pad = B._padded_lane_args

        def padded(*a):
            self.packed_on.append(threading.current_thread().name)
            return pad(*a)

        monkeypatch.setattr(B, "_padded_lane_args", padded)

    @staticmethod
    def _tables(padded):
        return np.zeros((1,), np.int32), np.ones((padded.shape[0],), bool)

    def _note(self, kind, lead):
        self.launched.append((kind, int(lead[0]),
                              threading.current_thread().name))

    def _rlc(self, tab, ok, lead, r32, s32, blocks, active, z10):
        self._note("rlc_gather", lead)
        assert z10.shape == (BUCKET, 10)
        return rlc_finish.verdict(bool((s32[:, 0] != BAD).all()))

    def _gather(self, tab, ok, lead, r32, s32, blocks, active):
        self._note("gather", lead)
        return s32[:, 0] != BAD

    def kinds(self):
        return [(kind, row) for kind, row, _ in self.launched]


@pytest.fixture
def seam(monkeypatch):
    saved = P.active()
    P.set_plan(dataclasses.replace(saved, lane_buckets=(BUCKET,),
                                   rlc_min_lanes=4), push_min_lanes=False)
    monkeypatch.setattr(B, "_DEVICE_INFLIGHT", None)
    tables = set(B._VALSET_TABLES)
    tracing.configure(enabled=True)
    tracing.clear()
    try:
        yield Programs(monkeypatch)
    finally:
        tracing.configure(enabled=False)
        tracing.clear()
        P.set_plan(saved, push_min_lanes=False)
        for key in set(B._VALSET_TABLES) - tables:  # the stand-in's tables
            B._VALSET_TABLES.pop(key, None)


def batch(k, bad=()):
    """``k`` lanes over a ``k``-row validator set; lane i gathers row i."""
    rng = np.random.default_rng(k)
    valset = rng.integers(0, 256, (k, 32), dtype=np.uint8)
    sigs = rng.integers(0, 256, (k, 64), dtype=np.uint8)
    sigs[:, 32] = 0
    sigs[list(bad), 32] = BAD
    msgs = rng.integers(0, 256, (k, 100), dtype=np.uint8)
    return dict(pubs=valset, sigs=sigs, msgs=msgs,
                lens=np.full((k,), 100, np.int64),
                valset_pubs=valset, scope=np.arange(k, dtype=np.int64))


def chunk_at_a_time(progs, b):
    """The loop as it was: pack a chunk, run it, read it back, then the
    next; the reference the pipelined loop's verdicts must equal."""
    k = b["pubs"].shape[0]
    rs, ss = b["sigs"][:, :32], b["sigs"][:, 32:]
    tab, ok = progs._tables(np.zeros((k, 32), np.int32))
    out = np.zeros((k,), bool)
    for start in range(0, k, BUCKET):
        sl = slice(start, min(start + BUCKET, k))
        c = sl.stop - sl.start
        args, z10 = B._pack(b["pubs"][sl], rs[sl], ss[sl], b["msgs"][sl],
                            b["lens"][sl], BUCKET, scope=b["scope"][sl])
        if z10 is not None and B._run("rlc_gather", progs._rlc,
                                      (tab, ok, *args, z10), c, BUCKET):
            out[sl] = True
        else:
            out[sl] = B._run("gather", progs._gather, (tab, ok, *args), c,
                             BUCKET)[:c]
    return out


def spans(name):
    return sorted((r for r in tracing.dump(limit=0)
                   if r["sub"] == "crypto.seam" and r["name"] == name),
                  key=lambda r: r["start_ns"])


def chunks_counted(since=(0, 0)):
    """``crypto_seam_chunks_total``: (ahead, inline), less ``since``."""
    c = B._seam_chunks()
    return (c.value(prepared="ahead") - since[0],
            c.value(prepared="inline") - since[1])


# 40 lanes = 16 + 16 + 8; 35 = 16 + 16 + 3, whose tail is below the RLC
# threshold (4) and goes to the per-lane program at once
@pytest.mark.parametrize("patient", [False, True])
@pytest.mark.parametrize("k,bad", [
    (40, ()), (40, (0,)), (40, (20,)), (40, (39,)), (40, (3, 17, 33)),
    (35, ()), (35, (34,)), (48, (47,)), (16, (5,)), (7, ()),
], ids=["clean", "first", "middle", "last", "every_chunk", "short_tail",
        "short_tail_bad", "full_chunks_last", "one_chunk_bad", "one_small"])
def test_verdicts_equal_the_chunk_at_a_time_loop(seam, k, bad, patient):
    b = batch(k, bad)
    expected = chunk_at_a_time(seam, b)
    assert expected.tolist() == [i not in bad for i in range(k)]
    reference_launches = seam.kinds()
    seam.launched.clear()
    ok, oks = B.verify_dense("jax", **b, patient=patient)
    assert oks.dtype == bool and oks.tolist() == expected.tolist()
    assert ok is not bool(bad)
    # the same programs over the same chunks, each once
    assert sorted(seam.kinds()) == sorted(reference_launches)


@pytest.mark.parametrize("bad_chunk", [0, 1, 2])
def test_a_refuted_chunk_launches_one_gather_for_that_chunk_only(
        seam, bad_chunk):
    b = batch(48, bad=(bad_chunk * BUCKET + 5,))
    _, oks = B.verify_dense("jax", **b)
    assert np.flatnonzero(~oks).tolist() == [bad_chunk * BUCKET + 5]
    # the three RLC launches in chunk order first, then the localization
    assert seam.kinds() == [("rlc_gather", 0), ("rlc_gather", 16),
                            ("rlc_gather", 32),
                            ("gather", bad_chunk * BUCKET)]
    launches, readbacks = spans("launch"), spans("readback")
    assert [r["attrs"]["kind"] for r in launches] == \
        ["rlc_gather"] * 3 + ["gather"]
    assert [(r["attrs"]["kind"], r["attrs"]["ok"]) for r in readbacks] == \
        [(kind, ok) for i in range(3) for kind, ok in
         ([("rlc_gather", True)] if i != bad_chunk else
          [("rlc_gather", False), ("gather", False)])]
    # the gather was launched when its chunk's fold said so: after that
    # chunk's RLC readback, before the next chunk's
    gather = launches[3]
    assert readbacks[bad_chunk]["end_ns"] <= gather["start_ns"]
    assert gather["end_ns"] <= readbacks[bad_chunk + 1]["start_ns"]


@pytest.mark.parametrize("k", [33, 48, 96])
def test_every_rlc_launch_precedes_the_first_readback(seam, k):
    B.verify_dense("jax", **batch(k))
    launches, readbacks = spans("launch"), spans("readback")
    n = -(-k // BUCKET)
    assert len(launches) == len(readbacks) == n
    assert max(r["end_ns"] for r in launches) \
        <= min(r["start_ns"] for r in readbacks)
    # packed and launched alternately: chunk k+1 is packed after chunk k
    # is on its way, and the chip starts on chunk 0 before chunk 1 exists
    order = sorted(spans("pack") + launches, key=lambda r: r["start_ns"])
    assert [r["name"] for r in order] == ["pack", "launch"] * n
    assert all(t.startswith(OWNER) for _, _, t in seam.launched)


def hold_the_device():
    """Put a dispatch in flight on the device-owner thread; returns the
    gate that lets it go and the thread that waits for it."""
    gate, started = threading.Event(), threading.Event()

    def dispatch():
        started.set()
        return gate.wait(30)

    holder = threading.Thread(
        target=lambda: B._device_call(dispatch, patient=30.0))
    holder.start()
    assert started.wait(10) and B._device_busy()
    return gate, holder


def test_a_patient_call_that_will_queue_packs_on_its_own_thread(seam):
    gate, holder = hold_the_device()
    before = chunks_counted()
    result = {}
    caller = threading.Thread(name="staging-0", target=lambda: result.update(
        out=B.verify_dense("jax", **batch(40, bad=(20,)), patient=True)))
    try:
        caller.start()
        # all three chunks are packed while the dispatch ahead still holds
        # the device, and nothing of this call has been launched
        for _ in range(1000):
            if len(seam.packed_on) == 3:
                break
            threading.Event().wait(0.01)
        assert seam.packed_on == ["staging-0"] * 3
        assert seam.launched == []
    finally:
        gate.set()
        holder.join(30)
        caller.join(30)
    assert not holder.is_alive() and not caller.is_alive()
    ok, oks = result["out"]
    assert not ok and np.flatnonzero(~oks).tolist() == [20]
    assert seam.packed_on == ["staging-0"] * 3      # the owner packed none
    assert all(t.startswith(OWNER) for _, _, t in seam.launched)
    packs = spans("pack")
    assert [r["attrs"]["ahead"] for r in packs] == [True] * 3
    assert chunks_counted(before) == (3, 0)
    # packed before it queued, launched after: the caller's packs name
    # its ``verify_dense`` as their parent, as the owner's spans do
    (dense,) = [r for r in spans("verify_dense")
                if r["attrs"]["lanes"] == 40]
    (queue,) = [r for r in spans("queue") if r["parent"] == dense["id"]]
    assert all(r["parent"] == dense["id"] for r in packs)
    assert max(r["end_ns"] for r in packs) <= queue["start_ns"]
    assert queue["end_ns"] <= min(
        r["start_ns"] for r in spans("launch"))


@pytest.mark.parametrize("patient", [False, True])
def test_a_call_that_finds_the_chip_free_packs_on_the_owner_thread(
        seam, patient):
    before = chunks_counted()
    B.verify_dense("jax", **batch(40), patient=patient)
    assert len(seam.packed_on) == 3
    assert all(t.startswith(OWNER) for t in seam.packed_on)
    assert [r["attrs"]["ahead"] for r in spans("pack")] == \
        [False, True, True]
    assert chunks_counted(before) == (2, 1)


def test_a_fail_fast_call_behind_a_dispatch_in_flight_packs_nothing(seam):
    """The fast-fail is what it was: no packing for a call that will not
    ride the device (``verify_dense`` then answers from the host)."""
    gate, holder = hold_the_device()
    try:
        assert B._device_call(lambda: 1) is None
        assert seam.packed_on == []
    finally:
        gate.set()
        holder.join(30)
    assert not holder.is_alive()


@pytest.mark.parametrize("k,rlc", [(12, True), (3, False)])
def test_a_fail_fast_one_chunk_call_runs_the_sequence_it_always_ran(
        seam, k, rlc):
    before = chunks_counted()
    B.verify_dense("jax", **batch(k))
    recs = sorted((r for r in tracing.dump(limit=0)
                   if r["sub"] == "crypto.seam"),
                  key=lambda r: r["start_ns"])
    dense = recs[0]
    assert dense["name"] == "verify_dense" and dense["attrs"] == {
        "lanes": k, "patient": False, "route": "device"}
    seq = [r for r in recs[1:] if r["name"] != "finish"]
    assert [r["name"] for r in seq] == ["queue", "tables", "pack", "launch",
                                        "readback"]
    assert all(r["parent"] == dense["id"] for r in seq)
    # each ends before the next starts: nothing overlaps, nothing is ahead
    assert all(a["end_ns"] <= b["start_ns"] for a, b in zip(seq, seq[1:]))
    kind = "rlc_gather" if rlc else "gather"
    attrs = {r["name"]: r["attrs"] for r in seq}
    assert attrs["pack"] == {"lanes": k, "bucket": BUCKET, "blocks": 2,
                             "ahead": False}
    assert attrs["launch"] == {"kind": kind, "lanes": k, "bucket": BUCKET}
    assert attrs["readback"] == {"kind": kind, "ok": True}
    assert [r["name"] for r in recs if r["name"] == "finish"] == \
        ["finish"] * rlc
    assert len(seam.packed_on) == 1 and seam.packed_on[0].startswith(OWNER)
    assert seam.launched == [(kind, 0, seam.packed_on[0])]
    assert chunks_counted(before) == (0, 1)
