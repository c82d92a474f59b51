"""Flight-recorder tracing (``libs/tracing``): ring-buffer semantics,
concurrent writers, disabled-mode cost, the ``/dump_trace`` +enriched
``/status`` RPC surface, and the tentpole acceptance — one committed
height whose consensus step spans contain the vote scheduler's verify
micro-batch dispatches."""

import asyncio
import sys
import threading

import pytest

from cometbft_tpu.libs import tracing

pytestmark = pytest.mark.timeout(120)


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


@pytest.fixture(autouse=True)
def _tracer_hygiene():
    """Tracing state is process-global: every test starts disabled/empty
    and leaves it that way (node tests elsewhere assume tracing off)."""
    tracing.configure(enabled=False, ring_size=8192)
    tracing.clear()
    yield
    tracing.configure(enabled=False, ring_size=8192)
    tracing.clear()


# ------------------------------------------------------------- core API


def test_event_span_records_and_ordering():
    tracing.configure(enabled=True)
    tracing.event("t", "first", x=1)
    with tracing.span("t", "outer", height=7):
        tracing.event("t", "inner")
    recs = tracing.dump()
    assert [r["name"] for r in recs] == ["first", "inner", "outer"]
    ev_first, ev_inner, sp = recs
    assert ev_first["kind"] == "event" and ev_first["attrs"] == {"x": 1}
    assert sp["kind"] == "span" and sp["attrs"]["height"] == 7
    assert sp["dur_us"] >= 0 and sp["end_ns"] >= sp["start_ns"]
    # the inner event happened within the outer span and points at it
    assert ev_inner["parent"] == sp["id"]
    assert sp["start_ns"] <= ev_inner["start_ns"] <= sp["end_ns"]
    # ids are unique
    assert len({r["id"] for r in recs}) == 3


def test_span_nesting_parent_chain():
    tracing.configure(enabled=True)
    with tracing.span("t", "a"):
        with tracing.span("t", "b"):
            with tracing.span("t", "c"):
                pass
    by_name = {r["name"]: r for r in tracing.dump()}
    assert by_name["c"]["parent"] == by_name["b"]["id"]
    assert by_name["b"]["parent"] == by_name["a"]["id"]
    assert by_name["a"]["parent"] == 0
    # completion order is inside-out; start order is outside-in
    starts = sorted(by_name.values(), key=lambda r: r["start_ns"])
    assert [r["name"] for r in starts] == ["a", "b", "c"]


def test_begin_finish_cross_frame_span_with_extra_attrs():
    tracing.configure(enabled=True)
    sp = tracing.begin("t", "step", step="Prevote")
    tracing.event("t", "mid")
    tracing.finish(sp, verdict="ok")
    span = [r for r in tracing.dump() if r["kind"] == "span"][0]
    assert span["attrs"] == {"step": "Prevote", "verdict": "ok"}
    # finish(None) is the disabled-mode contract
    tracing.finish(None)
    tracing.finish(None, extra=1)


def test_ring_bounded_memory_and_resize():
    tracing.configure(enabled=True, ring_size=64)
    for i in range(1000):
        tracing.event("t", "e", i=i)
    recs = tracing.dump()
    assert len(recs) == 64
    # newest survive, oldest fell off the back
    assert [r["attrs"]["i"] for r in recs] == list(range(936, 1000))
    assert tracing.stats()["buffered"] == 64
    # dump(limit) trims from the newest end
    assert [r["attrs"]["i"] for r in tracing.dump(5)] \
        == list(range(995, 1000))
    # shrinking keeps the newest records
    tracing.configure(ring_size=16)
    assert len(tracing.dump()) == 16


def test_attrs_sanitized_for_json():
    import json

    tracing.configure(enabled=True)
    tracing.event("t", "e", raw=b"\x01\x02", obj=object(), s="x", n=1.5)
    rec = tracing.dump()[0]
    json.dumps(rec)                      # must not raise
    assert rec["attrs"]["raw"] == "0102"
    assert rec["attrs"]["s"] == "x" and rec["attrs"]["n"] == 1.5


# ------------------------------------------------------ concurrency


def test_concurrent_writers_threads_and_asyncio_no_lost_or_torn():
    """8 threads + 8 asyncio tasks hammer the ring concurrently; with
    capacity >= total writes nothing may be lost, every record must be
    intact (id unique, attrs consistent with the writer that built it),
    and memory stays bounded by the ring."""
    per = 250
    n_threads = 8
    n_tasks = 8
    total = per * (n_threads + n_tasks)
    tracing.configure(enabled=True, ring_size=total + 100)

    def thread_writer(wid):
        for i in range(per):
            tracing.event("thr", "w", wid=wid, i=i, tag=wid * 1_000_000 + i)

    async def task_writer(wid):
        for i in range(per):
            tracing.event("aio", "w", wid=wid, i=i, tag=wid * 1_000_000 + i)
            if i % 50 == 0:
                await asyncio.sleep(0)

    async def main():
        threads = [threading.Thread(target=thread_writer, args=(w,))
                   for w in range(n_threads)]
        for t in threads:
            t.start()
        await asyncio.gather(*(task_writer(w) for w in range(n_tasks)))
        for t in threads:
            t.join()

    run(main())
    recs = tracing.dump(total + 100)
    assert len(recs) == total                       # nothing lost
    assert len({r["id"] for r in recs}) == total    # nothing duplicated
    for r in recs:                                  # nothing torn
        a = r["attrs"]
        assert a["tag"] == a["wid"] * 1_000_000 + a["i"], r
    # each writer's own events are in its program order
    for sub, wid in [("thr", 0), ("aio", 0), ("thr", 7), ("aio", 7)]:
        seq = [r["attrs"]["i"] for r in recs
               if r["sub"] == sub and r["attrs"]["wid"] == wid]
        assert seq == list(range(per))


# -------------------------------------------------------- disabled mode


def test_disabled_mode_is_noop_and_allocation_free():
    assert not tracing.is_enabled()
    # span() hands back one shared no-op object: no per-call allocation
    s1 = tracing.span("a", "b")
    s2 = tracing.span("a", "b", k=1)
    assert s1 is s2
    with s1:
        tracing.event("a", "b", x=1)
    assert tracing.begin("a", "b") is None
    assert tracing.dump() == []

    # steady-state allocation check: after warmup, a disabled
    # event/span cycle leaves the interpreter's allocated-block count
    # unchanged (everything it touches is freed before returning)
    def cycle():
        tracing.event("sub", "name", a=1, b="x")
        with tracing.span("sub", "name"):
            pass

    for _ in range(256):
        cycle()
    before = sys.getallocatedblocks()
    for _ in range(4096):
        cycle()
    after = sys.getallocatedblocks()
    assert after - before <= 8, f"disabled tracing leaked {after - before}"
    assert tracing.dump() == []


def test_off_path_of_a_seam_span_site_allocates_nothing():
    """A span site as ``crypto/batch`` writes them: attrs as keywords,
    the open span taken for an attr only known at the end."""
    import jax  # noqa: F401  (the session check resolves once jax is in)

    assert not tracing.is_enabled()

    def site(lanes, bucket):
        with tracing.span("crypto.seam", "pack", lanes=lanes,
                          bucket=bucket) as sp:
            if sp is not None:
                sp.attrs["blocks"] = 2
        tracing.finish(tracing.begin("crypto.seam", "queue", patient=False))

    for _ in range(256):
        site(101, 256)
    before = sys.getallocatedblocks()
    for _ in range(4096):
        site(101, 256)
    assert sys.getallocatedblocks() - before <= 8
    assert tracing.snapshot() == []


# ------------------------------------- following a live profiler session


def _profiler_session():
    import jax
    from jax._src.lib import _profiler

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return _profiler.ProfilerSession(opts)


def test_recorder_follows_a_live_profiler_session():
    import jax

    assert not tracing.is_enabled() and tracing.begin("t", "x") is None
    session = _profiler_session()
    try:
        assert tracing.is_enabled() and tracing.stats()["recording"]
        assert not tracing.stats()["enabled"]       # the configuration
        with tracing.span("crypto.seam", "pack", lanes=3):
            tracing.event("t", "inside")
    finally:
        xspace = session.stop()
    assert not tracing.is_enabled()
    with tracing.span("crypto.seam", "pack"):       # off again: not kept
        pass
    recs = tracing.dump()
    assert [(r["sub"], r["name"]) for r in recs] == [
        ("t", "inside"), ("crypto.seam", "pack")]
    assert recs[0]["parent"] == recs[1]["id"]
    # the span also wrote itself into the profile, as <sub>:<name>
    names = {e.name for p in
             jax.profiler.ProfileData.from_serialized_xspace(xspace).planes
             for line in p.lines for e in line.events}
    assert "crypto.seam:pack" in names
    assert not any(n.startswith("bench:") for n in names)


# ------------------------------------------- spans of the backend seam


def test_owner_thread_spans_name_the_callers_span_as_parent():
    from cometbft_tpu.crypto import batch as B

    tracing.configure(enabled=True)

    def on_owner():
        tracing.event("t", "on_owner")
        with tracing.span("t", "owner_span"):
            pass
        return threading.current_thread().name

    with tracing.span("t", "caller") as caller:
        assert B._device_call(on_owner).startswith("tpu-verify")
    by_name = {r["name"]: r for r in tracing.dump()}
    for name in ("queue", "on_owner", "owner_span"):
        assert by_name[name]["parent"] == caller.id, name
    assert by_name["queue"]["attrs"] == {"patient": False, "abandoned": False}
    assert by_name["queue"]["end_ns"] <= by_name["on_owner"]["start_ns"]
    # the caller's context is back to what it was
    tracing.event("t", "after")
    assert tracing.dump()[-1]["parent"] == 0


def _children(recs, parent):
    return sorted((r for r in recs if r["parent"] == parent["id"]),
                  key=lambda r: r["start_ns"])


def test_verify_dense_spans_at_the_16_lane_bucket(monkeypatch):
    """One commit through the ``jax`` backend: the entry, ``rows`` and
    ``verify_dense`` with exactly the seam's spans beneath it, each with
    its counts; a refuted batch verdict adds a ``gather`` launch."""
    import numpy as np

    from cometbft_tpu.crypto import batch as B
    from cometbft_tpu.crypto import plan as P
    from cometbft_tpu.crypto import rlc_finish
    from cometbft_tpu.testing import make_light_chain
    from cometbft_tpu.types import validation as V

    lb = make_light_chain(1, n_vals=4)[0]

    def light():
        V.VerifyCommitLight("light-chain", lb.validators, lb.commit.block_id,
                            lb.height, lb.commit, backend="jax",
                            use_cache=False)

    light()                     # the table of this valset is built here
    assert tracing.snapshot() == []         # ... with the recorder off
    tracing.configure(enabled=True)
    light()
    recs = tracing.dump()
    (entry,) = [r for r in recs if r["name"] == "verify"]
    assert entry["sub"] == "types.validation" and entry["attrs"] == {
        "entry": "VerifyCommitLight", "commits": 1, "lanes": 4, "ok": True,
        "height": lb.height}
    rows, dense = _children(recs, entry)
    assert (rows["name"], rows["attrs"]) == ("rows", {"commits": 1, "lanes": 3})
    assert (dense["sub"], dense["name"]) == ("crypto.seam", "verify_dense")
    assert dense["attrs"] == {"lanes": 3, "patient": False, "route": "device"}
    seam = _children(recs, dense)
    assert [r["name"] for r in seam] == ["queue", "tables", "pack", "launch",
                                         "readback"]
    assert all(r["sub"] == "crypto.seam" for r in seam)
    attrs = {r["name"]: r["attrs"] for r in seam}
    assert attrs["tables"] == {"hit": True, "rows": 4}
    assert attrs["pack"] == {"lanes": 3, "bucket": 16, "blocks": 2,
                             "ahead": False}
    assert attrs["launch"] == {"kind": "gather", "lanes": 3, "bucket": 16}
    assert attrs["readback"] == {"kind": "gather", "ok": True}
    assert len(recs) == 8                   # nothing else was recorded
    assert tracing.dump(height=lb.height) == [entry]

    # a placement to a pinned chip is a ``put`` span with its bytes (the
    # unpinned route above hands numpy arrays to the launch itself)
    import jax

    tracing.clear()
    B._put((np.zeros((16, 32), np.int32), np.zeros((16,), np.int32)),
           jax.devices()[0])
    (put,) = tracing.dump()
    assert (put["name"], put["attrs"]) == ("put", {"bytes": 16 * 33 * 4})

    # the batch verdict refuted: the per-lane kernel localizes, and the
    # entry's span closes not ok when the commit is refused
    tracing.clear()
    monkeypatch.setattr(B, "_compiled_rlc_gather",
                        lambda: lambda *a: rlc_finish.verdict(False))
    P.configure(rlc_min_lanes=1)
    bad = make_light_chain(1, n_vals=4)[0].commit
    bad.signatures[1].signature = bytes(64)
    with pytest.raises(V.ErrInvalidSignature):
        V.VerifyCommit("light-chain", lb.validators, bad.block_id, lb.height,
                       bad, backend="jax")
    recs = tracing.dump()
    assert [(r["attrs"]["kind"], r["attrs"]["lanes"], r["attrs"]["bucket"])
            for r in recs if r["name"] == "launch"] == [
        ("rlc_gather", 4, 16), ("gather", 4, 16)]
    assert [r["attrs"]["ok"] for r in recs if r["name"] == "readback"] \
        == [False, False]
    (fold,) = [r for r in recs if r["name"] == "finish"]     # the RLC sums
    assert fold["attrs"]["ok"] is False and fold["parent"] == next(
        r["id"] for r in recs if r["name"] == "readback"
        and r["attrs"]["kind"] == "rlc_gather")
    (entry,) = [r for r in recs if r["name"] == "verify"]
    assert entry["attrs"]["entry"] == "VerifyCommit" \
        and entry["attrs"]["ok"] is False


# ------------------------------------------------------- RPC round-trip


def _single_node_cfg():
    from cometbft_tpu.config import Config
    from cometbft_tpu.config import test_consensus_config as _tcc

    cfg = Config(consensus=_tcc())
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.instrumentation.tracing = True
    cfg.instrumentation.tracing_ring_size = 4096
    return cfg


def test_dump_trace_rpc_roundtrip_and_enriched_status():
    """A tracing-enabled single validator serves its flight recorder via
    GET /dump_trace and the timeline block via /status."""
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.node import Node
    from cometbft_tpu.rpc import HTTPClient
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
    from cometbft_tpu.types.priv_validator import MockPV

    async def main():
        pv = MockPV.from_secret(b"trace-rpc")
        doc = GenesisDoc(chain_id="trace-net",
                         validators=[GenesisValidator(pv.get_pub_key(), 10)])
        node = await Node.create(doc, KVStoreApplication(),
                                 priv_validator=pv,
                                 config=_single_node_cfg(), name="tr0")
        await node.start()
        try:
            for _ in range(600):
                if node.block_store.height() >= 1:
                    break
                await asyncio.sleep(0.05)
            assert node.block_store.height() >= 1
            cli = HTTPClient(*node.rpc_addr)
            out = await cli.call("dump_trace", limit=2000)
            assert out["enabled"] is True
            assert out["ring_size"] == 4096
            recs = out["records"]
            assert recs and len(recs) <= 2000
            steps = [r for r in recs if r["sub"] == "consensus"
                     and r["name"] == "step"]
            assert steps, "no consensus step spans in the dump"
            names = {r["attrs"]["step"] for r in steps}
            assert {"Propose", "Prevote", "Precommit"} <= names
            commits = [r for r in recs if r["sub"] == "consensus"
                       and r["name"] == "commit"]
            assert commits and commits[0]["attrs"]["height"] >= 1
            # the app calls rode the traced consensus connection
            assert any(r["sub"] == "abci" and
                       r["attrs"].get("method") == "finalize_block"
                       for r in recs)
            # bad limit is a clean RPC error
            from cometbft_tpu.rpc import RPCError

            with pytest.raises(RPCError):
                await cli.call("dump_trace", limit=-1)

            st = await cli.call("status")
            ci = st["consensus_info"]
            assert ci["height"] >= 1 and ci["round"] >= 0
            assert ci["step"] in ("NewHeight", "NewRound", "Propose",
                                  "Prevote", "PrevoteWait", "Precommit",
                                  "PrecommitWait", "Commit")
            assert ci["step_age_s"] >= 0
            assert ci["fatal_error"] is None
            await cli.close()
        finally:
            await node.stop()
        return True

    assert run(main())


# -------------------------------------------------- tentpole acceptance


def test_height_timeline_contains_scheduler_microbatches():
    """Acceptance: with tracing on, one committed height's trace shows
    its consensus step spans AND the verify micro-batch dispatches the
    vote scheduler ran inside them (time containment in the height's
    [first step start, last step end] window)."""
    from cometbft_tpu.crypto import scheduler as vsched
    from cometbft_tpu.testing import make_inproc_network

    async def main():
        tracing.configure(enabled=True, ring_size=16384)
        sched = await vsched.acquire_scheduler(backend="cpu",
                                               max_wait_ms=1.0)
        net = await make_inproc_network(4)
        # the ensemble shares ONE process-wide verified-sig cache, and
        # in-proc gossip is synchronous: a signer's own-vote verification
        # seeds the cache in the same event-loop slice that delivers the
        # vote to every peer, so prefetches always hit and the dispatch
        # path never runs.  Production hosts each hold their own cold
        # cache — emulate that by forcing lookups to miss (seeding and
        # in-flight dedup stay live), which routes gossip through the
        # micro-batch dispatches this test is about.
        sched.cache.hit = lambda key: False
        try:
            await net.start()
            await net.wait_for_height(2, timeout=60)
        finally:
            await net.stop()
            await vsched.release_scheduler()
        assert sched.stats()["batches"] > 0, \
            "scheduler never dispatched a micro-batch"
        return tracing.dump(16384)

    recs = run(main())
    steps = [r for r in recs
             if r["sub"] == "consensus" and r["name"] == "step"]
    dispatches = [r for r in recs
                  if r["sub"] == "crypto.sched" and r["name"] == "dispatch"]
    flushes = [r for r in recs
               if r["sub"] == "crypto.sched" and r["name"] == "flush"]
    assert steps and dispatches and flushes
    # pick a committed height and build its wall-clock window from its
    # step spans; at least one micro-batch dispatch must sit inside it
    heights = sorted({r["attrs"]["height"] for r in steps
                      if r["attrs"]["step"] == "Commit"})
    assert heights, "no height reached Commit in the trace"
    found = None
    for h in heights:
        hs = [r for r in steps if r["attrs"]["height"] == h]
        t_lo = min(r["start_ns"] for r in hs)
        t_hi = max(r["end_ns"] for r in hs)
        inside = [d for d in dispatches
                  if t_lo <= d["start_ns"] and d["end_ns"] <= t_hi]
        # the height shows the nested propose->prevote->precommit
        # progression, not just a single step
        step_names = {r["attrs"]["step"] for r in hs}
        if inside and {"Propose", "Prevote", "Precommit"} <= step_names:
            found = (h, len(inside))
            break
    assert found, "no committed height contains a scheduler dispatch"
