"""Height-timeline attribution (``libs/timeline``): folding the flight
recorder into per-height commit-latency waterfalls — phase ordering,
exact bucket decomposition, multi-round and aggregate-catch-up edge
cases, eviction tolerance, interleaved heights — plus the emitter attr
contract (every consensus record stamps node+height, steps stamp round)
checked against a live in-proc ensemble, and the /consensus_timeline
projection."""

import asyncio

import pytest

from cometbft_tpu.libs import timeline, tracing

pytestmark = pytest.mark.timeout(120)

S = 1_000_000_000          # 1 virtual second, in ns
WALL = 1_800_000_000 * S   # arbitrary wall epoch for synthetic rings


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


@pytest.fixture(autouse=True)
def _tracer_hygiene():
    tracing.configure(enabled=False, ring_size=8192)
    tracing.clear()
    yield
    tracing.configure(enabled=False, ring_size=8192)
    tracing.clear()


# ------------------------------------------------- synthetic ring records

_ids = iter(range(1, 1 << 20))


def ev(sub, name, t, **attrs):
    return ("event", next(_ids), 0, sub, name, WALL + t, t, t, attrs)


def sp(sub, name, t0, t1, **attrs):
    return ("span", next(_ids), 0, sub, name, WALL + t0, t0, t1, attrs)


def height_records(node="n0", h=5, t0=0, round_=0):
    """A complete, well-formed height: NewHeight at t0, proposal at
    +1s, parts at +2s, +2/3 prevotes at +3s, +2/3 precommits at +4s,
    commit at +5s."""
    a = dict(node=node, height=h)
    return [
        sp("consensus", "step", t0, t0 + 1 * S,
           step="NewHeight", round=round_, **a),
        ev("consensus", "proposal_received", t0 + 1 * S, round=round_, **a),
        sp("consensus", "step", t0 + 1 * S, t0 + 3 * S,
           step="Propose", round=round_, **a),
        ev("consensus", "block_assembled", t0 + 2 * S, **a),
        sp("consensus", "step", t0 + 3 * S, t0 + 4 * S,
           step="Precommit", round=round_, **a),
        sp("consensus", "step", t0 + 4 * S, t0 + 5 * S,
           step="Commit", round=round_, **a),
        ev("consensus", "commit", t0 + 5 * S, round=round_, **a),
    ]


# ---------------------------------------------------------- basic folding


def test_basic_waterfall_phases_ordered_and_buckets_sum_to_total():
    wfs = timeline.fold(height_records())
    assert len(wfs) == 1
    wf = wfs[0]
    assert wf["node"] == "n0" and wf["height"] == 5
    assert wf["complete"] and not wf["catchup"]
    assert wf["total_s"] == 5.0
    # all five phases present, in classification order, contiguous
    assert [p["phase"] for p in wf["phases"]] == list(timeline.PHASES)
    cursor = 0.0
    for p in wf["phases"]:
        assert p["start_s"] == cursor
        cursor += p["dur_s"]
    assert cursor == wf["total_s"]
    # marks are height-relative seconds
    assert wf["marks"]["proposal_received"] == 1.0
    assert wf["marks"]["parts_complete"] == 2.0
    assert wf["marks"]["prevote_23"] == 3.0
    assert wf["marks"]["precommit_23"] == 4.0
    assert wf["marks"]["commit"] == 5.0
    # buckets decompose the same total exactly
    assert sum(wf["buckets"].values()) == pytest.approx(wf["total_s"])
    assert set(wf["buckets"]) == set(timeline.BUCKETS)


def test_abci_wal_dispatch_buckets_clip_into_budget():
    recs = height_records()
    # 0.5s of app time inside the height, node-attributed
    recs.append(sp("abci", "call", 4 * S, int(4.5 * S),
                   method="finalize_block", height=5, node="n0"))
    # a wal fsync joined on height only
    recs.append(ev("wal", "fsync", int(4.6 * S), height=5,
                   dur_us=100_000))
    # a verify micro-batch whose window overlaps heights 4..6, plus a
    # BLS aggregate pairing check stamped with this height exactly
    recs.append(sp("crypto.sched", "dispatch", 3 * S, int(3.25 * S),
                   h_lo=4, h_hi=6, n=64))
    recs.append(sp("crypto.agg", "verify", int(3.5 * S), int(3.6 * S),
                   height=5, lanes=7, ok=True))
    wf = timeline.fold(recs)[0]
    assert wf["buckets"]["app"] == pytest.approx(0.5)
    assert wf["buckets"]["wal"] == pytest.approx(0.1)
    assert wf["buckets"]["verify"] == pytest.approx(0.35)
    assert wf["marks"]["finalize"] == pytest.approx(4.5)
    assert wf["marks"]["fsync"] == pytest.approx(4.6)
    assert sum(wf["buckets"].values()) == pytest.approx(wf["total_s"])


def test_oversized_bucket_values_never_exceed_total():
    recs = height_records()
    # an absurd fsync duration (clock glitch / bad attr) must clip
    recs.append(ev("wal", "fsync", int(4.5 * S), height=5,
                   dur_us=3_600_000_000))
    wf = timeline.fold(recs)[0]
    assert sum(wf["buckets"].values()) == pytest.approx(wf["total_s"])
    assert wf["buckets"]["wal"] <= wf["total_s"]


# ------------------------------------------------------------- edge cases


def test_multi_round_height_uses_commit_round_marks():
    """A height that failed round 0 and committed in round 1: the vote-
    phase marks must come from the committing round's step entries, not
    the stale round-0 ones."""
    a = dict(node="n0", height=9)
    recs = [
        sp("consensus", "step", 0, 1 * S, step="NewHeight", round=0, **a),
        ev("consensus", "proposal_received", 1 * S, round=0, **a),
        ev("consensus", "block_assembled", 2 * S, **a),
        sp("consensus", "step", 3 * S, 4 * S, step="Precommit",
           round=0, **a),
        # round 0 dies; round 1 runs the ladder again
        sp("consensus", "step", 5 * S, 6 * S, step="NewRound",
           round=1, **a),
        sp("consensus", "step", 7 * S, 8 * S, step="Precommit",
           round=1, **a),
        sp("consensus", "step", 8 * S, 9 * S, step="Commit",
           round=1, **a),
        ev("consensus", "commit", 9 * S, round=1, **a),
    ]
    wf = timeline.fold(recs)[0]
    assert wf["rounds"] == 1 and wf["complete"]
    assert wf["marks"]["prevote_23"] == 7.0     # round 1's, not 3.0
    assert wf["marks"]["precommit_23"] == 8.0
    assert wf["total_s"] == 9.0
    cursor = 0.0
    for p in wf["phases"]:
        assert p["start_s"] == cursor
        cursor += p["dur_s"]
    assert cursor == wf["total_s"]


def test_catchup_commit_skips_vote_phases():
    """An aggregate/blocksync catch-up commit never enters Prevote or
    Precommit: the waterfall folds with the vote marks absent rather
    than inventing zero-length phases from stale data."""
    a = dict(node="n3", height=12)
    recs = [
        sp("consensus", "step", 0, 1 * S, step="NewHeight", round=0, **a),
        ev("consensus", "proposal_received", 1 * S, round=0, **a),
        ev("consensus", "block_assembled", 2 * S, **a),
        ev("consensus", "commit", 3 * S, round=0, catchup=True, **a),
    ]
    wf = timeline.fold(recs)[0]
    assert wf["catchup"] and wf["complete"]
    assert [p["phase"] for p in wf["phases"]] == \
        ["propose", "gossip", "prevote"]
    assert wf["marks"]["prevote_23"] is None
    assert wf["marks"]["precommit_23"] is None
    assert wf["total_s"] == 3.0
    assert sum(wf["buckets"].values()) == pytest.approx(3.0)


def test_evicted_prefix_and_incomplete_heights_degrade_gracefully():
    # eviction took the NewHeight step and the proposal event: the
    # height anchors at its earliest surviving record
    a = dict(node="n0", height=7)
    partial = [
        sp("consensus", "step", 10 * S, 11 * S, step="Precommit",
           round=0, **a),
        sp("consensus", "step", 11 * S, 12 * S, step="Commit",
           round=0, **a),
        ev("consensus", "commit", 12 * S, round=0, **a),
    ]
    wf = timeline.fold(partial)[0]
    assert wf["complete"] and wf["total_s"] == 2.0
    assert wf["marks"]["proposal_received"] is None
    assert [p["phase"] for p in wf["phases"]] == \
        ["propose", "precommit", "commit"]
    # a height still in flight (no commit yet) is not "complete" and
    # measures up to its last record
    b = dict(node="n0", height=8)
    inflight = [
        sp("consensus", "step", 20 * S, 21 * S, step="NewHeight",
           round=0, **b),
        ev("consensus", "proposal_received", 21 * S, round=0, **b),
    ]
    wf2 = timeline.fold(inflight)[0]
    assert not wf2["complete"]
    assert wf2["total_s"] == 1.0


def test_interleaved_heights_and_nodes_fold_independently():
    recs = []
    # two nodes x two heights, records interleaved as a shared ring
    # would hold them
    quads = [height_records("a", 5, 0), height_records("b", 5, S // 2),
             height_records("a", 6, 6 * S), height_records("b", 6, 7 * S)]
    for i in range(max(len(q) for q in quads)):
        for q in quads:
            if i < len(q):
                recs.append(q[i])
    wfs = timeline.fold(recs)
    assert [(w["node"], w["height"]) for w in wfs] == \
        [("a", 5), ("b", 5), ("a", 6), ("b", 6)]
    assert all(w["complete"] and w["total_s"] == 5.0 for w in wfs)
    # node/height filters and the per-node limit
    assert [(w["node"], w["height"])
            for w in timeline.fold(recs, node="a")] == [("a", 5), ("a", 6)]
    assert [(w["node"], w["height"])
            for w in timeline.fold(recs, height=6)] == [("a", 6), ("b", 6)]
    newest = timeline.fold(recs, limit=1)
    assert [(w["node"], w["height"]) for w in newest] == \
        [("a", 6), ("b", 6)]


def test_attr_contract_violations_are_skipped_not_crashed():
    recs = height_records()
    recs.append(ev("consensus", "commit", 99 * S, height=77))   # no node
    recs.append(ev("consensus", "commit", 99 * S, node="x"))    # no height
    recs.append(sp("abci", "call", 0, S, method="echo"))        # no height
    recs.append(sp("crypto.sched", "dispatch", 0, S, h_lo=0, h_hi=0))
    wfs = timeline.fold(recs)
    assert [(w["node"], w["height"]) for w in wfs] == [("n0", 5)]


# ----------------------------------------------------------- phase stats


def test_phase_stats_percentiles_deterministic_and_skip_incomplete():
    recs = []
    for i in range(10):
        recs += height_records("n0", 10 + i, i * 10 * S)
    # one in-flight height must not contribute samples
    recs.append(sp("consensus", "step", 200 * S, 201 * S, step="NewHeight",
                   round=0, node="n0", height=99))
    st = timeline.phase_stats(timeline.fold(recs, limit=0))
    assert st["samples"] == 10
    assert st["phases"]["total"] == {"n": 10, "p50_s": 5.0, "p99_s": 5.0}
    for p in timeline.PHASES:
        assert st["phases"][p]["n"] == 10
        assert st["phases"][p]["p50_s"] == 1.0
    for b in timeline.BUCKETS:
        assert st["buckets"][b]["n"] == 10
    # nearest-rank: p50 of [1..10] is 5, p99 is 10 (no interpolation)
    xs = sorted(float(i) for i in range(1, 11))
    assert timeline._pctl(xs, 0.50) == 5.0
    assert timeline._pctl(xs, 0.99) == 10.0
    assert timeline._pctl([3.0], 0.99) == 3.0
    empty = timeline.phase_stats([])
    assert empty["samples"] == 0
    assert empty["phases"]["total"]["p50_s"] is None


# ----------------------------------- live attr contract + RPC projection


def test_live_ensemble_attr_contract_and_timeline_projection():
    """Every consensus record a real 4-validator ensemble emits carries
    node+height, step spans carry round — the contract fold() keys on —
    and the folded waterfalls + /consensus_timeline projection agree."""
    from cometbft_tpu.testing import make_inproc_network

    async def main():
        tracing.configure(enabled=True, ring_size=32768)
        net = await make_inproc_network(4)
        try:
            await net.start()
            await net.wait_for_height(2, timeout=60)
        finally:
            await net.stop()
        return tracing.snapshot()

    recs = run(main())
    cons = [r for r in recs if r[3] == "consensus"]
    assert cons, "no consensus records emitted"
    for r in cons:
        attrs = r[8]
        assert attrs.get("node") is not None, r
        assert attrs.get("height") is not None, r
        if r[4] == "step":
            assert "round" in attrs and "step" in attrs, r
    wfs = timeline.fold(recs)
    done = [w for w in wfs if w["complete"]]
    # 4 nodes x >=2 heights committed
    assert len(done) >= 8
    for wf in done:
        assert [p["phase"] for p in wf["phases"]] == list(timeline.PHASES)
        assert sum(wf["buckets"].values()) == pytest.approx(wf["total_s"])
        cursor = 0.0
        for p in wf["phases"]:
            # start/dur are rounded to 1us independently: contiguous
            # within accumulated rounding, not bit-exact
            assert p["start_s"] == pytest.approx(cursor, abs=1e-5)
            cursor = p["start_s"] + p["dur_s"]
    st = timeline.phase_stats(wfs)
    assert st["samples"] == len(done)
    assert st["phases"]["total"]["p50_s"] > 0

    # the RPC projection serves the same fold off the event loop
    from cometbft_tpu.rpc import core as rpc_core

    out = run(rpc_core.consensus_timeline(None, height=0, n=4))
    assert out["enabled"] is True
    assert out["phases"] == list(timeline.PHASES)
    assert out["buckets"] == list(timeline.BUCKETS)
    assert out["waterfalls"]
    h2 = run(rpc_core.consensus_timeline(None, height=2))
    assert {w["height"] for w in h2["waterfalls"]} == {2}
