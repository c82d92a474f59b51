"""The reducers that read the program's own spans: the alignment of the
flight recorder's clock with the trace's, and the three readers, on a
synthetic trace and a synthetic ring; then rehearsals on the CPU, traced (the
span metrics that need no device plane are reported) and untraced (the ring
stays empty), whose failure message names the check that tripped."""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import loop, run  # noqa: E402
from benchmarks.reduce import (device_idle, dispatch_occupancy,  # noqa: E402
                               idle_in_spans, program_spans, seam_host, span_ms,
                               xplane)
from cometbft_tpu.libs import tracing  # noqa: E402

ONE_CELL_PER_DRIVER = ["vals150.light_commit", "vals1000.sync_window"]
CLOCKS_APART_S = 1000.0         # the calls' clock reads this much more
SEAM_NAMES = ["tables", "pack", "put", "launch", "readback"]
# (id, parent, sub, name, start, end, attrs) in the trace's seconds: call A's
# batch verdict is refuted and a gather dispatch follows, call B's is not
RING = [
    (1, 0, "types.validation", "verify", 1.0, 5.0, {"entry": "VerifyCommit"}),
    (2, 1, "types.validation", "rows", 1.0, 1.4, {"lanes": 100}),
    (3, 1, "crypto.seam", "verify_dense", 1.5, 4.9, {"lanes": 100}),
    (4, 3, "crypto.seam", "queue", 1.5, 1.6, {}),
    (5, 3, "crypto.seam", "tables", 1.6, 1.7, {"hit": True}),
    (6, 3, "crypto.seam", "pack", 1.7, 2.0, {"lanes": 100, "bucket": 256}),
    (7, 3, "crypto.seam", "launch", 2.0, 2.2,
     {"kind": "rlc_gather", "lanes": 100, "bucket": 256}),
    (8, 3, "crypto.seam", "readback", 2.2, 3.6, {"ok": False}),
    (9, 3, "crypto.seam", "launch", 3.6, 3.7,
     {"kind": "gather", "lanes": 100, "bucket": 256}),
    (10, 3, "crypto.seam", "readback", 3.7, 4.8, {"ok": False}),
    (11, 0, "types.validation", "verify", 6.0, 9.0, {"entry": "VerifyCommit"}),
    (12, 11, "types.validation", "rows", 6.0, 6.5, {"lanes": 50}),
    (13, 11, "crypto.seam", "verify_dense", 6.5, 8.9, {"lanes": 50}),
    (14, 13, "crypto.seam", "queue", 6.5, 6.6, {}),
    (15, 13, "crypto.seam", "pack", 6.6, 7.0, {"lanes": 50, "bucket": 64}),
    (16, 13, "crypto.seam", "launch", 7.0, 7.1,
     {"kind": "gather", "lanes": 50, "bucket": 64}),
    (17, 13, "crypto.seam", "readback", 7.1, 8.8, {"ok": True}),
    # a third call, begun in the window and cut off by its end
    (18, 0, "types.validation", "verify", 9.2, 10.4, {"entry": "VerifyCommit"}),
    (19, 18, "types.validation", "rows", 9.2, 9.6, {"lanes": 50}),
]


def ring_records(shift_s: float = CLOCKS_APART_S) -> list:
    """``RING`` as ``tracing.snapshot()`` gives it: stamps in nanoseconds of
    the calls' clock, and one event, which is no span."""
    def ns(t):
        return round((t + shift_s) * 1e9)
    return [("event", 99, 0, "crypto.kernel", "first_dispatch", 0, ns(2.0),
             ns(2.0), {})] + [
        ("span", rid, parent, sub, name, 0, ns(t0), ns(t1), attrs)
        for rid, parent, sub, name, t0, t1, attrs in RING]


def traced_window(entries=((1.0, 5.0), (6.0, 9.0))) -> xplane.Trace:
    chip = "/device:TPU:0"
    spans = []
    for s, e in entries:
        spans += [("t", "bench:entry", s, e - s),
                  ("t", "bench:verify_dense", s + 0.5, e - s - 0.6)]
    return xplane.Trace(
        window=(0.0, 10.0), spans=spans,
        busy={chip: xplane.Intervals([2.1, 3.65, 7.05], [3.5, 4.6, 8.5])})


def calls_of(entries, before=2e-6, after=1e-6) -> list:
    """The window's calls on their own clock: each encloses its entry span."""
    return [loop.Call(i, s + CLOCKS_APART_S - before, e + CLOCKS_APART_S + after,
                      ("ok", 1)) for i, (s, e) in enumerate(entries)]


@pytest.fixture
def ring(monkeypatch):
    monkeypatch.setattr(tracing, "snapshot", ring_records)


def context(entries=((1.0, 5.0), (6.0, 9.0)), calls=None) -> dict:
    return {"trace": traced_window(entries),
            "calls": calls_of(entries) if calls is None else calls}


def test_the_idle_shares_partition_the_idle_share(ring):
    ctx = context()
    spans = program_spans.read(ctx)
    assert spans.residual_s == pytest.approx(3e-6, abs=1e-7)
    assert len(spans.all) == len(RING) and spans.window == (0.0, 10.0)
    assert spans.all[5].start == pytest.approx(1.7, abs=1e-5)
    shares = {
        "pack": idle_in_spans.reduce(ctx, names=["tables", "pack"]),
        "launch": idle_in_spans.reduce(ctx, names=["put", "launch"]),
        "readback": idle_in_spans.reduce(ctx, names=["readback"]),
        "outside": idle_in_spans.reduce(ctx, names=SEAM_NAMES, outside=True)}
    assert shares == pytest.approx(
        {"pack": 8.0, "launch": 2.0, "readback": 6.0, "outside": 46.0}, abs=1e-3)
    assert sum(shares.values()) == pytest.approx(device_idle.reduce(ctx))
    # a call: those that lie whole in the window (the third does not)
    assert span_ms.reduce(ctx, sub="crypto.seam", name="queue") \
        == pytest.approx(100.0, abs=1e-2)
    assert span_ms.reduce(ctx, sub="types.validation", name="rows") \
        == pytest.approx(450.0, abs=1e-2)
    assert dispatch_occupancy.reduce(ctx) == pytest.approx(100 * 250 / 576)
    # the program's own verify_dense spans give the harness's seam_host_ms
    busy = ctx["trace"].busy["/device:TPU:0"]
    own = sum((s.end - s.start) - busy.covered(s.start, s.end)
              for s in spans.whole("crypto.seam", "verify_dense")) / 2
    assert 1e3 * own == pytest.approx(seam_host.reduce(ctx), abs=1e-2)


def test_a_dropped_entry_still_aligns(ring):
    """``clip_to_record`` kept two of the three entries: the calls are matched
    in the order they ended, and the numbers are those of the whole window."""
    entries = ((1.0, 5.0), (6.0, 9.0), (9.2, 10.4))
    ctx = context(entries[:2], calls_of(entries))
    assert program_spans.read(ctx).residual_s == pytest.approx(3e-6, abs=1e-7)
    assert span_ms.reduce(ctx, sub="crypto.seam", name="queue") \
        == pytest.approx(100.0, abs=1e-2)
    # two callers: the second call began first and ended last
    entries = ((1.0, 9.0), (1.5, 5.0))
    fit = program_spans.clock_offset(list(entries), calls_of(entries))
    assert fit == (pytest.approx(CLOCKS_APART_S - 0.5e-6), pytest.approx(3e-6))


def test_clocks_that_do_not_meet_give_nothing(ring, monkeypatch):
    entries = ((1.0, 5.0), (6.0, 9.0))
    late = calls_of(entries)
    late[1].end -= 1e-3                 # an entry that outlasts its call
    slack = calls_of(entries, before=40e-6, after=40e-6)    # pinned too loosely
    for calls in (late, slack, calls_of(entries[:1]), []):
        ctx = context(entries, calls)
        assert program_spans.read(ctx) is None
        assert idle_in_spans.reduce(ctx, names=SEAM_NAMES) is None
        assert span_ms.reduce(ctx, sub="crypto.seam", name="queue") is None
        assert dispatch_occupancy.reduce(ctx) is None
    monkeypatch.setattr(program_spans, "same_clock", lambda: False)
    assert program_spans.read(context()) is None


def test_a_program_without_the_spans_gives_nothing(monkeypatch):
    """The parent of the PR that added the spans: an empty ring, or one that
    holds other subsystems' records only."""
    for records in ([], ring_records()[:1],
                    [r for r in ring_records() if r[3] == "types.validation"]):
        monkeypatch.setattr(tracing, "snapshot", lambda: records)
        ctx = context()
        assert idle_in_spans.reduce(ctx, names=SEAM_NAMES, outside=True) is None
        assert span_ms.reduce(ctx, sub="crypto.seam", name="queue") is None
        assert dispatch_occupancy.reduce(ctx) is None
    # no device plane (a rehearsal on the CPU): no idle share to divide
    monkeypatch.setattr(tracing, "snapshot", ring_records)
    ctx = context()
    ctx["trace"].busy = {}
    assert idle_in_spans.reduce(ctx, names=["readback"]) is None
    assert dispatch_occupancy.reduce(ctx) == pytest.approx(100 * 250 / 576)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", ONE_CELL_PER_DRIVER)
def test_rehearsal_names_the_check_that_trips(capsys, cell, trace):
    """Traced, the recorder follows the profiler session and the span metrics
    that need no device plane are reported; untraced, it records nothing."""
    tracing.clear()
    capsys.readouterr()
    assert run.main(["--workload", cell, "--seed", "4100000011", "--seconds",
                     "0.5", "--trace", str(trace), "--rehearse"]) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    tripped = {k: c["value"] for k, c in res["checks"].items() if c["value"]}
    assert res["correct"] is True and not tripped, \
        f"checks that tripped: {tripped}; all of them: {res['checks']}"
    assert res["failed"] == 0 < res["attempted"], res["checks"]
    assert not tracing.is_enabled()
    names = {m["name"] for m in run.load_cell(cell)["per_layer"]}
    if not trace:
        assert tracing.snapshot() == []
        assert not names & set(res["metrics"])
        return
    recorded = {(r[3], r[4]) for r in tracing.snapshot()}
    assert {("types.validation", "verify"), ("types.validation", "rows"),
            ("crypto.seam", "verify_dense"), ("crypto.seam", "queue"),
            ("crypto.seam", "launch")} <= recorded
    for stem in ("seam_queue_ms", "validation_rows_ms", "dispatch_occupancy_pct"):
        (name,) = [n for n in names if n.startswith(stem + ".")]
        assert res["metrics"][name]["value"] > 0, (res["metrics"], res["checks"])
    assert not [n for n in res["metrics"] if n.startswith("idle_")]
    tracing.clear()
