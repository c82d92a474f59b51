"""The benchmark's own tests: the manifest, the arithmetic, the reducers on a
synthetic trace, the plain reference, and rehearsals of each driver on the CPU
at the 16-lane bucket tier-1 compiles anyway (``run.py --rehearse``), with the
control and a planted fault coming out as not correct."""

from __future__ import annotations

import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import data, loop, reference, run  # noqa: E402
from benchmarks.drivers import sync_windows  # noqa: E402
from benchmarks.reduce import (device_idle, lane_occupancy, roofline,  # noqa: E402
                               seam_host, validation_host, xplane)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = run.load_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
ONE_CELL_PER_DRIVER = ["vals150.light_commit", "vals1000.sync_window"]


def rehearse(capsys, cell: str, *extra: str) -> dict:
    capsys.readouterr()
    assert run.main(["--workload", cell, "--seed", "4100000007", "--seconds",
                     "0.5", "--trace", "0", "--rehearse", *extra]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_manifest_resolves_every_file_by_name():
    for cell in CELLS:
        spec = run.load_cell(cell)
        assert spec["config"]["name"] == spec["cell"]["config"]
        assert spec["mix"]["name"] == spec["cell"]["traffic"]
        importlib.import_module(f"benchmarks.drivers.{spec['mix']['driver']}").Driver
        assert {m["name"] for m in spec["end_to_end"]} > {"setup_s"}
        assert spec["per_layer"]
        for m in spec["per_layer"]:
            how = run.load_json(run.HERE, "metrics", m["name"] + ".json")
            assert {k: how[k] for k in m} == m      # one statement, two files
            importlib.import_module(f"benchmarks.reduce.{how['reducer']}").reduce
    for path in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))


def test_names_units_and_lengths_keep_to_the_contract():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names) and len(set(CELLS)) == len(CELLS)
    for entry in metrics + BENCH["workloads"] + BENCH["configs"]:
        assert NAME.match(entry["name"]), entry["name"]
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) == len(CELLS)
    for c in BENCH["configs"]:
        assert 1 <= len(c["source"]) <= 200
        assert all(k in run.load_json(ROOT, c["file"]) for k in c["reduced"])
    assert 1 <= BENCH["run_seconds"] <= 51
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(CELLS) // 2)


def test_every_cell_of_a_layer_metric_reports_what_it_moves():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in moved.get("workloads", CELLS), (m["name"], cell)
    for cell in CELLS:          # set-up, another end-to-end metric, one layer
        spec = run.load_cell(cell)
        assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]


def test_percentile_and_rate_arithmetic():
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0, 8.0]
    for q in (0, 50, 95, 100):
        assert loop.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    with pytest.raises(ValueError):
        loop.percentile([], 50)
    drv = object.__new__(sync_windows.Driver)
    drv.w = 32
    # two callers; the deadline (10 + 3) falls while units 2 and 3 are in
    # flight: the clock stops at unit 2's return, unit 3's lone tail is out
    calls = [loop.Call(0, 10.0, 11.5, ("ok", 1)), loop.Call(1, 10.5, 12.0, ("ok", 1)),
             loop.Call(2, 11.5, 13.25, ("ok", 1)), loop.Call(3, 12.0, 14.5, ("ok", 1))]
    assert drv.end_to_end(calls, 10.0, 3.0) == {
        "sync_blocks_per_s": pytest.approx(3 * 32 / 3.25)}
    assert drv.end_to_end(calls, 10.0, 9.0) == {      # all returned in time
        "sync_blocks_per_s": pytest.approx(4 * 32 / 4.5)}


def test_operations_bytes_and_peaks():
    ops, nbytes = roofline.ops_and_bytes(1000, 116)
    assert ops == 1000 * ((256 + 128) * 8 + 3 * 265) * 32 * 32 * 2 == 7_919_616_000
    assert nbytes == 1000 * (32 + 64 + 116 + 1)
    least, binds = roofline.least_seconds(1000, 116, "TPU v5 lite")
    assert binds == "compute" and least == pytest.approx(7_919_616_000 / 393e12)
    with pytest.raises(KeyError):
        roofline.least_seconds(1000, 116, "TPU v9 imaginary")
    assert lane_occupancy.padded_lanes(
        21_344, 4096, lambda n: 1024 if n <= 1024 else 4096) == 5 * 4096 + 1024


def synthetic_trace() -> xplane.Trace:
    """Two calls in a 10 s window; the chip runs 2 s inside each seam span."""
    chip = "/device:TPU:0"
    return xplane.Trace(
        window=(0.0, 10.0),
        spans=[("t", "bench:window", 0.0, 10.0), ("t", "bench:present", 0.5, 0.5),
               ("t", "bench:entry", 1.0, 4.0), ("t", "bench:verify_dense", 1.5, 3.0),
               ("t", "bench:entry", 6.0, 3.0), ("t", "bench:verify_dense", 6.5, 2.0)],
        modules=[(chip, "jit_verify_padded_gather(1)", 2.0, 2.0),
                 (chip, "jit_verify_batch_rlc_gather(2)", 7.0, 1.0),
                 (chip, "jit_other(3)", 9.5, 0.25)],
        busy={chip: xplane.Intervals([2.0, 3.0, 7.0], [3.5, 4.0, 8.0])},
        op_seconds={"while.1": 2.5, "fusion.2": 0.5})


def test_intervals_and_reducers_on_a_synthetic_trace():
    iv = xplane.Intervals([0, 1, 1.5, 5], [1.2, 1.4, 2, 6])
    assert list(iv.starts) == [0, 1.5, 5] and list(iv.ends) == [1.4, 2, 6]
    assert iv.total() == pytest.approx(2.9)
    assert iv.covered(0.5, 5.5) == pytest.approx(1.9) and iv.covered(2, 5) == 0
    gaps = iv.gaps(1.0, 5.5)
    assert gaps.total() == pytest.approx(0.1 + 3.0) and gaps.covered(0, 1.5) \
        == pytest.approx(0.1)
    tr = synthetic_trace()
    ctx = {"trace": tr, "calls": [None, None], "lanes_per_call": 100,
           "message_bytes": 116, "device_kind": "TPU v5 lite"}
    assert device_idle.busy_and_window(tr) == (pytest.approx(3.0), 10.0)
    assert device_idle.reduce(ctx) == pytest.approx(70.0)
    assert validation_host.reduce(ctx) == pytest.approx(1e3 * (7.0 - 5.0) / 2)
    assert seam_host.reduce(ctx) == pytest.approx(1e3 * (5.0 - 3.0) / 2)
    share = roofline.reduce(ctx, modules="verify_padded|verify_batch_rlc")
    assert share == pytest.approx(100 * roofline.least_seconds(
        200, 116, "TPU v5 lite")[0] / 3.0)
    assert xplane.short_op("%fusion.12 = (s32[4]{0}) fusion(%p)") == "fusion.12"
    got = run.breakdown(tr)
    assert got["device_ops"] == [["while.1", 2.5], ["fusion.2", 0.5]]
    assert dict(got["idle_gaps"]) == pytest.approx({
        "bench:window": 2.5, "bench:verify_dense": 2.0, "bench:entry": 2.0,
        "bench:present": 0.5})
    # the device's record stops at 8.0 (a full trace buffer): the window ends
    # at the last return it covers, and what follows is not read as idle
    cut = synthetic_trace()
    cut.spans += [("t", "bench:entry", 9.2, 0.7), ("t", "bench:verify_dense", 9.3, 0.5)]
    cut.busy[next(iter(cut.busy))] = xplane.Intervals([2.0, 7.0], [4.0, 8.0])
    cut = xplane.clip_to_record(cut)
    assert cut.window == (0.0, 5.0) and len(cut.spans_named("bench:entry")) == 1
    assert [m[1] for m in cut.modules] == ["jit_verify_padded_gather(1)"]
    assert device_idle.reduce(dict(ctx, trace=cut)) == pytest.approx(60.0)
    # a reader that finds nothing to read returns nothing, never 0
    empty = dict(ctx, trace=xplane.Trace(window=(0.0, 1.0)))
    assert [r.reduce(empty) for r in (device_idle, validation_host, seam_host)] \
        == [None] * 3 and roofline.reduce(empty, modules="x") is None


def test_own_sign_bytes_and_reference_verdicts():
    from cometbft_tpu.types.canonical import canonical_vote_sign_bytes

    cfg = dict(run.load_json(ROOT, "benchmarks/configs/vals150.json"), validators=7)
    ring = data.make_ring(cfg, 2**31 + 11, 3)
    bid, height, commit = data.present(ring, ring.blocks[1])
    for lane in (0, 6):
        assert data.vote_sign_bytes(
            ring.chain_id, height, ring.blocks[1].block_hash, 1,
            ring.blocks[1].parts_hash, ring.blocks[1].stamps[lane]) \
            == canonical_vote_sign_bytes(ring.chain_id, 2, height, 0, bid,
                                         commit.signatures[lane].timestamp_ns)
    assert data.light_lanes(ring.powers) == 5
    ref = reference.Reference(ring)
    assert ref.commit(ring.blocks[0], light=True) == ("ok", 5)
    assert ref.commit(ring.blocks[0], light=False) == ("ok", 7)
    late = data.tamper(ring.blocks[0], 6)       # beyond the light scope
    assert ref.commit(late, light=True) == ("ok", 5)
    assert ref.commit(late, light=False) == ("bad_sig", 6)
    assert ref.window([ring.blocks[0], data.tamper(ring.blocks[1], 2),
                       ring.blocks[2]]) == ("bad_item", 2, 2)
    assert data.make_ring(cfg, 2**31 + 11, 3).blocks == ring.blocks   # seeded


@pytest.mark.parametrize("cell", ONE_CELL_PER_DRIVER)
def test_rehearsal_prints_a_whole_result_line(capsys, cell):
    res = rehearse(capsys, cell)
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device",
                         "checks"]
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    assert res["device"]["platform"] == "cpu"       # a rehearsal says so
    wanted = {m["name"] for m in run.load_cell(cell)["end_to_end"]}
    assert set(res["metrics"]) == wanted
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert all(c["value"] == 0 == c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("control,check", [("skip_signatures", "wrong_answers"),
                                           ("host_route", "lanes_off_device")])
@pytest.mark.parametrize("cell", ONE_CELL_PER_DRIVER)
def test_the_control_comes_out_not_correct(capsys, cell, control, check):
    res = rehearse(capsys, cell, "--control", control)
    assert res["correct"] is False and res["failed"] > 0
    assert res["checks"][check]["value"] > 0


@pytest.mark.parametrize("cell", ONE_CELL_PER_DRIVER)
def test_an_answer_altered_where_it_is_produced_is_caught(capsys, monkeypatch, cell):
    """The timed path broken underneath: the backend seam reports every lane
    valid, so tampered commits are accepted."""
    from cometbft_tpu.crypto import batch as cryptobatch

    real = cryptobatch.verify_dense

    def all_valid(*a, **kw):
        ok, oks = real(*a, **kw)
        return True, np.ones_like(oks)
    monkeypatch.setattr(cryptobatch, "verify_dense", all_valid)
    res = rehearse(capsys, cell)
    assert res["correct"] is False and res["checks"]["wrong_answers"]["value"] > 0


def test_refuses_to_measure_without_a_tpu_or_without_the_program(tmp_path):
    with pytest.raises(SystemExit, match="needs a TPU"):
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0", "--rehearse"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    assert p.returncode != 0 and '"correct"' not in p.stdout
