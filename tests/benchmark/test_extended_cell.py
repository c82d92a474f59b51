"""The extended-commit cell's additions to the benchmark: its metric files,
its own CanonicalVoteExtension bytes, its plain reference and its driver's
units on the CPU, and rehearsals of ``vals10k.extended_commit`` at the
16-lane bucket (``run.py --rehearse``) with both controls and a planted fault
coming out as not correct."""

from __future__ import annotations

import importlib
import json
import os
import sys
from dataclasses import replace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import data, reference, run  # noqa: E402
from benchmarks import reference_extended as refx  # noqa: E402
from benchmarks.drivers import extended_commit_loop  # noqa: E402

CELL = "vals10k.extended_commit"
BENCH = run.load_json(ROOT, "BENCHMARK.json")
EXTENDED = [m for m in BENCH["per_layer"] if m["name"].endswith(".extended")]
SEED = 2**31 + 29


def rehearse(capsys, *extra: str) -> dict:
    capsys.readouterr()
    assert run.main(["--workload", CELL, "--seed", "4100000007", "--seconds",
                     "0.5", "--trace", "0", "--rehearse", *extra]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def small(validators: int = 5, blocks: int = 3):
    cfg = dict(run.load_json(ROOT, "benchmarks/configs/vals10k.json"),
               validators=validators)
    ring = data.make_ring(cfg, SEED, blocks)
    return ring, extended_commit_loop.extend(ring, SEED, cfg["extension_bytes"])


def test_the_cell_and_its_configuration_are_what_the_issue_names():
    spec = run.load_cell(CELL)
    cfg, mix = spec["config"], spec["mix"]
    assert (cfg["validators"], cfg["extension_bytes"], cfg["blocks"]) \
        == (10_000, 32, 8) and cfg["vote_extensions"] is True
    assert set(cfg["reduced"]) == {"key_mix", "blocks"} and "deployment" in cfg
    assert (mix["driver"], mix["callers"], mix["ring_blocks"],
            mix["tamper_every"], mix["patient"]) \
        == ("extended_commit_loop", 1, 8, 32, True)
    assert [m["name"] for m in spec["end_to_end"]] \
        == ["commit_verify_p50_ms", "setup_s"]
    assert spec["cell"]["chips"] == 1 and len(EXTENDED) == 13


@pytest.mark.parametrize("metric", EXTENDED, ids=lambda m: m["name"])
def test_every_new_metric_file_resolves_and_equals_its_entry(metric):
    how = run.load_json(run.HERE, "metrics", metric["name"] + ".json")
    assert {k: how[k] for k in metric} == metric
    assert metric["moves"] == "commit_verify_p50_ms" \
        and metric["workloads"] == [CELL]
    importlib.import_module(f"benchmarks.reduce.{how['reducer']}").reduce
    twin = run.load_json(run.HERE, "metrics", metric["name"].replace(
        ".extended", ".sync") + ".json") \
        if not metric["name"].startswith("ext_rows") else \
        {"reducer": "span_ms", "args": {"sub": "types.validation",
                                        "name": "ext_rows"}}
    assert (how["reducer"], how["args"]) == (twin["reducer"], twin["args"])


@pytest.mark.parametrize("extension", [b"", b"\x01", bytes(range(32)),
                                       bytes(200)], ids=len)
def test_own_extension_sign_bytes_equal_the_programs(extension):
    """The only place the benchmark's encoder and the program's meet."""
    from cometbft_tpu.types.canonical import canonical_vote_extension_sign_bytes

    for chain_id, height in (("bench-vals10k", 1), ("c", 2**40 + 3)):
        assert refx.extension_sign_bytes(chain_id, height, extension) \
            == canonical_vote_extension_sign_bytes(chain_id, height, 0,
                                                   extension)


def test_own_nil_vote_sign_bytes_equal_the_programs():
    from cometbft_tpu.types.block_id import BlockID
    from cometbft_tpu.types.canonical import canonical_vote_sign_bytes

    ts = data.BASE_TIME_NS + 1_000_007
    assert refx.nil_vote_sign_bytes("bench-vals10k", 9, ts) \
        == canonical_vote_sign_bytes("bench-vals10k", 2, 9, 0, BlockID(), ts)


def test_reference_verdicts_follow_upstreams_order_and_tally():
    ring, blocks = small()
    ref = refx.Reference(ring)
    b = blocks[0]
    assert ref.commit(b) == ("ok", 10) and ref.lanes_checked == 10
    assert ref.commit(refx.tamper(b, 3, "vote")) == ("bad_sig", 3, "vote")
    assert ref.commit(refx.tamper(b, 3, "extension")) \
        == ("bad_sig", 3, "extension")
    # two bad lanes: the first validator in order, its vote before its extension
    both = refx.tamper(refx.tamper(b, 4, "vote"), 1, "extension")
    assert ref.commit(replace(both, tampered_lane=99, tampered_kind="both")) \
        == ("bad_sig", 1, "extension")
    same = refx.tamper(refx.tamper(b, 2, "extension"), 2, "vote")
    assert ref.commit(replace(same, tampered_lane=98, tampered_kind="both")) \
        == ("bad_sig", 2, "vote")

    def with_lane(lane, flag, ext, ext_sig, tag):
        flags = tuple(flag if i == lane else refx.FLAG_COMMIT for i in range(5))
        put = lambda t, v: t[:lane] + (v,) + t[lane + 1:]   # noqa: E731
        return replace(b, tampered_lane=tag, tampered_kind="shape", flags=flags,
                       extensions=put(b.extensions, ext),
                       ext_sigs=put(b.ext_sigs, ext_sig))
    # an absent lane is skipped; 4 of 5 for the block is above two thirds
    assert ref.commit(with_lane(2, refx.FLAG_ABSENT, b"", b"", 1)) == ("ok", 8)
    # a nil lane's vote signature is checked (it signed for the block: bad)
    assert ref.commit(with_lane(2, refx.FLAG_NIL, b"", b"", 2)) \
        == ("bad_sig", 2, "vote")
    for flag in (refx.FLAG_NIL, refx.FLAG_ABSENT):  # extension where none belongs
        assert ref.commit(with_lane(2, flag, b.extensions[2], b"", 3 + flag)) \
            == ("refused", "ErrInvalidCommit")
        assert ref.commit(with_lane(2, flag, b"", b.ext_sigs[2], 6 + flag)) \
            == ("refused", "ErrInvalidCommit")
    assert ref.commit(with_lane(0, refx.FLAG_COMMIT, b.extensions[0], b"", 10)) \
        == ("refused", "ErrInvalidCommit")          # none on a for-block lane
    # 3 of 5 for the block: 30 <= 50 * 2 // 3 = 33
    two_absent = replace(
        b, tampered_lane=11, tampered_kind="shape",
        extensions=("",) * 2 + b.extensions[2:],
        ext_sigs=("",) * 2 + b.ext_sigs[2:],
        flags=(refx.FLAG_ABSENT,) * 2 + (refx.FLAG_COMMIT,) * 3)
    assert ref.commit(two_absent) == ("refused", "ErrNotEnoughVotingPower")
    assert extended_commit_loop.extend(ring, SEED, 32) == blocks    # seeded


def test_units_are_fresh_objects_and_tampering_walks_the_ring():
    ring, _ = small(validators=4, blocks=4)
    mix = dict(run.load_json(run.HERE, "traffic", "extended_one_at_a_time.json"),
               tamper_every=3)
    drv = extended_commit_loop.Driver(ring, mix, SEED)
    assert drv.lanes_per_call == 8 and drv.warm_lanes(4096) == [8]
    clean, bad = drv.prime_units()
    assert drv.units(clean)[0].tampered_lane == -1 \
        and drv.units(bad)[0].tampered_lane >= 0
    a, b = drv.units(bad)[0], drv.units(bad)[0]
    assert a == b and a.fresh[0] is not b.fresh[0]      # same commit, new object
    assert isinstance(a, data.Block)        # what data.present and judge read
    bid, height, commit = data.present(ring, a)
    assert height == a.height and commit.size() == 4
    tampered = [drv.units(i)[0] for i in range(24) if drv._tampered(i)]
    assert len(tampered) == 8
    assert {t.tampered_kind for t in tampered} == {"vote", "extension"}
    assert len({t.height for t in tampered}) == 4       # every ring commit
    # one tampered copy of each ring commit, whichever unit presents it
    assert len({(t.height, t.tampered_lane, t.tampered_kind)
                for t in tampered}) == 4
    # on the program's host path the driver's verdicts are the reference's
    ref = reference.Reference(ring)
    for i in range(8):
        unit = drv.units(i)
        got = drv.call(unit, [data.present(ring, u) for u in unit], "cpu")
        assert got == drv.expected(ref, unit)
        assert got[0] == ("bad_sig" if drv._tampered(i) else "ok")
    assert ref.lanes_checked > 0
    # a unit called again (run.py's host-path line) gets an object of its own
    unit = drv.units(clean)
    for _ in range(2):
        assert drv.call(unit, [data.present(ring, unit[0])], "cpu") == ("ok", 8)


def test_rehearsal_prints_a_whole_result_line(capsys):
    res = rehearse(capsys)
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device",
                         "checks"]
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    assert res["device"]["platform"] == "cpu"       # a rehearsal says so
    assert set(res["metrics"]) == {"commit_verify_p50_ms", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert all(c["value"] == 0 == c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("control,check", [("skip_signatures", "wrong_answers"),
                                           ("host_route", "lanes_off_device")])
def test_the_control_comes_out_not_correct(capsys, control, check):
    res = rehearse(capsys, "--control", control)
    assert res["correct"] is False and res["failed"] > 0
    assert res["checks"][check]["value"] > 0


def test_a_kind_altered_where_it_is_produced_is_caught(capsys, monkeypatch):
    """The timed path broken underneath: an extension fault is reported as
    the validator's vote signature (right validator, wrong kind)."""
    from cometbft_tpu.types import validation as V

    real = V._first_bad_extended

    def vote_kind(*a):
        return V.ErrInvalidSignature(real(*a).idx)
    monkeypatch.setattr(V, "_first_bad_extended", vote_kind)
    res = rehearse(capsys)
    assert res["correct"] is False and res["checks"]["wrong_answers"]["value"] > 0
    assert res["checks"]["lanes_off_device"]["value"] == 0
