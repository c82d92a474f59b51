"""chip_smoke.py cannot pass without a chip, and its phase functions give
the host's answers at tiny sizes on the CPU backend (backend "jax")."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from cometbft_tpu.crypto import batch as B  # noqa: E402


@pytest.fixture(autouse=True)
def clean_seam():
    # same hygiene as test_routing: node-spawning tests leave the lane
    # threshold raised and may leave an abandoned in-flight future
    saved = B.TpuBatchVerifier.MIN_DEVICE_LANES, B._DEVICE_INFLIGHT
    B.TpuBatchVerifier.MIN_DEVICE_LANES = 1
    B._DEVICE_INFLIGHT = None
    chip_smoke.Sent.lanes = 0
    yield
    B.TpuBatchVerifier.MIN_DEVICE_LANES, B._DEVICE_INFLIGHT = saved


def _device_lanes() -> float:
    return chip_smoke.device_health(chip_smoke.local_metrics())[
        "device_lanes"]


def test_smoke_refuses_to_pass_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr


def test_tampered_batch_matches_its_labels():
    batch = chip_smoke.signed_batch(12, 10, seed=3)
    bad, labels = chip_smoke.tampered(batch, seed=3)
    assert batch["valset"].shape == (12, 32) and len(labels) == 7
    assert chip_smoke.oracle(batch).all()
    want = chip_smoke.oracle(bad)
    rejected = {i for i in range(10) if not want[i]}
    assert rejected == {i for i, what in labels.items()
                        if what in ("bad_signature", "wrong_message",
                                    "s_ge_l")}


@pytest.mark.timeout(420)
def test_commit_phase_at_tiny_size():
    before = _device_lanes()
    out = chip_smoke.check_commit("jax", n_vals=4)
    assert out["bad_lane_caught"] == 1 and out["light_lanes"] == 3
    assert _device_lanes() - before == chip_smoke.Sent.lanes == 3 + 4 * 4


@pytest.mark.timeout(420)
def test_headline_phase_at_tiny_size():
    before = _device_lanes()
    out = chip_smoke.check_headline("jax", n_vals=16, n_lanes=16,
                                    plain_lanes=12, seed=5)
    assert len(out["cached_rejected"]) == 3
    assert set(out["plain_rejected"]) == set(out["cached_rejected"])
    assert _device_lanes() - before == chip_smoke.Sent.lanes == 3 * (16 + 12)


@pytest.mark.timeout(420)
def test_blocksync_phase_at_tiny_size():
    out = chip_smoke.check_blocksync("jax", n_blocks=4, n_vals=4,
                                     corrupt_block=3)
    assert out["caught_height"] == 3 and out["lanes"] == 4 * 3


def test_merkle_phase_at_tiny_size(monkeypatch):
    from cometbft_tpu.crypto import merkle

    monkeypatch.setenv("TPU_BFT_MERKLE_KERNEL", "1")    # no chip here
    monkeypatch.setattr(merkle, "_KERNEL_MIN_LEAVES", 256)
    out = chip_smoke.check_merkle(n_leaves=256, seed=5)
    assert len(out["root"]) == 64


def test_metrics_text_is_read_back():
    m = chip_smoke.parse_metrics(
        '# HELP x y\nfoo_total{route="device",k="1"} 5.0\n'
        'foo_total{route="host_fallback"} 2\nbar 1.5\n')
    assert chip_smoke.metric(m, "foo_total", route="device") == 5.0
    assert chip_smoke.metric(m, "foo_total") == 7.0
    assert chip_smoke.metric(m, "bar") == 1.5
    assert chip_smoke.metric(m, "absent") == 0


@pytest.mark.timeout(420)
def test_warm_dispatches_run_concurrently_and_raise_failures(monkeypatch):
    out = chip_smoke.warm_concurrently(
        [dict(lanes=12), dict(lanes=12, n_vals=4)], workers=2)
    assert out["jobs"] == 2
    firsts = chip_smoke.first_dispatches(chip_smoke.local_metrics())
    assert {"verify:16", "tables:16", "gather:16"} <= set(firsts)

    def boom(*a, **k):
        raise RuntimeError("compiler said no")

    monkeypatch.setattr(B, "device_verify_ed25519", boom)
    with pytest.raises(RuntimeError, match="compiler said no"):
        chip_smoke.warm_concurrently([dict(lanes=12)], workers=2)


def test_jit_ledger_sums_jax_compile_events():
    import jax
    import jax.numpy as jnp

    ledger = chip_smoke.JitLedger()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    assert ledger.sums["trace_s"] > 0
    assert ledger.sums["backend_compile_s"] > 0
    assert set(ledger.report()) == {
        "trace_s", "lower_s", "backend_compile_s", "cache_saved_s",
        "cache_hits", "cache_misses"}
