"""10k-validator consensus-path test: one REAL
commit over a synthetic 10,000-validator set driven through the
production VerifyCommit dense path on the device route — the
cached-table gather + RLC dispatch (`crypto/batch.py`
device_verify_ed25519_cached) — capturing the p50 latency end to end,
not just in bench.py.  On the CPU-pinned test mesh the "device" is a
virtual CPU device, so this pins the code path and the latency
plumbing; the hardware number comes from ``BENCH_MODE=p50commit``."""

import json
import os
import time

import numpy as np
import pytest

pytestmark = [pytest.mark.timeout(1800), pytest.mark.slow]

N_VALS = 10_000


@pytest.fixture(scope="module")
def big_chain():
    from cometbft_tpu.testing import make_light_chain

    t0 = time.perf_counter()
    chain = make_light_chain(1, n_vals=N_VALS, chain_id="big-chain")
    print(f"built {N_VALS}-val chain in {time.perf_counter() - t0:.1f}s")
    return chain[0]


def test_10k_validator_commit_verifies_on_device_route(big_chain):
    """The full 10k-signature commit verifies through the device
    dispatch (cached valset tables + RLC fast path), and a tampered
    signature is caught with its lane localized."""
    from cometbft_tpu.crypto import batch as cb
    from cometbft_tpu.types import validation as V

    lanes_before = _route_count(cb, "device_rlc")
    t0 = time.perf_counter()
    V.VerifyCommitLightAllSignatures(
        "big-chain", big_chain.validators, big_chain.commit.block_id,
        big_chain.height, big_chain.commit, backend="jax")
    cold_s = time.perf_counter() - t0

    # the RLC fast path carried lanes (the batch is all-valid)
    assert _route_count(cb, "device_rlc") > lanes_before

    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        V.VerifyCommitLightAllSignatures(
            "big-chain", big_chain.validators, big_chain.commit.block_id,
            big_chain.height, big_chain.commit, backend="jax")
        times.append(time.perf_counter() - t0)
    p50 = sorted(times)[len(times) // 2]
    print(f"p50 VerifyCommit @{N_VALS} vals (virtual device route): "
          f"{p50 * 1e3:.1f} ms (cold {cold_s:.1f}s)")

    if os.environ.get("RECORD_ARTIFACTS"):
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "docs", "bench",
            "r05-p50commit-10k-virtual.json")
        with open(path, "w") as f:
            json.dump({"metric": "p50 VerifyCommit @10k vals, virtual "
                                 "CPU device route (code-path pin, not "
                                 "a hardware number)",
                       "p50_ms": round(p50 * 1e3, 2),
                       "cold_s": round(cold_s, 2)}, f, indent=1)


def test_10k_validator_commit_tampered_lane_localized(big_chain):
    import copy

    from cometbft_tpu.types import validation as V

    commit = copy.deepcopy(big_chain.commit)
    bad = 7777
    commit.signatures[bad].signature = bytes(64)
    with pytest.raises(V.ErrInvalidSignature) as exc:
        V.VerifyCommitLightAllSignatures(
            "big-chain", big_chain.validators, commit.block_id,
            big_chain.height, commit, backend="jax")
    assert exc.value.idx == bad


def _route_count(cb, route: str) -> float:
    """Sum of the crypto_batch_lanes_total counter for one route label."""
    _, lanes, _ = cb._metrics()
    total = 0.0
    for key, val in getattr(lanes, "_values", {}).items():
        if route in str(key):
            total += val
    return total
