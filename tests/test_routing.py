"""Measured backend auto-routing: under
backend="auto" the dispatcher must never keep verifying on a device the
router has measured slower than the native host path — with periodic
exploration so a recovered device gets re-measured."""

import numpy as np
import pytest

from cometbft_tpu.crypto import batch as B
from cometbft_tpu.crypto.keys import Ed25519PrivKey


class _FakeDevice:
    platform = "tpu"


@pytest.fixture(autouse=True)
def clean_router():
    # node-spawning tests earlier in the suite raise the process-wide
    # device-lane threshold (node.py applies config.base.min_device_lanes,
    # default 64) and can leave an abandoned in-flight device future; both
    # would silently force these 8-lane batches onto the host path
    saved_min = B.TpuBatchVerifier.MIN_DEVICE_LANES
    saved_inflight = B._DEVICE_INFLIGHT
    B.TpuBatchVerifier.MIN_DEVICE_LANES = 1
    B._DEVICE_INFLIGHT = None
    B._ROUTER.reset()
    yield
    B._ROUTER.reset()
    B.TpuBatchVerifier.MIN_DEVICE_LANES = saved_min
    B._DEVICE_INFLIGHT = saved_inflight


def test_router_optimistic_until_measured():
    r = B._ThroughputRouter()
    assert r.prefer_device(1024)           # no samples: try the device
    r.observe("host", 1024, 0.01)
    assert r.prefer_device(1024)           # still no device sample


def test_router_prefers_measured_faster_host():
    r = B._ThroughputRouter()
    r.observe("device", 1024, 1.0)         # 1024 sigs/s
    r.observe("host", 1024, 0.01)          # 102400 sigs/s
    assert not r.prefer_device(1024)
    # flip: device gets dramatically faster on re-measure
    for _ in range(8):
        r.observe("device", 1024, 0.001)
    assert r.prefer_device(1024)


def test_router_hysteresis_keeps_device_near_parity():
    r = B._ThroughputRouter()
    r.observe("device", 512, 1.0)
    r.observe("host", 512, 1.05)           # host barely slower than 90% rule
    assert r.prefer_device(512)


def test_router_periodic_exploration():
    r = B._ThroughputRouter()
    r.observe("device", 256, 1.0)
    r.observe("host", 256, 0.01)
    decisions = [r.prefer_device(256) for _ in range(130)]
    assert not decisions[0]
    assert any(decisions), "exploration never re-tried the device"
    assert decisions.count(True) <= 3      # rare, not flapping


def test_router_buckets_are_independent():
    r = B._ThroughputRouter()
    r.observe("device", 2000, 1.0)
    r.observe("host", 2000, 0.001)
    assert not r.prefer_device(2000)
    assert r.prefer_device(16)             # small bucket: unmeasured


def _items(n):
    out = []
    for i in range(n):
        pv = Ed25519PrivKey.from_secret(b"route%d" % i)
        msg = b"m%d" % i
        out.append((pv.pub_key(), msg, pv.sign(msg)))
    return out


def test_auto_backend_routes_slow_device_to_host(monkeypatch):
    """A present-but-slow device must not capture the hot path: with the
    router seeded from measurements, backend=auto serves from the native
    host batch and never dispatches to the device."""
    monkeypatch.setattr(B, "_accelerator_device", lambda: _FakeDevice())
    B._ROUTER.observe("device", 8, 10.0)   # measured: painfully slow
    B._ROUTER.observe("host", 8, 0.001)

    def boom(*a, **k):
        raise AssertionError("device dispatch must not run")

    monkeypatch.setattr(B, "device_verify_ed25519", boom)
    monkeypatch.setattr(B, "device_verify_ed25519_cached", boom)

    bv = B.create_batch_verifier("auto")
    assert isinstance(bv, B.TpuBatchVerifier) and bv._routed
    for pub, msg, sig in _items(8):
        bv.add(pub, msg, sig)
    ok, oks = bv.verify()
    assert ok and all(oks)


def test_explicit_tpu_backend_skips_router(monkeypatch):
    """backend="tpu" is an operator override: the router must not keep
    it off the device."""
    monkeypatch.setattr(B, "_accelerator_device", lambda: _FakeDevice())
    B._ROUTER.observe("device", 8, 10.0)
    B._ROUTER.observe("host", 8, 0.001)
    assert B._backend_wants_device("tpu", None, lanes=8)
    assert B._backend_wants_device("jax", None, lanes=8)
    assert not B._backend_wants_device("auto", None, lanes=8)


def test_device_timeout_feeds_pessimistic_sample(monkeypatch):
    """A bounded-wait abandonment charges the router the full wait, so
    subsequent auto batches route to host until the device answers."""
    monkeypatch.setattr(B, "_accelerator_device", lambda: _FakeDevice())
    monkeypatch.setattr(B, "_device_call", lambda fn: None)  # wedged

    bv = B.TpuBatchVerifier(routed=True)
    for pub, msg, sig in _items(8):
        bv.add(pub, msg, sig)
    ok, oks = bv.verify()                  # host fallback still verifies
    assert ok and all(oks)
    assert ("device", B.bucket_for_lanes(8)) in B._ROUTER._ewma
    # the pessimistic sample must now lose to any healthy host number
    B._ROUTER.observe("host", 8, 0.001)
    assert not B._ROUTER.prefer_device(8)


# ------------------------------------------- "tpu" means TPU; cache placement


def test_tpu_backend_on_a_cpu_only_process_raises():
    """backend="tpu" must never be XLA:CPU counted as route="device":
    verifier creation, the dense path and the node-start check all name
    the platform found ("jax" stays whatever backend JAX has)."""
    for call in (lambda: B.create_batch_verifier("tpu"),
                 lambda: B._backend_wants_device("tpu", None, lanes=8),
                 lambda: B.device_info("tpu")):
        with pytest.raises(B.DeviceUnavailable, match="'cpu'"):
            call()
    assert isinstance(B.create_batch_verifier("jax"), B.TpuBatchVerifier)
    assert B.device_info("jax")["route"] == "device"
    info = B.device_info("auto")
    assert (info["platform"], info["route"]) == ("cpu", "host")
    assert B.device_info("cpu")["route"] == "host"


@pytest.mark.parametrize("placed", [True, False],
                         ids=["env_set", "env_unset"])
def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path, placed):
    """JAX_COMPILATION_CACHE_DIR set: the program sets no directory in
    code (jax reads the variable itself) and the AOT bundle goes under
    it; unset: both stay at the fixed <checkout>/.jax_cache."""
    import os

    import jax

    from cometbft_tpu import jaxenv
    from cometbft_tpu.crypto import aotbundle

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fixed = os.path.join(checkout, ".jax_cache")
    seen = []
    real_update = jax.config.update

    def spy(name, value):
        seen.append(name)
        if name != "jax_compilation_cache_dir":
            real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jaxenv.enable_compile_cache()
    assert ("jax_compilation_cache_dir" in seen) == (not placed)
    want = str(tmp_path) if placed else fixed
    assert jaxenv.compile_cache_dir() == want
    assert os.path.dirname(aotbundle.default_path()) == \
        os.path.join(want, "aot")
