"""True SPMD dispatch (r19): the plan's mesh shape drives device
resolution, chunking and the blocksync window; a multi-device mesh runs
ONE sharded program per bucket (no per-device fan-out); sharded AOT
bundles are keyed by mesh shape and a mismatch degrades to jit with its
own staleness reason; and ``init_multihost`` probes the distributed
runtime through public API only.

Runs on the conftest's 8 emulated CPU host devices
(``--xla_force_host_platform_device_count=8``)."""

import dataclasses

import numpy as np
import pytest

from cometbft_tpu.crypto import aotbundle
from cometbft_tpu.crypto import batch as B
from cometbft_tpu.crypto import plan as P
from cometbft_tpu.crypto import rlc_finish
from cometbft_tpu.parallel import mesh as M

pytestmark = pytest.mark.timeout(120)


@pytest.fixture(autouse=True)
def clean_plan():
    saved = P.active()
    yield
    P.set_plan(saved, push_min_lanes=False)
    P.set_devices(None)
    aotbundle.reset()


def _stale_counter():
    from cometbft_tpu.libs import metrics

    return metrics.counter("crypto_compile_bundle_stale_total", "")


# --------------------------------------------------- plan mesh semantics


def test_mesh_shape_resolves_devices():
    import jax

    assert len(jax.devices()) >= 8        # conftest forces 8 host devices
    P.configure(mesh_shape=(4,))
    devs = P.resolve_devices(None)
    assert len(devs) == 4
    assert devs == tuple(jax.devices())[:4]
    # an explicit pin still wins over the mesh
    assert P.resolve_devices(jax.devices()[5]) == (jax.devices()[5],)
    # no mesh declared: CPU hosts keep single-device (jit default)
    P.configure(mesh_shape=())
    assert P.resolve_devices(None) == ()


def test_mesh_shape_outside_plan_hash_but_in_describe():
    base = P.active()
    meshed = dataclasses.replace(base, mesh_shape=(4,))
    # a mesh change must NOT look like a plan change: the bundle guard
    # reports it as reason=mesh, not reason=version
    assert P.plan_hash(base) == P.plan_hash(meshed)
    d = P.describe(meshed)
    assert d["mesh_shape"] == [4]
    assert d["mesh_size"] == 4
    assert P.mesh_size(meshed) == 4
    assert P.mesh_size(base) == 1


def test_chunk_bucket_and_occupancy_past_cap_on_mesh():
    devs8 = tuple(range(8))
    # past the single-device cap the global shape is per-device-bucket x
    # mesh: 5000 over 8 devices -> ceil(5000/8)=625 -> 1024 x 8
    assert P.chunk_bucket(5000, devs8) == 8192
    assert P.chunk_bucket(5000, ()) == 5000       # single device: exact
    # at or below the cap the r13 semantics stand (pinned elsewhere)
    assert P.chunk_bucket(100, (1, 2, 3, 4)) == 256
    # occupancy is judged against the full-mesh padded shape; the chunk
    # cap scales with the mesh so 10k lanes on 8 devices is ONE dispatch
    assert abs(P.mesh_occupancy(10_000, 8) - 10_000 / 16_384) < 1e-9
    # non-power-of-two lane counts on a multi-device mesh
    assert abs(P.mesh_occupancy(3000, 3) - 3000 / 4098) < 1e-9
    assert abs(P.mesh_occupancy(5000, 4) - 5000 / 8192) < 1e-9
    assert P.mesh_occupancy(4096 * 2, 2) == 1.0


def test_window_blocks_snaps_to_full_mesh():
    # no mesh: the configured window stands
    assert P.window_blocks(32, 100) == 32
    P.configure(mesh_shape=(8,))
    # 32 blocks x 100 vals = 3200 lanes; per-device share 400 -> 1024
    # bucket -> full-mesh shape 8192 lanes -> 81 blocks (snapped from
    # below: 82 would spill 8 lanes into a second padded dispatch)
    assert P.window_blocks(32, 100) == 81
    assert P.mesh_occupancy(81 * 100, 8) >= 0.98
    # a window whose per-device share already sits at the lane cap only
    # snaps to the cap's full-mesh shape (never an uncompilable size)
    assert P.window_blocks(200, 100) == 327       # 4096 x 8 / 100
    # huge valsets fill the mesh from a single block: window stands
    assert P.window_blocks(32, 5000) == 32
    assert P.window_blocks(32, 0) == 32


# ------------------------------------------- ONE sharded dispatch per bucket


def test_one_sharded_dispatch_per_bucket(monkeypatch):
    """A multi-device mesh must execute ONE sharded program per bucket —
    never a per-device fan-out, never the single-device route."""
    calls = []

    def factory(name, result):
        def make(*key):
            def fn(*a, **k):
                calls.append(name)
                return result
            return fn
        return make

    bb = 1024                     # chunk_bucket(300, 4 devices)
    monkeypatch.setattr(B, "_compiled_rlc_sharded",
                        factory("rlc_sharded", rlc_finish.verdict(True)))
    monkeypatch.setattr(B, "_compiled_verify_sharded",
                        factory("verify_sharded", np.ones((bb,), bool)))
    monkeypatch.setattr(
        B, "_compiled_rlc",
        factory("rlc_single", rlc_finish.verdict(True)))
    monkeypatch.setattr(
        B, "_compiled_verify",
        factory("verify_single", np.ones((bb,), bool)))
    P.configure(mesh_shape=(4,))
    n = 300                       # >= rlc_min_lanes, one bucket
    z = np.zeros((n, 32), np.uint8)
    msgs = np.zeros((n, 8), np.uint8)
    lens = np.full((n,), 8, np.int64)
    out = B.device_verify_ed25519(z, z, z, msgs, lens)
    assert out.shape == (n,)
    assert calls == ["rlc_sharded"]          # exactly one dispatch
    # an RLC reject localizes with exactly ONE sharded per-lane dispatch
    calls.clear()
    monkeypatch.setattr(B, "_compiled_rlc_sharded",
                        factory("rlc_sharded", rlc_finish.verdict(False)))
    B.device_verify_ed25519(z, z, z, msgs, lens)
    assert calls == ["rlc_sharded", "verify_sharded"]


def test_mesh_metrics_record_sharded_route(monkeypatch):
    monkeypatch.setattr(B, "_compiled_rlc_sharded",
                        lambda devs: lambda *a: rlc_finish.verdict(True))
    gauge, occ, total = B._mesh_metrics()
    before = total.value(route="sharded")
    P.configure(mesh_shape=(4,))
    n = 300
    z = np.zeros((n, 32), np.uint8)
    B.device_verify_ed25519(z, z, z, np.zeros((n, 8), np.uint8),
                            np.full((n,), 8, np.int64))
    assert total.value(route="sharded") == before + 1
    assert gauge.value() == 4


# -------------------------------------------------- sharded AOT bundles


def _mesh_plan(nd=4, lanes=16):
    return dataclasses.replace(
        P.active(), warm_kinds=(), warm_tables=(),
        warm_merkle=(lanes,), mesh_shape=(nd,))


def test_sharded_bundle_roundtrip_keyed_by_mesh(tmp_path):
    """Build -> save -> fresh load of a sharded executable, keyed
    ``@m<D>``, with sharded output bit-identical to single-device."""
    import jax

    plan = _mesh_plan(nd=4, lanes=16)
    path = str(tmp_path / "bundle-m4.aot")
    info = aotbundle.build(plan=plan, path=path)
    key = "merkle_level:16@m4"
    assert info["buckets"] == {key: "warm"}
    rng = np.random.default_rng(3)
    left = rng.integers(0, 2**32, (16, 8), dtype=np.uint32)
    right = rng.integers(0, 2**32, (16, 8), dtype=np.uint32)
    sharded = np.asarray(aotbundle.timed_call(key, left, right))

    aotbundle.reset()
    info = aotbundle.load(path=path, plan=plan)
    assert info["status"] == "loaded"
    assert info["buckets"][key] == "warm"
    assert aotbundle.lookup(key) is not None
    assert aotbundle.lookup("merkle_level:16") is None   # tag required
    reloaded = np.asarray(aotbundle.timed_call(key, left, right))

    from cometbft_tpu.ops import sha256 as _sha

    single = np.asarray(jax.jit(_sha.merkle_inner_level)(left, right))
    assert (sharded == single).all()
    assert (reloaded == single).all()


def test_mesh_mismatch_degrades_with_reason_mesh(tmp_path):
    """A 4-device bundle must never load on an 8-device mesh: same
    bundle_version (mesh is outside the plan hash), so the header's mesh
    dims are the guard — reason=mesh, safe degrade to jit."""
    plan4 = _mesh_plan(nd=4, lanes=16)
    path = str(tmp_path / "bundle.aot")
    aotbundle.build(plan=plan4, path=path)
    aotbundle.reset()

    plan8 = dataclasses.replace(plan4, mesh_shape=(8,))
    assert aotbundle.bundle_version(plan4) == aotbundle.bundle_version(plan8)
    c = _stale_counter()
    before = c.value(reason="mesh")
    info = aotbundle.load(path=path, plan=plan8)
    assert info["status"] == "stale"
    assert info["buckets"] == {}
    assert aotbundle.lookup("merkle_level:16@m4") is None
    assert aotbundle.lookup("merkle_level:16@m8") is None
    assert c.value(reason="mesh") == before + 1
    # and a single-device plan rejects a sharded bundle the same way
    aotbundle.reset()
    plan1 = dataclasses.replace(plan4, mesh_shape=())
    assert aotbundle.load(path=path, plan=plan1)["status"] == "stale"


def test_default_path_carries_mesh_tag():
    plan = _mesh_plan(nd=4)
    p = aotbundle.default_path(plan=plan)
    assert p.endswith("-m4.aot")
    single = dataclasses.replace(plan, mesh_shape=())
    assert aotbundle.default_path(plan=single).endswith(
        f"bundle-{aotbundle.bundle_version(single)}.aot")


# --------------------------------------------- init_multihost public probe


def test_init_multihost_asks_the_public_probe(monkeypatch):
    import types

    import jax

    calls = []

    def fake_init(**kw):
        calls.append(kw)

    # runtime not live: initialize once, with exactly what was given
    monkeypatch.setattr(
        jax, "distributed",
        types.SimpleNamespace(initialize=fake_init,
                              is_initialized=lambda: False), raising=False)
    M.init_multihost(coordinator="127.0.0.1:9999", num_processes=1,
                     process_id=0)
    assert calls == [{"coordinator_address": "127.0.0.1:9999",
                      "num_processes": 1, "process_id": 0}]

    # runtime live: no re-init
    monkeypatch.setattr(
        jax, "distributed",
        types.SimpleNamespace(initialize=fake_init,
                              is_initialized=lambda: True), raising=False)
    M.init_multihost(coordinator="127.0.0.1:9999")
    assert len(calls) == 1                       # unchanged

    # an initialize that raises is the caller's to see, whatever it says
    for msg in ("coordinator unreachable",
                "jax.distributed.initialize was already called"):
        def broken_init(msg=msg, **kw):
            raise RuntimeError(msg)

        monkeypatch.setattr(
            jax, "distributed",
            types.SimpleNamespace(initialize=broken_init,
                                  is_initialized=lambda: False),
            raising=False)
        with pytest.raises(RuntimeError, match=msg.split()[-1]):
            M.init_multihost(coordinator="127.0.0.1:9999")


def test_mesh_module_has_no_private_jax_reach():
    import inspect

    src = inspect.getsource(M)
    assert "jax._src" not in src
