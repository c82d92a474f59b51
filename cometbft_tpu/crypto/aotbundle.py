"""The AOT compile-bundle cache: serialized XLA executables for the
device plan's warm compile buckets, loaded at node start.

Why it exists: PR 5 measured ~110 s of cold XLA compile per verify
bucket on this image vs 0.14 s warm — and even with the persistent
HLO-level compile cache a fresh process still pays multi-second tracing
and lowering on its first dispatch of every shape.  Spinning up a verify
node per traffic spike is only plausible if the node boots WARM: this
module enumerates the compile buckets from the declarative device plan
(``crypto/plan.py``), AOT-lowers and compiles each one
(``jax.jit(fn).lower(args).compile()``), serializes the executables
(``jax.experimental.serialize_executable``) into one versioned on-disk
bundle, and on later boots deserializes them straight into the dispatch
table — the first real dispatch then runs at warm-dispatch latency, with
no tracing, no lowering, no compile.

Versioning/staleness (the hard safety requirement): serialized
executables embed jaxlib internals, so a bundle is only valid for the
exact (bundle format, jax, jaxlib, platform, device count, plan hash)
that built it.  The fingerprint is checked BEFORE any payload is
deserialized; a mismatched or undecodable bundle is ignored with a
logged warning and a ``crypto_compile_bundle_stale_total`` tick — never
a crash, never a silently wrong executable.  The bundle file is trusted
local state (same trust level as the XLA persistent cache it extends):
the outer container is msgpack, and the pickled pytree metadata inside
is only touched after the fingerprint matches.

Surfaces: ``crypto_compile_bundle_info`` (gauge: warm-bucket count,
labeled by bundle version + status) and the ``compile_bundle`` block in
``/status`` (version, plan shape, per-bucket cold/warm).  The dispatch
integration lives in ``crypto/batch.py``/``crypto/merkle.py``:
``lookup(key)`` is a plain dict hit consulted before the jit caches.
"""

from __future__ import annotations

import functools
import os
import pickle
import time

from . import plan as _plan

_MAGIC = "cmt-aot"
_FORMAT = 4          # 2: the RLC programs return their packed window
#   sums (crypto/rlc_finish.py), not a boolean: a format-1 bundle must
#   never be loaded against the caller that folds them.  3: the RLC
#   programs select their window entries instead of gathering them; a
#   saved format-2 bundle would go on serving the old executable.  4: the
#   RLC lane tree runs in the tree form (ops/group.py add_tree)

_LOADED: dict[str, object] = {}      # bucket key -> loaded executable
_INFO: dict = {"status": "absent", "buckets": {}}


@functools.cache
def _metrics():
    from ..libs import metrics as m

    return (
        m.gauge("crypto_compile_bundle_info",
                "AOT compile-bundle state: value = warm (loaded) bucket "
                "count, labeled by bundle version and load status"),
        m.counter("crypto_compile_bundle_stale_total",
                  "bundles (or bundle buckets) ignored, by reason"),
    )


def _log():
    from ..libs import log as tmlog

    return tmlog.logger("aotbundle")


# -------------------------------------------------------------- identity


def bundle_version(plan=None) -> str:
    """The full environment+plan fingerprint a bundle is keyed by.
    Anything that could change the compiled artifact's meaning is folded
    in: bundle format, jax + jaxlib versions, backend platform and
    device count, and the declarative plan hash."""
    import hashlib

    import jax

    try:
        import jaxlib

        jl = getattr(jaxlib, "__version__", "?")
    except Exception:
        jl = "?"
    devs = jax.devices()
    doc = "|".join([
        str(_FORMAT), jax.__version__, jl,
        devs[0].platform if devs else "?", str(len(devs)),
        _plan.plan_hash(plan),
    ])
    return hashlib.sha256(doc.encode()).hexdigest()[:16]


def default_path(dir_: str | None = None, plan=None) -> str:
    """Bundle location: ``<dir>/bundle-<version>[-m<D>].aot`` (one file
    per fingerprint, so a jax upgrade builds beside the old bundle
    instead of clobbering it; a mesh tag keeps sharded bundles beside
    the single-device one — the plan hash deliberately excludes the
    mesh shape).  Default dir sits under the persistent XLA cache's."""
    from ..jaxenv import compile_cache_dir

    base = dir_ or os.path.join(compile_cache_dir(), "aot")
    plan = plan or _plan.active()
    nd = _plan.mesh_size(plan)
    tag = f"-m{nd}" if nd > 1 else ""
    return os.path.join(base, f"bundle-{bundle_version(plan)}{tag}.aot")


# --------------------------------------------------------------- samples


def _kernel_fn(kind: str):
    if kind == "verify":
        from ..ops import ed25519 as k

        return k.verify_padded
    if kind == "rlc":
        from ..ops import rlc as k

        return k.verify_batch_rlc
    if kind == "gather":
        from ..ops import ed25519 as k

        return k.verify_padded_gather
    if kind == "rlc_gather":
        from ..ops import rlc as k

        return k.verify_batch_rlc_gather
    if kind == "tables":
        from ..ops import ed25519 as k

        return k.prepare_pubkey_tables
    if kind == "bls_agg":
        from ..ops import blsg1 as k

        return k.aggregate_g1_masked
    if kind == "merkle_level":
        from ..ops import sha256 as k

        return k.merkle_inner_level
    raise ValueError(f"unknown compile-bucket kind {kind!r}")


def sample_args(bucket: "_plan.CompileBucket") -> tuple:
    """Arrays of EXACTLY the shapes/dtypes the production dispatch
    builds for this bucket — assembled through the same host packers
    (``batch._padded_lane_args`` / ``_rlc_args``), so the AOT-compiled
    executable and the runtime call can never disagree on a shape."""
    import numpy as np

    if bucket.kind == "merkle_level":
        row = np.zeros((bucket.lanes, 8), np.uint32)
        return (row, row)
    if bucket.kind == "tables":
        return (np.zeros((bucket.table_rows, 32), np.int32),)
    if bucket.kind == "bls_agg":
        from ..ops import blsg1

        return (np.zeros((bucket.table_rows, 2, blsg1.NLIMB), np.int32),
                np.zeros((bucket.table_rows,), np.int32))
    from . import batch as _b

    bb, nb = bucket.lanes, bucket.blocks
    # longest message that still fits nb SHA-512 blocks after the
    # 64-byte R||A prefix and 17 bytes of padding (same as warmup)
    msg_len = nb * 128 - 64 - 17
    zeros32 = np.zeros((bb, 32), np.uint8)
    msgs = np.zeros((bb, msg_len), np.uint8)
    lens = np.full((bb,), msg_len, np.int64)
    args = _b._padded_lane_args(zeros32, zeros32, zeros32, msgs, lens, bb)
    if bucket.kind == "rlc":
        return args + (_b._rlc_args(bb, bb),)
    if bucket.kind in ("gather", "rlc_gather"):
        # cached-valset route: (tab, ok, idx, r32, s32, blocks, active
        # [, z10]) — the table/ok avals come from the table-build kernel
        # itself so they can never drift from what _valset_tables feeds
        import jax

        from ..ops import ed25519 as _ked

        # the table is a custom pytree (ops.group Cached) — zero-fill
        # every leaf of the exact structure the table kernel emits
        tab, ok = jax.tree_util.tree_map(
            lambda s: np.zeros(s.shape, s.dtype),
            jax.eval_shape(
                _ked.prepare_pubkey_tables,
                jax.ShapeDtypeStruct((bucket.table_rows, 32), np.int32)))
        idx = np.zeros((bb,), np.int32)
        out = (tab, ok, idx) + args[1:]
        if bucket.kind == "rlc_gather":
            out = out + (_b._rlc_args(bb, bb),)
        return out
    return args


# ------------------------------------------------------------ build/save


def build(plan=None, kinds: tuple | None = None, path: str | None = None,
          save: bool = True) -> dict:
    """AOT-lower + compile every warm bucket of the plan, register the
    executables in the live dispatch table, and (by default) serialize
    them into the versioned bundle file.  Returns the info dict also
    surfaced at ``/status``."""
    from jax.experimental import serialize_executable as se
    import jax

    from . import batch as _b

    plan = plan or _plan.active()
    _b._jit_env()
    nd = _plan.mesh_size(plan)
    mesh_devices = None
    if nd > 1:
        devs = jax.devices()
        if len(devs) >= nd:
            mesh_devices = list(devs[:nd])
        else:
            _log().warn("plan mesh wider than visible devices; building "
                        "a single-device bundle", mesh=nd,
                        devices=len(devs))
            nd = 1
    buckets = _plan.enumerate_buckets(plan, kinds=kinds)
    entries: dict[str, dict] = {}
    statuses: dict[str, str] = {}
    for bucket in buckets:
        key = bucket.key
        t0 = time.perf_counter()
        try:
            if mesh_devices is not None and bucket.kind not in (
                    "tables", "bls_agg"):
                # sharded program over the plan's mesh; the @m<D> key tag
                # and the header's mesh dims keep it off any other mesh.
                # ("tables" builds once and replicates, so it stays a
                # single-device program.)
                if bucket.lanes % nd:
                    statuses[key] = "degraded:mesh_divides"
                    _log().warn("bucket lanes do not divide the mesh; "
                                "not bundling", bucket=key, mesh=nd)
                    continue
                from ..parallel.mesh import sharded_kernel

                key = f"{bucket.key}@m{nd}"
                jfn = sharded_kernel(bucket.kind, mesh_devices)
            else:
                jfn = jax.jit(_kernel_fn(bucket.kind))
            args = sample_args(bucket)
            compiled = jfn.lower(*args).compile()
            payload, in_tree, out_tree = se.serialize(compiled)
        except Exception as e:
            _log().error("AOT build failed for bucket; skipping",
                         bucket=key, err=repr(e))
            statuses[key] = "degraded:compile"
            continue
        secs = time.perf_counter() - t0
        _LOADED[key] = compiled
        entries[key] = {
            "payload": payload,
            "trees": pickle.dumps((in_tree, out_tree)),
            "compile_s": round(secs, 3),
        }
        statuses[key] = "warm"
        _log().info("AOT-compiled bucket", bucket=key,
                    secs=round(secs, 2))
    version = bundle_version(plan)
    out_path = path or default_path(plan=plan)
    if save and entries:
        _save_file(out_path, version, plan, entries)
    return _set_info({
        "status": "built" if entries else "build_failed",
        "version": version,
        "path": out_path if save else None,
        "plan": _plan.describe(plan),
        "buckets": statuses,
    })


def _save_file(path: str, version: str, plan, entries: dict) -> None:
    import msgpack

    doc = {
        "magic": _MAGIC,
        "format": _FORMAT,
        "version": version,
        # mesh dims ride OUTSIDE the version hash: a mesh mismatch is
        # its own staleness reason (a 4-chip executable on an 8-chip
        # mesh would be silently wrong, not just stale)
        "mesh": [int(d) for d in plan.mesh_shape],
        "plan": _plan.describe(plan),
        "buckets": entries,
    }
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(msgpack.packb(doc, use_bin_type=True))
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    _log().info("compile bundle written", path=path,
                buckets=len(entries),
                bytes=os.path.getsize(path))


# ------------------------------------------------------------------ load


def load(path: str | None = None, plan=None) -> dict:
    """Load a bundle into the live dispatch table.  The staleness guard
    runs BEFORE any pickled payload is touched: magic/format/version
    mismatches are ignored with a warning + counter, never a crash and
    never a wrong executable."""
    import msgpack

    plan = plan or _plan.active()
    gauge, stale = _metrics()
    want = bundle_version(plan)
    path = path or default_path(plan=plan)
    if not os.path.exists(path):
        return _set_info({"status": "absent", "version": want,
                          "path": path, "plan": _plan.describe(plan),
                          "buckets": {}})
    try:
        with open(path, "rb") as f:
            doc = msgpack.unpackb(f.read(), raw=False)
    except Exception as e:
        stale.inc(reason="corrupt")
        _log().warn("compile bundle undecodable; ignoring",
                    path=path, err=repr(e))
        return _set_info({"status": "corrupt", "version": want,
                          "path": path, "plan": _plan.describe(plan),
                          "buckets": {}})
    if not isinstance(doc, dict) or doc.get("magic") != _MAGIC \
            or doc.get("format") != _FORMAT or doc.get("version") != want:
        stale.inc(reason="version")
        _log().warn(
            "compile bundle version mismatch; ignoring (rebuild will "
            "replace it)", path=path,
            bundle_version=str((doc or {}).get("version"))
            if isinstance(doc, dict) else "?", want=want)
        return _set_info({"status": "stale", "version": want,
                          "path": path, "plan": _plan.describe(plan),
                          "buckets": {}})
    want_mesh = [int(d) for d in plan.mesh_shape]
    got_mesh = [int(d) for d in (doc.get("mesh") or [])]
    if got_mesh != want_mesh:
        # version matches (mesh is deliberately outside the plan hash)
        # but the executables were sharded for a different mesh: running
        # them would be WRONG, not slow — degrade to jit compiles
        stale.inc(reason="mesh")
        _log().warn("compile bundle mesh mismatch; ignoring",
                    path=path, bundle_mesh=got_mesh, want=want_mesh)
        return _set_info({"status": "stale", "version": want,
                          "path": path, "plan": _plan.describe(plan),
                          "buckets": {}})
    import jax
    from jax.experimental import serialize_executable as se

    from . import batch as _b

    _b._jit_env()
    statuses: dict[str, str] = {}
    nd = _plan.mesh_size(plan)
    devs = jax.devices()
    for bucket in _plan.enumerate_buckets(plan):
        k = bucket.key
        if nd > 1 and bucket.kind not in ("tables", "bls_agg"):
            k = f"{k}@m{nd}"
        statuses.setdefault(k, "cold")
    for key, ent in (doc.get("buckets") or {}).items():
        try:
            in_tree, out_tree = pickle.loads(ent["trees"])
            # load onto the devices the bucket was compiled for: the
            # plan's mesh for an @m<D> bucket, the default device
            # otherwise (left alone, jax loads it onto EVERY local
            # device and the first call fails on the shard count)
            _LOADED[key] = se.deserialize_and_load(
                ent["payload"], in_tree, out_tree,
                execution_devices=devs[:nd if "@m" in key else 1])
            statuses[key] = "warm"
        except Exception as e:
            # per-bucket degrade with a REASON in /status (the r13 CPU
            # quirk: executables referencing runtime symbols — "Symbols
            # not found" on the tables kernel — fail cross-process
            # deserialization while the rest of the bundle is fine)
            stale.inc(reason="bucket")
            _log().warn("bundle bucket failed to deserialize; that "
                        "bucket degrades to jit", bucket=key, err=repr(e))
            statuses[key] = "degraded:deserialize"
    return _set_info({
        "status": "loaded",
        "version": want,
        "path": path,
        "plan": _plan.describe(plan),
        "buckets": statuses,
    })


def _set_info(info: dict) -> dict:
    global _INFO
    _INFO = info
    gauge, _ = _metrics()
    warm = sum(1 for s in (info.get("buckets") or {}).values()
               if s == "warm")
    gauge.set(warm, version=str(info.get("version")),
              status=str(info.get("status")))
    return info


def info() -> dict:
    """The current bundle state (the /status ``compile_bundle`` block)."""
    return _INFO


# -------------------------------------------------------------- dispatch


def lookup(key: str):
    """The hot-path consult: the loaded executable for a bucket key, or
    None.  A plain dict hit — callers fall through to their jit cache."""
    return _LOADED.get(key)


def timed_call(key: str, *args):
    """Execute a loaded bucket with first-dispatch instrumentation (the
    PR 5 ``crypto_kernel_first_dispatch_seconds`` gauge — how the bundle
    smoke proves a prewarmed process dispatches at warm latency)."""
    fn = _LOADED[key]
    t0 = time.perf_counter()
    out = fn(*args)
    try:
        import jax

        jax.block_until_ready(out)
    except Exception:
        pass
    dt = time.perf_counter() - t0
    base = key.split("@", 1)[0]          # drop any @m<D> mesh tag
    kind = base.split(":")[0]
    lanes = int(base.split(":")[-1].split("x")[0])
    from .batch import _note_dispatch

    _note_dispatch(kind, lanes, dt)
    return out


def reset() -> None:
    """Test hook: drop loaded executables and state."""
    global _INFO
    _LOADED.clear()
    _INFO = {"status": "absent", "buckets": {}}
