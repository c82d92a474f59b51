"""The declarative device plan: one layer that says HOW verification
work maps onto the machine.

Before r13 the mapping was smeared across two modules: ``crypto/batch.py``
owned the lane/block/table bucket tables, the device set, and the
RLC/min-lane routing thresholds, while ``crypto/scheduler.py`` kept its
own copy of the bucket-snapping math (``snap_lane_cap``).  This module
collapses that into a single declarative :class:`DevicePlan` — the mesh
(device set), the compile-bucket tables (verify lanes x hash blocks,
valset table rows, merkle level widths), and the routing thresholds —
that both the batched verifier and the coalescing scheduler read, and
that the AOT compile-bundle cache (``crypto/aotbundle.py``) enumerates:

- ``active()`` is the live plan; ``configure()``/``set_plan()`` replace
  it (node startup wires ``config.base``/``config.blocksync`` through
  here; the legacy ``crypto/batch`` ``set_*`` hooks now delegate).
- ``bucket``/``bucket_for_lanes``/``buckets_for_batch``/``chunk_bucket``/
  ``snap_lane_cap`` are the ONE copy of the bucket math (``batch.py``
  and ``scheduler.py`` re-export them for their callers).
- :func:`enumerate_buckets` lists every compiled shape the plan implies
  — the warm set a node AOT-lowers into its on-disk bundle, and the
  per-bucket cold/warm status surfaced in ``/status``.
- :func:`plan_hash` fingerprints the declarative fields; the bundle
  loader combines it with the jax/jaxlib/platform fingerprint so a
  stale bundle is ignored, never silently executed
  (``aotbundle.bundle_version``).

Mutable runtime registers deliberately stay where tests and tooling
already poke them: ``TpuBatchVerifier.MIN_DEVICE_LANES`` (the class
attribute IS the live value; ``configure(min_device_lanes=...)`` writes
it) and the device set (moved here from ``batch._DEVICES``;
``batch.set_devices`` delegates).
"""

from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass, field, replace

# Default bucket tables (moved verbatim from crypto/batch.py r12).
# Lane buckets cap at 4096: measured on TPU v5e, verify throughput peaks
# at 2048-4096 lanes and HALVES by 10240 (round 4, older kernels);
# oversized batches chunk at the cap.  Valset TABLE rows bucket
# separately and keep growing past the cap: a cached per-valset table
# must hold every validator (the gather indexes into it, it cannot
# chunk).  Hash-block buckets: a vote sign-bytes message is ~120 B ->
# 2 SHA-512 blocks.  Merkle level widths mirror crypto/merkle.py.
LANE_BUCKETS = (16, 64, 256, 1024, 2048, 4096)
TABLE_BUCKETS = LANE_BUCKETS + (8192, 16384, 32768, 65536)
BLOCK_BUCKETS = (2, 3, 4, 8, 16)
MERKLE_BUCKETS = (256, 1024, 4096)
# BLS cohort-table row buckets (ops/blsg1 masked G1 fold): powers of two
# only — the kernel's tree reduction pads to one anyway, so intermediate
# sizes would compile distinct shapes for identical work
BLS_BUCKETS = (16, 64, 256, 1024, 4096, 16384)


@dataclass(frozen=True)
class DevicePlan:
    """Declarative description of the verification pipeline's device
    mapping.  Frozen: mutate via :func:`configure` (which installs a
    replaced copy), so a plan captured by the AOT bundle or /status can
    never drift under its reader."""

    lane_buckets: tuple = LANE_BUCKETS
    block_buckets: tuple = BLOCK_BUCKETS
    table_buckets: tuple = TABLE_BUCKETS
    merkle_buckets: tuple = MERKLE_BUCKETS
    bls_buckets: tuple = BLS_BUCKETS
    # routing thresholds (crypto/batch dispatch):
    rlc_min_lanes: int = 128        # lanes before the one-shot RLC verdict
    min_device_lanes: int = 1       # below: host crypto even with a device
    # the warm set: the (kind x lanes x blocks) compile buckets a node
    # AOT-lowers into its on-disk bundle.  Deliberately a subset of the
    # full bucket cross-product — every shape costs a multi-minute XLA
    # compile at build time and megabytes in the bundle, so the plan
    # names the shapes the workload actually hits (the same hot shapes
    # node warmup compiled before r13, plus the lane cap the blocksync
    # accumulator fills).
    warm_lanes: tuple = (256, 1024, 4096)
    warm_blocks: tuple = (2,)
    warm_kinds: tuple = ("verify", "rlc")
    warm_merkle: tuple = ()         # merkle level widths to bundle
    # valset TABLE row buckets to bundle: each adds the table-build
    # kernel plus the cached-gather verify/RLC shapes — the route every
    # real commit takes (the node wires the bucket its CURRENT valset
    # lands in, so "first real commit" really is warm)
    warm_tables: tuple = ()
    # BLS aggregation row buckets to bundle (``bls_agg:<rows>`` — the
    # ops/blsg1 masked cohort fold).  Default EMPTY: the host complement
    # fold is already sub-millisecond, and each bls_agg shape is a
    # multi-minute XLA compile; a BLS-heavy deployment opts in with the
    # bucket its valset cohort lands in.
    warm_bls: tuple = ()
    mesh_axis: str = "batch"
    # explicit device-mesh dims for true SPMD dispatch: () = single-device
    # (the pre-r19 behavior), (D,) = one sharded program over the first D
    # visible devices.  Kept OUT of plan_hash so a mesh-shape mismatch is
    # its own bundle-staleness reason (aotbundle reason="mesh"), distinct
    # from a plan change.
    mesh_shape: tuple = ()


@dataclass(frozen=True)
class CompileBucket:
    """One compiled shape the plan implies.  ``key`` is the bundle/
    status identity: ``"<kind>:<lanes>x<blocks>"`` for the plain verify
    kernels, ``"<kind>:<rows>:<lanes>x<blocks>"`` for the cached-table
    gather kernels, ``"tables:<rows>"`` for the table build,
    ``"bls_agg:<rows>"`` for the BLS cohort fold, and
    ``"merkle_level:<lanes>"`` for the tree kernel."""

    kind: str
    lanes: int
    blocks: int = 0
    table_rows: int = 0
    key: str = field(default="")

    def __post_init__(self):
        if not self.key:
            if self.kind == "tables":
                k = f"tables:{self.table_rows}"
            elif self.kind == "bls_agg":
                k = f"bls_agg:{self.table_rows}"
            elif self.table_rows:
                k = (f"{self.kind}:{self.table_rows}:"
                     f"{self.lanes}x{self.blocks}")
            elif self.blocks:
                k = f"{self.kind}:{self.lanes}x{self.blocks}"
            else:
                k = f"{self.kind}:{self.lanes}"
            object.__setattr__(self, "key", k)


def mesh_size(plan: "DevicePlan | None" = None) -> int:
    """Devices the plan's mesh spans (1 when no mesh is declared)."""
    plan = plan or _ACTIVE
    n = 1
    for d in plan.mesh_shape:
        n *= max(1, int(d))
    return n


# Per-kernel sharding labels: which positional argument is lane-sharded
# over the mesh axis and which is replicated to every device.  This
# table is the ONE place the argument layout of the sharded programs is
# declared — parallel/mesh.py turns the labels into NamedShardings and
# crypto/aotbundle.py compiles from the same source, so a bundle's
# executable and the live dispatch can never disagree about layout.
# ``donate`` lists the lane-sharded operands: they are staging copies of
# host arrays (dispatch always re-transfers from numpy), so the runtime
# may reuse their device memory for outputs.
KERNEL_SHARDINGS = {
    # verify_padded(pub, r, s, msgs, active) -> ok[lane]
    "verify": {"in": ("lane",) * 5, "out": "lane",
               "donate": (0, 1, 2, 3, 4)},
    # rlc(pub, r, s, msgs, active, z10) -> the packed window sums, one
    # replicated (20, 386) array (crypto/rlc_finish.py folds them)
    "rlc": {"in": ("lane",) * 6, "out": "repl", "donate": (0, 1, 2, 3, 4)},
    # gather(tables..., ok_active, idx, r, s, msgs, active) -> ok[lane]
    # (the Cached table tuple + precomputed ok row are replicated; the
    # per-lane operands shard)
    "gather": {"in": ("repl", "repl") + ("lane",) * 5, "out": "lane",
               "donate": (2, 3, 4, 5, 6)},
    # rlc_gather(tables..., ok_active, idx, r, s, msgs, active, z10)
    "rlc_gather": {"in": ("repl", "repl") + ("lane",) * 6, "out": "repl",
                   "donate": (2, 3, 4, 5, 6)},
    # merkle_inner_level(left, right) -> parents[lane]
    "merkle_level": {"in": ("lane", "lane"), "out": "lane",
                     "donate": (0, 1)},
}


def lane_sharding(mesh):
    """NamedSharding splitting the leading (lane) axis over the mesh."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))


def replicated_sharding(mesh):
    """NamedSharding replicating an operand to every mesh device."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


def kernel_shardings(kind: str, mesh):
    """(in_shardings, out_shardings, donate_argnums) for ``kind`` on
    ``mesh``, realized from :data:`KERNEL_SHARDINGS`.  Single-entry
    labels expand per positional argument; jit broadcasts a sharding
    over pytree leaves (the Cached table tuple) by prefix matching."""
    spec = KERNEL_SHARDINGS[kind]
    lane, repl = lane_sharding(mesh), replicated_sharding(mesh)
    pick = {"lane": lane, "repl": repl}
    ins = tuple(pick[label] for label in spec["in"])
    out = pick[spec["out"]]
    return ins, out, spec["donate"]


_ACTIVE = DevicePlan()
_DEVICES: tuple | None = None    # explicit device set (config/test hook)


def active() -> DevicePlan:
    return _ACTIVE


def set_plan(plan: DevicePlan, push_min_lanes: bool = True) -> None:
    """Install ``plan`` as the live plan; when ``push_min_lanes``, also
    write the batch verifier's class-level min-lane threshold (the live
    register tests and the legacy ``set_min_device_lanes`` hook poke
    directly — a configure() that did not touch that field leaves the
    register alone so a direct poke stays authoritative)."""
    global _ACTIVE
    _ACTIVE = plan
    if push_min_lanes:
        from . import batch as _b

        _b.TpuBatchVerifier.MIN_DEVICE_LANES = \
            max(1, int(plan.min_device_lanes))


def configure(**overrides) -> DevicePlan:
    """Replace fields of the active plan (node startup / legacy hooks).
    Unknown fields raise — a typo'd knob must not silently no-op."""
    plan = replace(_ACTIVE, **overrides)
    set_plan(plan, push_min_lanes="min_device_lanes" in overrides)
    return plan


def reset() -> None:
    """Test hook: restore the default plan and clear the device set."""
    global _DEVICES
    _DEVICES = None
    set_plan(DevicePlan())


# ------------------------------------------------------------ device set


def set_devices(devices) -> None:
    """Shard every device batch over these devices (None or a single
    device restores single-chip dispatch).  The node wires this from
    config; ``dryrun_multichip`` uses it so the driver artifact
    exercises the production sharded path."""
    global _DEVICES
    _DEVICES = tuple(devices) if devices else None


def resolve_devices(device) -> tuple:
    """Devices a batch should run on: an explicit single device wins,
    then the configured set, then the plan's declared mesh shape (the
    first ``mesh_size`` visible devices — CPU host-device emulation
    included, which is how CI exercises the sharded path), else all
    visible accelerator chips (so a multi-chip host shards
    automatically).  Empty tuple = jit default."""
    if device is not None:
        return (device,)
    if _DEVICES is not None:
        return _DEVICES
    try:
        import jax

        n = mesh_size(_ACTIVE)
        if n > 1:
            devs = tuple(jax.devices())
            if len(devs) >= n:
                return devs[:n]
        accels = tuple(d for d in jax.devices() if d.platform != "cpu")
        return accels if len(accels) > 1 else ()
    except Exception:
        return ()


# ----------------------------------------------------------- bucket math


def bucket(n: int, buckets) -> int:
    """Next bucket >= n; beyond the largest, the exact size (a fresh
    compile for the rare oversized case beats crashing or silent
    truncation)."""
    for b in buckets:
        if n <= b:
            return b
    return n


def bucket_for_lanes(n: int) -> int:
    """The lane bucket a batch of ``n`` signatures compiles into,
    clamped to the cap (bigger batches chunk, so no larger shape is
    ever compiled)."""
    lanes = _ACTIVE.lane_buckets
    return min(bucket(max(1, n), lanes), lanes[-1])


def buckets_for_batch(n: int) -> tuple:
    """EVERY lane bucket a batch of ``n`` signatures will dispatch: the
    dispatch splits past the largest bucket into cap-sized chunks plus a
    remainder, so n=10000 runs the cap shape AND the remainder's bucket
    — warmup/bundling must cover both."""
    lanes = _ACTIVE.lane_buckets
    cap = lanes[-1]
    if n <= cap:
        return (bucket_for_lanes(n),)
    out = {cap}
    rem = n % cap
    if rem:
        out.add(bucket(rem, lanes))
    return tuple(sorted(out))


def chunk_bucket(b: int, devices: tuple) -> int:
    """Lane bucket for a dispatch chunk: next size bucket, rounded up so
    each chip of a mesh takes an equal contiguous slab (power-of-two
    buckets already divide power-of-two meshes).  Past the single-device
    lane cap — a multi-device dispatch chunks at ``cap x mesh`` — the
    global shape is the per-device bucket times the mesh, so every shard
    is itself a compiled bucket shape."""
    lanes = _ACTIVE.lane_buckets
    nd = len(devices)
    if nd > 1 and b > lanes[-1]:
        per = bucket((b + nd - 1) // nd, lanes)
        return per * nd
    bb = bucket(b, lanes)
    if nd > 1:
        bb += (-bb) % nd
    return bb


def snap_lane_cap(n: int) -> int:
    """Largest lane bucket <= n (cap at the largest bucket): a
    size-flushed scheduler batch must exactly fill a shape the kernel
    already compiles, never force a new one.  Values BELOW the smallest
    bucket are honored exactly — any batch that small pads into the
    smallest shape regardless, so the operator's latency intent wins."""
    lanes = _ACTIVE.lane_buckets
    n = max(1, int(n))
    if n <= lanes[0]:
        return n
    snapped = lanes[0]
    for b in lanes:
        if b <= n:
            snapped = b
    return snapped


def mesh_occupancy(n_lanes: int, n_devices: int = 1) -> float:
    """Fraction of the padded compiled shape(s) a batch of ``n_lanes``
    actually fills — the bench's mesh-occupancy figure.  The dispatch
    chunks at the lane cap; each chunk pads up to its bucket (rounded to
    the mesh size), so occupancy = real lanes / padded lanes."""
    if n_lanes <= 0:
        return 0.0
    n_devices = max(1, int(n_devices))
    devices = tuple(range(n_devices))
    # a mesh widens the chunk cap: one sharded dispatch carries a
    # cap-sized slab PER DEVICE, and occupancy is judged against the
    # full-mesh padded shape (not per device)
    cap = _ACTIVE.lane_buckets[-1] * n_devices
    padded = 0
    for start in range(0, n_lanes, cap):
        c = min(start + cap, n_lanes) - start
        padded += chunk_bucket(c, devices if n_devices > 1 else ())
    return n_lanes / padded if padded else 0.0


def window_blocks(base_blocks: int, lanes_per_block: int) -> int:
    """Blocks the blocksync accumulator should stage per verify window
    so ONE sharded dispatch fills the whole mesh.  Without a mesh the
    configured window stands.  With one, the window's lane count snaps
    up to ``mesh_size x lane_bucket``: the per-device share of the base
    window rounds to its bucket, and the window grows (never shrinks) to
    the block count whose lanes fill that full-mesh shape."""
    base_blocks = max(1, int(base_blocks))
    nd = mesh_size(_ACTIVE)
    if nd <= 1 or lanes_per_block <= 0:
        return base_blocks
    lanes = base_blocks * lanes_per_block
    per = bucket_for_lanes((lanes + nd - 1) // nd)
    full = per * nd
    # snap from BELOW: one block past the full-mesh shape would spill
    # into a second padded dispatch and halve occupancy
    return max(base_blocks, full // lanes_per_block)


# --------------------------------------------- compile-bucket enumeration


def enumerate_buckets(plan: DevicePlan | None = None,
                      kinds: tuple | None = None) -> list[CompileBucket]:
    """Every compiled shape the plan's warm set implies — the bundle
    build list and the /status per-bucket ledger.  ``kinds`` restricts
    (the CI smoke bundles only the cheap merkle kernel; a production
    node bundles the verify/RLC shapes too)."""
    plan = plan or _ACTIVE
    want = kinds if kinds is not None else (
        tuple(plan.warm_kinds)
        + (("merkle_level",) if plan.warm_merkle else ())
        + (("tables", "gather", "rlc_gather") if plan.warm_tables
           else ())
        + (("bls_agg",) if plan.warm_bls else ()))
    out: list[CompileBucket] = []
    for kind in plan.warm_kinds:
        if kind not in want:
            continue
        for lanes in plan.warm_lanes:
            for nb in plan.warm_blocks:
                out.append(CompileBucket(kind, lanes, nb))
    # the cached-valset route (the real commit hot path): one table
    # build per row bucket plus every gather shape it feeds
    for rows in plan.warm_tables:
        if "tables" in want:
            out.append(CompileBucket("tables", 0, table_rows=rows))
        for kind in ("gather", "rlc_gather"):
            if kind not in want:
                continue
            for lanes in plan.warm_lanes:
                for nb in plan.warm_blocks:
                    out.append(CompileBucket(kind, lanes, nb,
                                             table_rows=rows))
    if "bls_agg" in want:
        for rows in plan.warm_bls:
            out.append(CompileBucket("bls_agg", 0, table_rows=rows))
    if "merkle_level" in want:
        for lanes in (plan.warm_merkle or plan.merkle_buckets):
            out.append(CompileBucket("merkle_level", lanes))
    return out


def plan_hash(plan: DevicePlan | None = None) -> str:
    """Stable fingerprint of the DECLARATIVE plan fields (no device or
    jax state — ``aotbundle.bundle_version`` folds those in).  Changing
    any bucket table, threshold, or the warm set changes the hash, so a
    bundle built under a different plan can never be loaded."""
    plan = plan or _ACTIVE
    doc = {
        "lane_buckets": list(plan.lane_buckets),
        "block_buckets": list(plan.block_buckets),
        "table_buckets": list(plan.table_buckets),
        "merkle_buckets": list(plan.merkle_buckets),
        "bls_buckets": list(plan.bls_buckets),
        "rlc_min_lanes": plan.rlc_min_lanes,
        "warm": [b.key for b in enumerate_buckets(plan)],
        "mesh_axis": plan.mesh_axis,
    }
    return hashlib.sha256(
        json.dumps(doc, sort_keys=True).encode()).hexdigest()[:16]


def describe(plan: DevicePlan | None = None) -> dict:
    """Operator surface (/status, bundle header): the plan's shape plus
    the live runtime registers it drives."""
    plan = plan or _ACTIVE
    from . import batch as _b

    return {
        "hash": plan_hash(plan),
        "lane_buckets": list(plan.lane_buckets),
        "block_buckets": list(plan.block_buckets),
        "table_buckets": list(plan.table_buckets),
        "merkle_buckets": list(plan.merkle_buckets),
        "bls_buckets": list(plan.bls_buckets),
        "rlc_min_lanes": plan.rlc_min_lanes,
        "min_device_lanes": _b.TpuBatchVerifier.MIN_DEVICE_LANES,
        "mesh_devices": len(_DEVICES) if _DEVICES is not None else None,
        "mesh_axis": plan.mesh_axis,
        "mesh_shape": list(plan.mesh_shape),
        "mesh_size": mesh_size(plan),
        "warm_buckets": [b.key for b in enumerate_buckets(plan)],
    }
