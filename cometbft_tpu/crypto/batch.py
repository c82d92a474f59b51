"""Batch signature verification: the TPU execution backend's seam.

Mirrors ``crypto.BatchVerifier`` (``crypto/crypto.go:44-52``) and the
dispatch in ``crypto/batch/batch.go:10-32``, with the 'tpu' backend the
reference lacks (the north-star of BASELINE.json): signatures accumulate
into dense numpy arrays, pad into (batch, hash-block) *buckets* so XLA
compiles a handful of shapes once, and verify on-device via the vmap'd
ZIP-215 kernel (``ops/ed25519.py``).  Lanes padded to fill a bucket repeat
lane 0 and are sliced away on return.

Unlike the reference — whose batch path refuses mixed key types
(``types/validation.go:18``) — the dispatcher here routes ed25519 lanes to
the device and anything else to per-signature CPU verification, merging
results positionally.

Backend selection: ``create_batch_verifier(backend=...)`` with "auto"
choosing the device backend iff an accelerator is present (the
``config.Config``-driven selection point; falls back to CPU like the
reference's pure-Go path).

Since r13 the bucket tables, device set and routing thresholds are
owned by the declarative device plan (``crypto/plan.py``) — shared with
the coalescing scheduler — and every unpinned single-device dispatch
consults the AOT compile bundle (``crypto/aotbundle.py``) before the
jit caches, so a node booted from a prewarmed bundle runs its first
dispatch at warm latency.
"""

from __future__ import annotations

import contextvars
import functools
import threading
import time
from abc import ABC, abstractmethod
from typing import NamedTuple

import numpy as np

from ..libs import tracing
from . import plan as _plan
from . import rlc_finish
from .keys import ED25519_KEY_TYPE, PubKey, verify_ed25519_zip215

# Bucket tables live in the declarative device plan (crypto/plan.py)
# since r13 — one layer owns the lane/block/table bucketing, the device
# set, and the routing thresholds for BOTH this module and the
# coalescing scheduler.  The names below are READ-ONLY aliases of the
# plan DEFAULTS for existing readers (bench, tests); dispatch reads the
# ACTIVE plan, so assigning to these is a no-op — install a plan via
# plan.set_plan/configure to change bucketing.
_LANE_BUCKETS = _plan.LANE_BUCKETS
_TABLE_BUCKETS = _plan.TABLE_BUCKETS
_BLOCK_BUCKETS = _plan.BLOCK_BUCKETS


class BatchVerifier(ABC):
    """Accumulate (pubkey, msg, sig) triples; verify all at once.

    ``verify()`` returns ``(all_ok, per_sig)`` like the reference's
    ``BatchVerifier.Verify`` (crypto/crypto.go:50-51).
    """

    @abstractmethod
    def add(self, pub: PubKey, msg: bytes, sig: bytes) -> None: ...

    @abstractmethod
    def verify(self) -> tuple[bool, list[bool]]: ...

    def __len__(self) -> int:
        return getattr(self, "_count", 0)


class CpuBatchVerifier(BatchVerifier):
    """Host fallback, used when no accelerator is present.

    ed25519 lanes verify through the native (C++) RLC batch verifier —
    one Pippenger multiscalar multiplication over the whole batch, ~5x a
    single-verify loop, matching the reference's curve25519-voi batch
    path (``crypto/ed25519/ed25519.go:188-221``).  On batch failure (or
    when the native lib is unavailable) lanes verify one by one; other
    key types always verify per-signature."""

    def __init__(self):
        self._items: list[tuple[PubKey, bytes, bytes]] = []

    def add(self, pub, msg, sig):
        self._items.append((pub, bytes(msg), bytes(sig)))

    @property
    def _count(self):
        return len(self._items)

    def verify(self):
        import time as _time

        hist, lanes, calls = _metrics()
        t0 = _time.perf_counter()
        try:
            return self._verify()
        finally:
            hist.observe(_time.perf_counter() - t0, backend="cpu")
            calls.inc(backend="cpu")

    def _verify(self):
        _, lanes, _ = _metrics()
        n = len(self._items)
        oks = [False] * n
        ed_idx = [i for i, (p, _, s) in enumerate(self._items)
                  if p.type() == ED25519_KEY_TYPE and len(s) == 64]
        ed_set = set(ed_idx)
        for i, (p, m, s) in enumerate(self._items):
            if i not in ed_set:
                oks[i] = p.verify_signature(m, s)
        ed_oks = _host_verify_ed25519(
            [self._items[i] for i in ed_idx], lanes, route="cpu")
        for j, i in enumerate(ed_idx):
            oks[i] = ed_oks[j]
        lanes.inc(n - len(ed_idx), route="cpu")
        return all(oks) and n > 0, oks


def _host_verify_ed25519(items, lanes_metric, route: str) -> list[bool]:
    """Host verification of ed25519 lanes (32-byte pubs, 64-byte sigs
    pre-filtered): one native C++ RLC batch when the whole batch is valid
    (the common case), falling back to per-signature verification to
    localize failures — or when the native lib is unavailable.  Shared by
    the CPU backend and every TpuBatchVerifier host-fallback path.
    Successful batches feed the throughput router's host estimate."""
    import time as _time

    from . import _native_ed25519 as _nat

    # >= 2 lanes: one RLC multiscalar beats OpenSSL's asm single verify
    if len(items) >= 2:
        t0 = _time.perf_counter()
        batched = _nat.batch_verify([p.bytes() for p, _, _ in items],
                                    [m for _, m, _ in items],
                                    [s for _, _, s in items])
        if batched:
            _ROUTER.observe("host", len(items), _time.perf_counter() - t0)
            lanes_metric.inc(len(items), route=route + "_batch")
            return [True] * len(items)
    lanes_metric.inc(len(items), route=route)
    return [p.verify_signature(m, s) for p, m, s in items]


# one copy of the bucket math, in the plan layer
_bucket = _plan.bucket


@functools.cache
def _compiled_verify():
    """The jitted kernel; jax.jit's own cache handles per-(batch, nb) shapes.

    The persistent on-disk XLA cache is enabled here — in the LIBRARY, not
    just the test conftest — so a node's first verification at a new
    bucket shape pays the multi-minute compile exactly once per machine,
    not once per process."""
    import jax

    from ..ops import ed25519 as _kernel

    _jit_env()
    return jax.jit(_kernel.verify_padded)


@functools.cache
def _compiled_verify_sharded(devices: tuple):
    """ONE sharded program of the verify kernel over a 1-D mesh of
    ``devices`` (SURVEY §2.10: verification is data-parallel over lanes,
    so the step is collective-free and scales linearly over ICI).
    Shardings + donation come from the plan's labels via the mesh
    authority.  Cached per device tuple; jit's cache handles shapes."""
    from ..parallel.mesh import sharded_kernel

    _jit_env()
    return sharded_kernel("verify", list(devices))


def _jit_env():
    """Every jit entry point enables the persistent XLA cache first, so
    a bucket shape's compile is paid once per machine, not per process."""
    from ..jaxenv import enable_compile_cache

    enable_compile_cache()


@functools.cache
def _compiled_prepare_tables():
    import jax

    from ..ops import ed25519 as _kernel

    _jit_env()
    return jax.jit(_kernel.prepare_pubkey_tables)


@functools.cache
def _compiled_rlc():
    """jit of the one-shot RLC batch verdict (ops/rlc.py)."""
    import jax

    from ..ops import rlc as _r

    _jit_env()
    return jax.jit(_r.verify_batch_rlc)


@functools.cache
def _compiled_rlc_gather():
    """jit of the RLC verdict through a cached whole-valset table."""
    import jax

    from ..ops import rlc as _r

    _jit_env()
    return jax.jit(_r.verify_batch_rlc_gather)


@functools.cache
def _compiled_rlc_sharded(devices: tuple):
    """jit of the lane-sharded RLC verdict over a device mesh: each chip
    reduces its own lane shard to per-window partial sums, a replicated
    add_cc tree folds the D partials, one chip-replicated ladder
    finishes — O(windows) cross-chip points per verdict (the reduction
    the old single-device gate forbade)."""
    from ..parallel.mesh import sharded_kernel

    _jit_env()
    return sharded_kernel("rlc", list(devices))


@functools.cache
def _compiled_rlc_gather_sharded(devices: tuple):
    """Sharded RLC through a replicated cached valset table."""
    from ..parallel.mesh import sharded_kernel

    _jit_env()
    return sharded_kernel("rlc_gather", list(devices))


# RLC dispatch threshold: batches with at least this many ed25519 lanes
# try the one-shot random-linear-combination kernel first (~3x less
# group-op work than the per-lane ladder; all-or-nothing verdict) and
# fall back to the per-lane kernel only to localize a rejection —
# mirroring the native CPU path's batch->single fallback.  Below the
# threshold the per-lane kernel runs directly: tiny batches don't
# amortize the extra compiled shape, and tests keep their compile
# budget.  Multi-device meshes use the lane-sharded RLC variant
# (device-local partial sums + a replicated fold of O(windows) points
# per verdict — ``ops/rlc.py make_verify_batch_rlc_sharded``), so a
# multi-chip host no longer falls back to the ~3x-slower per-lane
# kernel for large all-valid batches.  The threshold lives in the
# device plan since r13.


def _rlc_min_lanes() -> int:
    return _plan.active().rlc_min_lanes


def set_rlc_min_lanes(n: int) -> None:
    """Config hook: minimum ed25519 lanes before the RLC fast path
    (delegates to the device plan — the one routing layer)."""
    _plan.configure(rlc_min_lanes=max(1, int(n)))


def _rlc_args(bb: int, b: int):
    """Coefficient limbs for a padded chunk: fresh CSPRNG draws on the
    ``b`` active lanes, z = 0 on the padding."""
    from ..ops import rlc as _r

    return _r.host_rlc_coeffs(bb, active_mask=np.arange(bb) < b)


@functools.cache
def _compiled_verify_gather(devices: tuple):
    """jit of the cached-table verify: the whole-valset table is
    replicated (every chip gathers its own lanes' rows), the per-lane
    args shard on the lane axis.  devices=() compiles for the default
    single device."""
    import jax

    from ..ops import ed25519 as _kernel

    _jit_env()
    if len(devices) <= 1:
        return jax.jit(_kernel.verify_padded_gather)
    from ..parallel.mesh import sharded_kernel

    return sharded_kernel("gather", list(devices))


# Whole-validator-set device tables, keyed by the identity of the
# valset's cached pubkey matrix (regenerated on membership changes, so
# identity == valset version).  Entries hold a strong ref to the matrix,
# making id() reuse impossible while cached.
_VALSET_TABLES: "dict" = {}
_VALSET_TABLES_MAX = 4
_VALSET_TABLES_LOCK = threading.Lock()
_WARMUP_ACTIVE = False           # warmup_device in progress (executor)
_WARMUP_ARRAYS: list = []        # pubkey matrices owned by warmup


def _valset_tables(pubs_full, devices: tuple):
    """Device [j](-A) tables + ok mask for a full validator set, padded
    to the lane bucket; cached so consecutive commits from the same set
    skip decompression and table building on device."""
    key = (id(pubs_full), devices)
    ent = _VALSET_TABLES.get(key)
    hit = ent is not None and ent[0] is pubs_full
    with tracing.span("crypto.seam", "tables", hit=hit,
                      rows=pubs_full.shape[0]):
        if not hit:
            ent = _build_valset_tables(key, pubs_full, devices)
        return ent[1], ent[2], ent[3]


def _build_valset_tables(key, pubs_full, devices: tuple) -> tuple:
    """A table-cache miss: build on the device, insert, return the entry."""
    n = pubs_full.shape[0]
    nb = _bucket(n, _plan.active().table_buckets)
    if len(devices) > 1:
        nb += (-nb) % len(devices)
    padded = np.zeros((nb, 32), np.int32)
    padded[:n] = pubs_full
    padded[n:] = pubs_full[0] if n else 0
    if len(devices) == 1:
        # pinned single chip: build the table THERE, not on the default
        padded = _put(padded, devices[0])
    fn = None
    if not devices:
        # unpinned default-device build: a bundled table kernel skips
        # the trace+compile on the first valset of a warm-booted node
        from . import aotbundle as _aot

        fn = _aot.lookup(f"tables:{nb}")
    if fn is None:
        fn = _compiled_prepare_tables()
    import jax

    t0 = time.perf_counter()
    # force completion so the timing covers the table-build kernel, not
    # just its enqueue (runs once per valset, not per batch)
    tab, ok = jax.block_until_ready(fn(padded))
    _note_dispatch("tables", nb, time.perf_counter() - t0)
    with _VALSET_TABLES_LOCK:   # warm-up and dispatch threads both insert
        while len(_VALSET_TABLES) >= _VALSET_TABLES_MAX:
            # evict warmup-owned entries first; while warmup itself is
            # running, a real commit's concurrently-inserted table must
            # never be evicted to make room (the cache may exceed its
            # cap until warmup's cleanup drops the fake matrices)
            victim = next(
                (k for k, ent in _VALSET_TABLES.items()
                 if any(ent[0] is a for a in _WARMUP_ARRAYS)), None)
            if victim is None:
                if _WARMUP_ACTIVE:
                    break
                victim = next(iter(_VALSET_TABLES))
            _VALSET_TABLES.pop(victim)
        ent = _VALSET_TABLES[key] = (pubs_full, tab, ok, nb)
    return ent


def device_verify_ed25519_cached(valset_pubs, scope, pubs_rows, rs, ss,
                                 msgs, msg_lens, device=None,
                                 chunks=None) -> np.ndarray:
    """Dense verify through the per-valset table cache: like
    :func:`device_verify_ed25519` but A decompression + table building
    happen once per validator set, not once per batch.  ``scope`` (B,)
    are validator indices into ``valset_pubs``; ``pubs_rows`` (B,32) are
    the gathered pubkey bytes (still needed for the R||A||M hash).

    The chunk loop is software-pipelined: every chunk is packed and its
    first program launched before any is read back (launches are
    asynchronous and the device runs them in order, so it goes from one
    chunk to the next without waiting for the host); the readbacks and
    RLC folds follow in chunk order, and a refuted chunk's ``gather`` is
    launched when its fold says so.  ``chunks``: the call's chunks packed
    already, by a caller that was going to queue
    (:func:`_packed_chunks`); else they are packed here, chunk k+1 while
    chunk k runs."""
    b = pubs_rows.shape[0]
    if b == 0:
        return np.zeros((0,), bool)
    devices = _resolve_devices(device)
    tab, ok, n_pad = _valset_tables(valset_pubs, devices)
    place = _single_device_place(device, devices)
    if chunks is None:
        chunks = _packed_chunks(scope, pubs_rows, rs, ss, msgs, msg_lens,
                                devices)
    flying = []
    for start, end, bb, lane_args, z10 in chunks:
        # steady-state fast path: one RLC verdict over the cached tables
        # (lane-sharded over a multi-chip mesh); below the RLC threshold
        # the per-lane program at once
        args = lane_args if z10 is None else lane_args + (z10,)
        flying.append((start, end, lane_args, _launch_cached(
            z10 is not None, tab, ok, n_pad, args, end - start, bb,
            devices, place)))
        _note_mesh(devices, end - start, bb)
    results = np.zeros((b,), bool)
    for start, end, lane_args, flight in flying:
        c = end - start
        out = _readback(flight)
        if flight.kind.startswith("rlc"):
            if out:
                _metrics()[1].inc(c, route="device_rlc" if len(devices) <= 1
                                  else "device_rlc_sharded")
                results[start:end] = True
                continue
            # a reject falls through to per-lane localization
            out = _readback(_launch_cached(False, tab, ok, n_pad, lane_args,
                                           c, flight.bucket, devices, place))
        results[start:end] = out[:c]
    return results


def _packed_chunks(scope, pubs_rows, rs, ss, msgs, msg_lens, devices: tuple,
                   queued: bool = False):
    """The chunks of a cached-route call, each packed as the consumer
    asks for it: ``(start, end, bucket, lane_args, z10)``.
    :func:`device_verify_ed25519_cached`'s launch loop takes one at a
    time, so chunk k+1 is packed while chunk k runs; a ``queued`` caller
    drains the generator on its own thread, under its wait for the
    dispatch ahead of it (host work only: the table lookup and everything
    that touches the device stay on the device-owner thread)."""
    b = pubs_rows.shape[0]
    # a mesh multiplies the chunk cap: one sharded dispatch carries a
    # cap-sized lane slab per device
    cap = _plan.active().lane_buckets[-1] * max(1, len(devices))
    for start in range(0, b, cap):
        end = min(start + cap, b)
        sl = slice(start, end)
        bb = _chunk_bucket(end - start, devices)
        yield (start, end, bb) + _pack(
            pubs_rows[sl], rs[sl], ss[sl], msgs[sl], msg_lens[sl], bb,
            scope=scope[sl], ahead=queued or start > 0)


def _launch_cached(rlc: bool, tab, ok, n_pad: int, args: tuple, lanes: int,
                   bb: int, devices: tuple, place) -> "_Flight":
    """Launch one cached-route program over a packed chunk: the RLC
    verdict or the per-lane verify, sharded over a mesh, from the AOT
    bundle where it holds the shape."""
    name = "rlc_gather" if rlc else "gather"
    nb_blocks = args[3].shape[1]
    if len(devices) > 1:
        kind = name + "_sharded"
        fn = _aot_fn_mesh(f"{name}:{n_pad}", bb, nb_blocks, devices)
        if fn is None:
            fn = _compiled_rlc_gather_sharded(devices) if rlc \
                else _compiled_verify_gather(devices)
    else:
        kind = name
        fn = _aot_fn(f"{name}:{n_pad}", bb, nb_blocks, place)
        if fn is None:
            fn = _compiled_rlc_gather() if rlc \
                else _compiled_verify_gather(devices)
            if place is not None:
                args = _put(args, place)
    return _launch(kind, fn, (tab, ok, *args), lanes, bb)


# The device set and the bucket-selection math moved into the plan
# layer (r13); these names stay as the public seam callers already use
# (node wiring, dryrun_multichip, tests).
set_devices = _plan.set_devices
_resolve_devices = _plan.resolve_devices
bucket_for_lanes = _plan.bucket_for_lanes
buckets_for_batch = _plan.buckets_for_batch


def warmup_device(lane_buckets=(256, 1024), block_buckets=(2,),
                  device=None, valset_sizes=()) -> int:
    """Pre-compile BOTH verify kernels (plain and cached-table gather —
    the dense VerifyCommit path uses the latter) for the hot bucket
    shapes so the first real commit verification doesn't stall consensus
    for an XLA compile (node startup warmup; shapes beyond these hit the
    persistent cache or compile on demand).  ``valset_sizes`` warms the
    cached-gather route at REAL validator-set scale: the per-valset
    table pads to ``_TABLE_BUCKETS`` (which keeps growing past the lane
    cap), so a 10k-validator commit needs the (16384-row table,
    4096-lane chunk) gather shape — not covered by the square
    lane-bucket warmups below.  Returns the number of shapes compiled;
    a shape the compiler refuses RAISES (the caller decides whether a
    node may run without it — never a silent short count)."""
    import numpy as np

    global _WARMUP_ACTIVE
    done = 0
    # Cleanup must drop only the tables built from warmup's OWN fake
    # valset matrices: a REAL commit can populate the cache concurrently
    # (warmup runs in an executor while the node syncs) and must not
    # lose its tables.  Entries are matched by the identity of the pubs
    # array they were built from — warmup keeps every matrix it passed,
    # and _valset_tables' eviction prefers warmup-owned victims (never
    # evicting a real entry while _WARMUP_ACTIVE).
    warm_arrays = _WARMUP_ARRAYS
    warm_arrays.clear()
    _WARMUP_ACTIVE = True
    try:
        for lanes in lane_buckets:
            for nb in block_buckets:
                pubs = np.zeros((lanes, 32), np.uint8)
                rs = ss = pubs
                # longest message that still fits nb SHA-512 blocks after
                # the 64-byte R||A prefix and 17 bytes of padding
                msg_len = nb * 128 - 64 - 17
                msgs = np.zeros((lanes, msg_len), np.uint8)
                lens = np.full((lanes,), msg_len, np.int64)
                scope = np.zeros((lanes,), np.int64)
                warm_arrays.append(pubs)
                _device_verify_chunk(pubs, rs, ss, msgs, lens, device)
                device_verify_ed25519_cached(pubs, scope, pubs, rs, ss,
                                             msgs, lens, device)
                done += 1
        for n_vals in valset_sizes:
            for nb in block_buckets:
                valset = np.zeros((n_vals, 32), np.uint8)
                rows = np.zeros((n_vals, 32), np.uint8)
                msg_len = nb * 128 - 64 - 17
                msgs = np.zeros((n_vals, msg_len), np.uint8)
                lens = np.full((n_vals,), msg_len, np.int64)
                scope = np.zeros((n_vals,), np.int64)
                warm_arrays.append(valset)
                # drives the real dispatch: one table build at the
                # n_vals TABLE bucket + every chunked gather shape
                device_verify_ed25519_cached(valset, scope, rows, rows,
                                             rows, msgs, lens, device)
                done += 1
    finally:
        _WARMUP_ACTIVE = False
        for k in list(_VALSET_TABLES):    # snapshot: concurrent inserts
            ent = _VALSET_TABLES.get(k)
            if ent is not None and any(ent[0] is a for a in warm_arrays):
                _VALSET_TABLES.pop(k, None)   # warmup matrices aren't real
        warm_arrays.clear()
    return done


def device_verify_ed25519(pubs: np.ndarray, rs: np.ndarray, ss: np.ndarray,
                          msgs: np.ndarray, msg_lens: np.ndarray,
                          device=None) -> np.ndarray:
    """Dense-array entry: verify B ed25519 signatures on device.

    pubs (B,32) u8; rs/ss (B,32) u8 (signature halves); msgs (B,L) u8 padded;
    msg_lens (B,).  Returns (B,) bool.  Pads lanes/blocks to bucket shapes.
    """
    b = pubs.shape[0]
    if b == 0:
        return np.zeros((0,), bool)
    results = np.zeros((b,), bool)
    # chunk anything beyond the largest bucket; a mesh multiplies the
    # cap — one sharded dispatch carries a cap-sized slab per device
    cap = _plan.active().lane_buckets[-1] * \
        max(1, len(_resolve_devices(device)))
    for start in range(0, b, cap):
        end = min(start + cap, b)
        results[start:end] = _device_verify_chunk(
            pubs[start:end], rs[start:end], ss[start:end],
            msgs[start:end], msg_lens[start:end], device)
    return results


_chunk_bucket = _plan.chunk_bucket


def _padded_lane_args(pubs, rs, ss, msgs, msg_lens, bb):
    """The lane/block padding protocol shared by the cached and uncached
    device routes: R||A||M hash-input assembly, lens padding, block
    bucketing, repeat-lane-0 fill, int32 byte matrices.  Returns
    ``(pub32, r32, s32, blocks, active)``."""
    from ..ops import sha512 as _sha

    b = pubs.shape[0]
    # hash input is R || A || M
    hin = np.zeros((bb, 64 + msgs.shape[1]), np.uint8)
    hin[:b, :32] = rs
    hin[:b, 32:64] = pubs
    hin[:b, 64:] = msgs
    lens = np.full((bb,), 64, np.int64)
    lens[:b] = 64 + np.asarray(msg_lens, np.int64)
    hin[b:] = hin[0]
    lens[b:] = lens[0]
    nb = _bucket(int(_sha.max_blocks_for_len(int(lens.max()))),
                 _plan.active().block_buckets)
    blocks, active = _sha.host_pad(hin, lens, nb)

    def pad(a):
        out = np.zeros((bb, 32), np.int32)
        out[:b] = a
        out[b:] = a[0] if b else 0          # repeat lane 0 into padding
        return out

    return pad(pubs), pad(rs), pad(ss), blocks, active


def _pack(pubs, rs, ss, msgs, msg_lens, bb, scope=None, ahead=False):
    """One chunk's kernel arguments, packed under a ``pack`` span on both
    device routes: the padded lane matrices (led by the chunk's table
    rows ``scope`` on the cached route, by the pubkeys otherwise) and,
    from the RLC threshold up, the coefficient draw (else None).
    ``ahead``: packed while the device had earlier work to cover it (on
    a queued caller's thread, or with an earlier chunk of the call
    launched), not with the device waiting for it."""
    b = pubs.shape[0]
    _seam_chunks().inc(prepared="ahead" if ahead else "inline")
    with tracing.span("crypto.seam", "pack", lanes=b, bucket=bb,
                      ahead=ahead) as sp:
        lead, r32, s32, blocks, active = _padded_lane_args(
            pubs, rs, ss, msgs, msg_lens, bb)
        if scope is not None:
            lead = np.zeros((bb,), np.int32)
            lead[:b] = np.asarray(scope, np.int32)
            lead[b:] = lead[0]
        z10 = _rlc_args(bb, b) if b >= _rlc_min_lanes() else None
        if sp is not None:
            sp.attrs["blocks"] = blocks.shape[1]
    return (lead, r32, s32, blocks, active), z10


def _put(tree, place):
    """``jax.device_put`` of a dispatch's arguments under a ``put`` span.
    The copy is an asynchronous enqueue: the span is the host's time in
    it, never a wait for the copy to land."""
    import jax

    with tracing.span("crypto.seam", "put") as sp:
        if sp is not None:
            sp.attrs["bytes"] = sum(
                a.nbytes for a in jax.tree_util.tree_leaves(tree))
        return jax.device_put(tree, place)


class _Flight(NamedTuple):
    """A launched program whose result has not been read back."""
    kind: str
    out: object             # the program's (unready) result
    bucket: int
    launch_s: float         # host time inside the launch


def _launch(kind: str, fn, args: tuple, lanes: int, bb: int) -> _Flight:
    """Start one compiled program: the ``launch`` span ends when the call
    returns its (unready) result."""
    t0 = time.perf_counter()
    with tracing.span("crypto.seam", "launch", kind=kind, lanes=lanes,
                      bucket=bb):
        out = fn(*args)
    return _Flight(kind, out, bb, time.perf_counter() - t0)


def _readback(flight: _Flight) -> np.ndarray:
    """Wait for a launched program: the ``readback`` span ends when the
    verdict is on the host.  The RLC kinds return their per-window sums,
    and the verdict is the host's fold of them (``crypto/rlc_finish.py``),
    a ``finish`` span inside ``readback``.  The dispatch is noted with
    the host's time in its two spans, not with what the host did for
    other chunks between them."""
    kind = flight.kind
    t0 = time.perf_counter()
    with tracing.span("crypto.seam", "readback", kind=kind) as sp:
        out = np.asarray(flight.out)
        if kind.startswith("rlc"):
            with tracing.span("crypto.seam", "finish") as fsp:
                ok, native = rlc_finish.finish(out)
                if fsp is not None:
                    fsp.attrs.update(native=native, ok=ok)
            out = np.bool_(ok)
        if sp is not None:
            sp.attrs["ok"] = bool(out.all())
    _note_dispatch(kind, flight.bucket,
                   flight.launch_s + time.perf_counter() - t0)
    return out


def _run(kind: str, fn, args: tuple, lanes: int, bb: int) -> np.ndarray:
    """One compiled program from launch to verdict."""
    return _readback(_launch(kind, fn, args, lanes, bb))


def _single_device_place(device, devices: tuple):
    """The chip a non-sharded dispatch must pin its arrays to: the
    caller's pin wins, else a configured 1-device set (set_devices must
    actually pin THAT chip), else None for the jit default."""
    if device is not None:
        return device
    return devices[0] if len(devices) == 1 else None


def _aot_fn(kind: str, bb: int, nb: int, place):
    """AOT compile-bundle consult for an unpinned single-device
    dispatch: a bucket loaded from the versioned on-disk bundle skips
    tracing, lowering AND compiling — the warm-boot path.  Pinned
    placements and meshes keep their sharded jits (the serialized
    executable is bound to the default device layout)."""
    if place is not None:
        return None
    from . import aotbundle as _aot

    return _aot.lookup(f"{kind}:{bb}x{nb}")


def _aot_fn_mesh(kind: str, bb: int, nb: int, devices: tuple):
    """AOT compile-bundle consult for a SHARDED dispatch: bundle keys
    carry an ``@m<D>`` mesh tag (and the bundle header records the mesh
    shape), so a serialized 4-device executable can never run on 8."""
    from . import aotbundle as _aot

    return _aot.lookup(f"{kind}:{bb}x{nb}@m{len(devices)}")


def _device_verify_chunk(pubs, rs, ss, msgs, msg_lens, device):
    b = pubs.shape[0]
    devices = _resolve_devices(device)
    bb = _chunk_bucket(b, devices)
    args, z10 = _pack(pubs, rs, ss, msgs, msg_lens, bb)
    nb = args[3].shape[1]           # hash-block bucket of this dispatch
    _note_mesh(devices, b, bb)
    if len(devices) > 1:
        # production multi-chip path: ONE lane-sharded dispatch over the
        # mesh (no per-device fan-out) — RLC verdict first (device-local
        # partial sums, O(windows) cross-chip points), per-lane sharded
        # program to localize a rejection
        if z10 is not None:
            rfn = _aot_fn_mesh("rlc", bb, nb, devices)
            if rfn is None:
                rfn = _compiled_rlc_sharded(devices)
            if _run("rlc_sharded", rfn, args + (z10,), b, bb):
                _metrics()[1].inc(b, route="device_rlc_sharded")
                return np.ones((b,), bool)
        fn = _aot_fn_mesh("verify", bb, nb, devices)
        if fn is None:
            fn = _compiled_verify_sharded(devices)
        return _run("verify_sharded", fn, args, b, bb)[:b]
    place = _single_device_place(device, devices)
    if z10 is not None:
        # one-shot RLC verdict first (the all-valid common case); a
        # reject falls through to the per-lane ladder for localization
        rargs = args + (z10,)
        rfn = _aot_fn("rlc", bb, nb, place)
        if rfn is None:
            rfn = _compiled_rlc()
            if place is not None:
                rargs = _put(rargs, place)
        if _run("rlc", rfn, rargs, b, bb):
            _metrics()[1].inc(b, route="device_rlc")
            return np.ones((b,), bool)
    fn = _aot_fn("verify", bb, nb, place)
    if fn is None:
        fn = _compiled_verify()
        if place is not None:
            args = _put(args, place)
    return _run("verify", fn, args, b, bb)[:b]


@functools.cache
def _metrics():
    """Registered once; cached so the hot verify path pays a dict hit."""
    from ..libs import metrics as m

    return (
        m.histogram("crypto_batch_verify_seconds",
                    "wall time of one BatchVerifier.verify() call"),
        m.counter("crypto_batch_lanes_total",
                  "signature lanes verified, by route (device/cpu)"),
        m.counter("crypto_batch_calls_total", "BatchVerifier.verify calls"),
    )


@functools.cache
def _mesh_metrics():
    """crypto_mesh_*: the sharded-dispatch observability surface — mesh
    width, how full each sharded dispatch runs, and how often dispatch
    takes the sharded vs the single-device program."""
    from ..libs import metrics as m

    return (
        m.gauge("crypto_mesh_devices",
                "devices the verify dispatch spans (1 = single-device)"),
        m.histogram(
            "crypto_mesh_dispatch_occupancy",
            "real lanes / padded full-mesh lanes, per sharded dispatch",
            buckets=(0.25, 0.5, 0.75, 0.85, 0.9, 0.95, 1.0)),
        m.counter("crypto_mesh_dispatch_total",
                  "verify dispatches by route (sharded vs single)"),
    )


@functools.cache
def _seam_chunks():
    """``crypto_seam_chunks_total{prepared}``: chunks packed ``ahead``
    (under earlier device work) against ``inline`` (the device waiting)."""
    from ..libs import metrics as m

    return m.counter(
        "crypto_seam_chunks_total",
        "dispatch chunks packed, by whether earlier device work covered "
        "the packing (ahead) or the device waited for it (inline)")


def _note_mesh(devices: tuple, b: int, bb: int) -> None:
    """Record one dispatch chunk against the mesh series."""
    gauge, occ, total = _mesh_metrics()
    gauge.set(max(1, len(devices)))
    if len(devices) > 1:
        total.inc(1, route="sharded")
        if bb:
            occ.observe(b / bb)
    else:
        total.inc(1, route="single")


# -------------------------------------------------- kernel profiling hooks

@functools.cache
def _kprof():
    """Kernel-profiling series (tentpole: per-bucket compile visibility).

    ``crypto_kernel_first_dispatch_seconds{kind,lanes}`` records the wall
    time of the FIRST in-process dispatch of each compiled shape: a
    multi-second/minute value is a cold XLA compile, a value near the
    dispatch p50 means the persistent compile cache served it.  Later
    dispatches of a seen shape land in
    ``crypto_kernel_dispatch_seconds{kind}``.  Host->device placements
    are the flight recorder's ``crypto.seam/put`` spans."""
    from ..libs import metrics as m

    return (
        m.gauge("crypto_kernel_first_dispatch_seconds",
                "first dispatch wall time per compiled shape "
                "(compile when cold, cache-hit when warm)"),
        m.counter("crypto_kernel_first_dispatch_total",
                  "compiled shapes first-dispatched in this process"),
        m.histogram("crypto_kernel_dispatch_seconds",
                    "device kernel dispatch latency (warm shapes)",
                    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
                             0.05, 0.1, 0.25, 0.5, 1, 2.5)),
    )


_SEEN_SHAPES: set = set()


def _note_dispatch(kind: str, lanes_bucket: int, seconds: float) -> None:
    """Record one compiled-kernel execution: the first (kind, bucket)
    sighting is the compile-or-cache gauge + a flight-recorder event,
    repeats are the warm dispatch histogram."""
    gauge, first, hist = _kprof()
    key = (kind, lanes_bucket)
    if key not in _SEEN_SHAPES:
        _SEEN_SHAPES.add(key)
        gauge.set(seconds, kind=kind, lanes=str(lanes_bucket))
        first.inc(kind=kind)
        tracing.event("crypto.kernel", "first_dispatch", kind=kind,
                      lanes=lanes_bucket, dur_us=int(seconds * 1e6))
    else:
        hist.observe(seconds, kind=kind)


_DEVICE_WAIT_S = 2.0             # max time a verify waits on the device:
#   below the p2p pong timeout (5 s), so even the FIRST wedged dispatch
#   cannot make peers drop the node; a compile that outlasts the wait
#   finishes on the worker thread and the device resumes on a later batch
_DEVICE_POOL = None              # single dispatch thread owning the chip
_DEVICE_INFLIGHT = None          # last submitted future (may be stuck)
_DEVICE_SUBMIT_LOCK = threading.Lock()    # pool creation + submit order


def set_device_wait(seconds: float) -> None:
    """Config hook: bound on how long a verification waits for the
    accelerator before falling back to host crypto."""
    global _DEVICE_WAIT_S
    _DEVICE_WAIT_S = max(0.1, float(seconds))


@functools.cache
def _device_health():
    """Operator-facing device-health surface: a
    gauge that flips 0 when verification is riding the device and 1
    while dispatches are being abandoned to host fallback, plus a
    counter of abandonments.  Cached like _metrics."""
    from ..libs import metrics as m

    return (
        m.gauge("crypto_device_degraded",
                "1 while device dispatches are abandoned (host fallback)"),
        m.counter("crypto_device_abandoned_total",
                  "device dispatches abandoned after the bounded wait"),
    )


_DEGRADED_LOGGED = False         # one-shot transition log, not per-batch
_PATIENT_PREV_LANES = 0          # lanes of the last patient dispatch: the
#   window the NEXT patient caller queues behind (double-buffer depth 2)
_DEVICE_INFLIGHT_DEADLINE = 0.0  # when the in-flight dispatch is overdue


def patient_wait_s(lanes: int) -> float:
    """How long a patient (catch-up) dispatch of ``lanes`` signatures
    may wait on the device before host fallback: the fail-fast bound
    plus the compute of its OWN window AND the window it queues behind
    (the previous patient submission — adjacent windows can be wildly
    asymmetric, so a small tail window must still wait out the deep one
    ahead of it), at a deliberately pessimistic throughput floor.  The
    timeout exists to catch a WEDGED device, not a busy one, so a deep
    accumulated window must never outrun it; the work term is capped so
    a real wedge during catch-up still falls back within a bounded
    delay on top of the configured fail-fast wait."""
    global _PATIENT_PREV_LANES
    floor_sigs_per_s = 1000.0
    total = lanes + _PATIENT_PREV_LANES
    _PATIENT_PREV_LANES = lanes
    return _DEVICE_WAIT_S * 2 + min(56.0, 2.0 * total / floor_sigs_per_s)


def _device_busy() -> bool:
    """Is a dispatch in flight on the device-owner thread (or queued
    for it)?  The next submission waits behind it."""
    fut = _DEVICE_INFLIGHT
    return fut is not None and not fut.done()


def _device_call(fn, patient: float = 0.0):
    """Run ``fn`` (a device dispatch) on the single device-owner thread,
    waiting at most ``_DEVICE_WAIT_S``.  Returns ``fn()``'s result, or
    None when the device is unavailable: a previous call is still running
    (possibly wedged in native code — it cannot be killed, only
    abandoned) or the bounded wait expired.  Callers fall back to host
    verification; if the abandoned call eventually completes, the device
    resumes on a later batch.  This keeps the consensus event loop from
    ever blocking on the accelerator — the TPU is a compute sidecar, not
    a liveness dependency.  Every abandonment increments
    ``crypto_device_abandoned_total`` and holds ``crypto_device_degraded``
    at 1 (with a one-shot log line on the transition) so a node that
    quietly became a CPU node is visible to operators.

    ``patient`` (seconds, 0 = off) is the blocksync accumulator's
    double-buffered staging mode: the caller is a catch-up worker
    thread, not the consensus loop, and WANTS to queue behind the
    window currently verifying on the device (that queuing is the
    transfer/compute overlap).  It skips the in-flight fast-fail and
    waits up to the given bound — sized by the CALLER to the work it
    submitted (:func:`patient_wait_s`), because a deep accumulated
    window legitimately needs many seconds of device compute and must
    not be misread as a wedge.  A genuinely wedged device still
    degrades to host when the bound expires."""
    global _DEVICE_POOL, _DEVICE_INFLIGHT, _DEGRADED_LOGGED, \
        _DEVICE_INFLIGHT_DEADLINE
    import concurrent.futures as cf

    from ..libs import failures

    gauge, abandoned = _device_health()
    with _DEVICE_SUBMIT_LOCK:
        # concurrent staging threads (the double-buffered accumulator)
        # must agree on ONE device-owner executor — two would defeat the
        # queue-behind-the-previous-window serialization
        if _DEVICE_POOL is None:
            _DEVICE_POOL = cf.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="tpu-verify")
    if not patient and _device_busy():
        # fail-fast callers never wait on a busy device; only flag it
        # DEGRADED when the in-flight dispatch is past its own allowed
        # window (a healthy patient catch-up dispatch legitimately holds
        # the device for many seconds — that is busy, not wedged)
        if time.perf_counter() > _DEVICE_INFLIGHT_DEADLINE:
            gauge.set(1)
        return None
    if failures.is_enabled():
        # chaos sites wrap the dispatch ON the device-owner thread so
        # the hang/raise exercises the real bounded-wait + host-fallback
        # machinery (the only way to rehearse a wedged or dying
        # accelerator on a CPU-only box)
        f_hang = failures.fire("device.dispatch.hang")
        f_raise = failures.fire("device.dispatch.raise")
        if f_hang is not None or f_raise is not None:
            inner = fn

            def fn():
                if f_hang is not None:
                    time.sleep(float(f_hang.get("delay",
                                                _DEVICE_WAIT_S + 1.0)))
                if f_raise is not None:
                    raise RuntimeError(
                        "chaos: injected device dispatch failure")
                return inner()
    timeout = patient or _DEVICE_WAIT_S
    queued = None
    if tracing.is_enabled():
        # from submit to the first instruction on the device-owner thread,
        # whose spans then name the caller's open span as their parent
        queued = tracing.begin("crypto.seam", "queue", patient=bool(patient),
                               abandoned=False)
        work, run_in = fn, contextvars.copy_context().run

        def fn():
            tracing.finish(queued)
            return run_in(work)
    with _DEVICE_SUBMIT_LOCK:
        fut = _DEVICE_POOL.submit(fn)
        _DEVICE_INFLIGHT = fut
        _DEVICE_INFLIGHT_DEADLINE = time.perf_counter() + timeout
    try:
        result = fut.result(timeout=timeout)
    except cf.TimeoutError:
        abandoned.inc()
        gauge.set(1)
        if queued is not None:
            queued.attrs["abandoned"] = True
        if not _DEGRADED_LOGGED:
            _DEGRADED_LOGGED = True
            from ..libs import log as _tmlog

            _tmlog.logger("crypto").error(
                "device dispatch abandoned after bounded wait; "
                "verification falling back to host until the device "
                "answers again", wait_s=_DEVICE_WAIT_S)
        return None
    except Exception as e:
        # a dispatch that RAISES (driver crash, runtime error mid-kernel)
        # degrades exactly like one that hangs: host fallback, visible
        # on the same gauge/counter — never an exception on the
        # consensus path
        abandoned.inc()
        gauge.set(1)
        if queued is not None:
            queued.attrs["abandoned"] = True
        if not _DEGRADED_LOGGED:
            _DEGRADED_LOGGED = True
            from ..libs import log as _tmlog

            _tmlog.logger("crypto").error(
                "device dispatch raised; verification falling back to "
                "host until the device answers again", err=repr(e))
        return None
    gauge.set(0)
    if _DEGRADED_LOGGED:
        _DEGRADED_LOGGED = False
        from ..libs import log as _tmlog

        _tmlog.logger("crypto").info("device dispatch recovered")
    return result


class TpuBatchVerifier(BatchVerifier):
    """Device-backed batch verifier behind the ``crypto.BatchVerifier`` seam.

    Ed25519 lanes go to the device kernel; other key types verify on CPU
    (an improvement over the reference, which refuses mixed batches —
    ``types/validation.go:13-19``).
    """

    # batches below this go one-by-one on CPU even with a device present:
    # dispatch overhead dominates tiny batches (config-driven via
    # set_min_device_lanes; the reference's batchVerifyThreshold analogue)
    MIN_DEVICE_LANES = 1

    def __init__(self, device=None, routed: bool = False):
        self._items: list[tuple[PubKey, bytes, bytes]] = []
        self._device = device
        # created under backend="auto": consult the measured router per
        # batch (explicit "tpu"/"jax" pins the device unconditionally)
        self._routed = routed

    def add(self, pub, msg, sig):
        if not isinstance(msg, (bytes, bytearray)):
            raise TypeError("msg must be bytes")
        self._items.append((pub, bytes(msg), bytes(sig)))

    @property
    def _count(self):
        return len(self._items)

    def verify(self):
        import time as _time

        hist, lanes, calls = _metrics()
        t0 = _time.perf_counter()
        try:
            return self._verify()
        finally:
            hist.observe(_time.perf_counter() - t0, backend="device")
            calls.inc(backend="device")

    def _verify(self):
        import time as _time

        n = len(self._items)
        if n == 0:
            return False, []
        _, lanes, _ = _metrics()
        ed_idx = [i for i, (p, _, s) in enumerate(self._items)
                  if p.type() == ED25519_KEY_TYPE and len(s) == 64]
        ed_set = set(ed_idx)
        oks = [False] * n
        for i, (p, m, s) in enumerate(self._items):
            if i not in ed_set:
                oks[i] = p.verify_signature(m, s)
        if n < TpuBatchVerifier.MIN_DEVICE_LANES or (
                self._routed and ed_idx
                and not _ROUTER.prefer_device(len(ed_idx))):
            # tiny batch — or the router measured the host faster at
            # this bucket: host verification (still through the native
            # RLC batch when >= 2 ed lanes, which feeds the router's
            # host estimate)
            ed_oks = _host_verify_ed25519(
                [self._items[i] for i in ed_idx], lanes, route="cpu")
            for j, i in enumerate(ed_idx):
                oks[i] = ed_oks[j]
            lanes.inc(n - len(ed_idx), route="cpu")
            return all(oks) and n > 0, oks
        lanes.inc(len(ed_idx), route="device")
        lanes.inc(n - len(ed_idx), route="cpu")
        if ed_idx:
            # vectorized packing: one frombuffer per FIELD, not per lane
            # (a per-lane loop costs ~100 ms at 10k sigs — on the p50
            # VerifyCommit latency path that dwarfs the device dispatch)
            ed_items = [self._items[i] for i in ed_idx]
            maxlen = max(max(len(m) for _, m, _ in ed_items), 1)
            bsz = len(ed_idx)
            pubs = np.frombuffer(
                b"".join(p.bytes() for p, _, _ in ed_items),
                np.uint8).reshape(bsz, 32)
            sigs = np.frombuffer(
                b"".join(s for _, _, s in ed_items),
                np.uint8).reshape(bsz, 64)
            rs, ss = sigs[:, :32], sigs[:, 32:]
            buf = bytearray(bsz * maxlen)
            lens = np.empty((bsz,), np.int64)
            for j, (_, m, _) in enumerate(ed_items):
                buf[j * maxlen:j * maxlen + len(m)] = m
                lens[j] = len(m)
            msgs = np.frombuffer(bytes(buf), np.uint8).reshape(bsz, maxlen)
            t0 = _time.perf_counter()
            dev = _device_call(lambda: device_verify_ed25519(
                pubs, rs, ss, msgs, lens, self._device))
            if dev is not None:
                _ROUTER.observe("device", bsz, _time.perf_counter() - t0)
            else:
                _ROUTER.observe("device", bsz,
                                max(_DEVICE_WAIT_S,
                                    _time.perf_counter() - t0))
            if dev is None:
                # device busy/stuck/slow: verify these lanes on host (via
                # the native RLC batch) so consensus never waits on the
                # accelerator
                ed_oks = _host_verify_ed25519(
                    [self._items[i] for i in ed_idx], lanes,
                    route="host_fallback")
                for j, i in enumerate(ed_idx):
                    oks[i] = ed_oks[j]
            else:
                for j, i in enumerate(ed_idx):
                    oks[i] = bool(dev[j])
        return all(oks), oks


class _ThroughputRouter:
    """Measured device-vs-host routing (a node must
    never verify slower because a device is merely *present*).  Keeps a
    per-lane-bucket EWMA of observed throughput for each backend and
    prefers the faster one; every 64th decision per bucket deliberately
    explores the non-preferred backend so a backend that got faster
    (device un-wedged, host freed up) is re-measured instead of starved.
    Optimistic start: with no device sample yet, the device is tried
    (its first batches both measure and serve), matching the r4
    behavior until evidence says otherwise."""

    EXPLORE_EVERY = 64
    ALPHA = 0.25                # EWMA weight of the newest sample
    HYSTERESIS = 0.9            # device must be >=90% of host to keep

    def __init__(self):
        self._ewma: dict = {}   # (backend, bucket) -> sigs/s
        self._decisions: dict = {}   # bucket -> decision count

    def observe(self, backend: str, lanes: int, seconds: float) -> None:
        if lanes <= 0 or seconds <= 0:
            return
        key = (backend, bucket_for_lanes(lanes))
        tp = lanes / seconds
        prev = self._ewma.get(key)
        self._ewma[key] = tp if prev is None else (
            (1 - self.ALPHA) * prev + self.ALPHA * tp)

    def prefer_device(self, lanes: int) -> bool:
        bucket = bucket_for_lanes(lanes)
        n = self._decisions.get(bucket, 0)
        self._decisions[bucket] = n + 1
        dev = self._ewma.get(("device", bucket))
        host = self._ewma.get(("host", bucket))
        if dev is None:
            preferred = True           # optimism: measure by serving
        elif host is None:
            preferred = True
        else:
            preferred = dev >= self.HYSTERESIS * host
        if n and n % self.EXPLORE_EVERY == 0 and dev is not None \
                and host is not None:
            return not preferred       # periodic re-measure of the loser
        return preferred

    def snapshot(self) -> dict:
        """Operator surface: observed sigs/s by (backend, bucket)."""
        return {f"{b}:{bk}": v for (b, bk), v in self._ewma.items()}

    def reset(self) -> None:
        self._ewma.clear()
        self._decisions.clear()


_ROUTER = _ThroughputRouter()


def _backend_wants_device(backend: str, device, lanes: int | None = None
                          ) -> bool:
    """Shared backend dispatch for the object and dense paths: should
    this batch attempt the device route?  "jax" is whatever backend JAX
    has (tests use it on the CPU backend); "tpu" means TPU — a process
    whose JAX platform is anything else raises
    :class:`DeviceUnavailable` instead of quietly verifying on XLA:CPU
    under ``route="device"``.  "auto" takes the device iff this process
    found an accelerator, and then additionally consults the measured
    throughput router (``lanes`` given) so a device that is SLOWER than
    the native host path never captures the hot path — "tpu"/"jax" are
    explicit operator overrides and skip the router.  Raises ValueError
    on unknown backend names — misconfigurations must surface
    identically on every path."""
    if backend == "jax":
        return True
    if backend == "cpu":
        return False
    if backend not in ("tpu", "auto"):
        raise ValueError(f"unknown batch-verifier backend {backend!r}")
    dev = device if device is not None else _accelerator_device()
    platform = getattr(dev, "platform", "cpu")
    if backend == "tpu":
        if platform != "tpu":
            raise DeviceUnavailable(
                'signature_backend = "tpu" needs a TPU, but the JAX '
                f"platform of this process is {platform!r}"
                + (" (pinned by JAX_PLATFORMS=cpu)" if _cpu_pinned()
                   else "")
                + '; use "auto" or "cpu" to verify on the host')
        return True
    if platform == "cpu":
        return False
    return _ROUTER.prefer_device(lanes) if lanes is not None else True


def verify_dense(backend: str, pubs, sigs, msgs, lens, device=None,
                 valset_pubs=None, scope=None, patient: bool = False):
    """Dense-array verification behind the same backend dispatch as
    :func:`create_batch_verifier`: ``pubs`` (k,32) u8, ``sigs`` (k,64) u8,
    ``msgs`` (k,L) u8 zero-padded rows, ``lens`` (k,) int — the matrices
    the native sign-bytes builder emits.  All lanes must be ed25519.

    ``valset_pubs``/``scope`` (optional): the FULL validator-set pubkey
    matrix plus this batch's validator indices — lets the device route
    reuse per-valset decompressed-point tables across commits.

    Returns ``(all_ok, oks ndarray)``, or None when no dense-capable
    backend exists (no native lib on a CPU box) — the caller falls back
    to the per-lane object path.  Device wedging degrades to the native
    CPU batch under the same bounded wait as TpuBatchVerifier.
    ``patient`` queues behind an in-flight device dispatch instead of
    host-falling-back (the blocksync accumulator's staging mode; see
    :func:`_device_call`)."""
    k = pubs.shape[0]
    if k == 0:
        return True, np.zeros((0,), bool)
    with tracing.span("crypto.seam", "verify_dense", lanes=k,
                      patient=patient) as sp:
        res, route = _verify_dense_routed(backend, pubs, sigs, msgs, lens,
                                          device, valset_pubs, scope, patient)
        if sp is not None:
            sp.attrs["route"] = route
        return res


def _verify_dense_routed(backend, pubs, sigs, msgs, lens, device,
                         valset_pubs, scope, patient) -> tuple:
    """:func:`verify_dense`'s result and the route that gave it
    (``device``, ``cpu_batch``, ``cpu``; None with no dense backend)."""
    import time as _time

    from . import _native_ed25519 as _nat

    k = pubs.shape[0]
    _, lanes, _ = _metrics()
    if _backend_wants_device(backend, device, lanes=k) \
            and k >= TpuBatchVerifier.MIN_DEVICE_LANES:
        rs = np.ascontiguousarray(sigs[:, :32])
        ss = np.ascontiguousarray(sigs[:, 32:])
        t0 = _time.perf_counter()
        wait = patient_wait_s(k) if patient else 0.0
        if valset_pubs is not None and scope is not None:
            chunks = None
            if patient and _device_busy():
                # about to wait in ``queue`` behind the dispatch in
                # flight: pack under that wait, on this thread, so the
                # device-owner thread launches as soon as it is free
                chunks = list(_packed_chunks(
                    scope, pubs, rs, ss, msgs, lens,
                    _resolve_devices(device), queued=True))
            out = _device_call(lambda: device_verify_ed25519_cached(
                valset_pubs, scope, pubs, rs, ss, msgs, lens, device,
                chunks), patient=wait)
        else:
            out = _device_call(lambda: device_verify_ed25519(
                pubs, rs, ss, msgs, lens, device), patient=wait)
        if out is not None:
            _ROUTER.observe("device", k, _time.perf_counter() - t0)
            lanes.inc(k, route="device")
            return (bool(out.all()), out), "device"
        # device busy/wedged: bounded fallback to the native host batch.
        # Charge the router the full bounded wait so "auto" prefers the
        # host until the device measurably answers again.
        _ROUTER.observe("device", k, max(_DEVICE_WAIT_S,
                                         _time.perf_counter() - t0))
    t0 = _time.perf_counter()
    res = _nat.batch_verify_dense(pubs, sigs, msgs, lens)
    if res is None:
        return None, None
    if res:
        _ROUTER.observe("host", k, _time.perf_counter() - t0)
        lanes.inc(k, route="cpu_batch")
        return (True, np.ones((k,), bool)), "cpu_batch"
    # refuted: localize per lane with the exact native single verify
    oks = np.fromiter(
        (_nat.verify(pubs[i].tobytes(), msgs[i, :int(lens[i])].tobytes(),
                     sigs[i].tobytes()) for i in range(k)), bool, k)
    lanes.inc(k, route="cpu")
    return (bool(oks.all()), oks), "cpu"


class DeviceUnavailable(RuntimeError):
    """The configured backend names a device this process does not have."""


_ACCEL: list | None = None       # [device-or-None] once discovered
_ACCEL_LOCK = threading.Lock()


def _cpu_pinned() -> bool:
    import os

    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def _accelerator_device():
    """First non-CPU jax device of THIS process, or None.  Asked once,
    in process: a child that touched the chip before its parent would be
    exactly the two-processes-on-one-chip fault.  When the environment
    pins CPU (``JAX_PLATFORMS=cpu``) the answer is None without touching
    jax.  A process that DID find a chip is never downgraded; a backend
    that cannot initialize raises to the caller (node start)."""
    global _ACCEL
    if _ACCEL is None:
        with _ACCEL_LOCK:       # one discovery; concurrent callers share
            if _ACCEL is None:
                dev = None
                if not _cpu_pinned():
                    import jax

                    dev = next((d for d in jax.devices()
                                if d.platform != "cpu"), None)
                if dev is None:
                    from ..libs import log as _tmlog

                    _tmlog.logger("crypto").info(
                        "no accelerator in this process: signature "
                        "verification and tree hashing stay on the host")
                _ACCEL = [dev]
    return _ACCEL[0]


def device_info(backend: str) -> dict:
    """The ``/status`` ``verify_device`` block: the configured backend,
    the JAX platform it resolved to, and the route batches take.  Raises
    :class:`DeviceUnavailable` for "tpu" without a TPU (the node-start
    check)."""
    on_device = _backend_wants_device(backend, None)
    dev = None                  # "cpu" looks at no device
    if backend == "jax":
        import jax

        dev = jax.devices()[0]
    elif backend != "cpu":
        dev = _accelerator_device()
    return {
        "backend": backend,
        "platform": None if backend == "cpu"
        else getattr(dev, "platform", "cpu"),
        "device_kind": getattr(dev, "device_kind", None),
        "route": "device" if on_device else "host",
    }


def supports_batch_verifier(pub: PubKey) -> bool:
    """Only ed25519 batches on device (crypto/batch/batch.go:21-31 analogue;
    other key types still *work* in TpuBatchVerifier via the CPU route)."""
    return pub.type() == ED25519_KEY_TYPE


def set_min_device_lanes(n: int) -> None:
    """Config hook: batches smaller than ``n`` verify on CPU even when a
    device is present (latency vs throughput crossover, BASELINE's
    'fallback-to-CPU threshold must be config-driven')."""
    TpuBatchVerifier.MIN_DEVICE_LANES = max(1, int(n))


def create_batch_verifier(backend: str = "auto",
                          device=None) -> BatchVerifier:
    """Backend dispatch (the reference's config.Config selection point).

    backend: "auto" | "tpu" | "jax" | "cpu".  The small-batch CPU
    threshold is process-wide via :func:`set_min_device_lanes`.
    """
    # device=None on the device backends lets the dispatch shard over
    # ALL visible chips (SURVEY §2.10 — multi-chip in the production hot
    # path); a caller-pinned device restores single-chip dispatch
    if _backend_wants_device(backend, device):
        return TpuBatchVerifier(device, routed=(backend == "auto"))
    return CpuBatchVerifier()
