#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the verification path and a live node ONCE on a TPU, through the
entry points a user calls, and checks every answer against the host:

  phase ``verify``  a 150-validator commit (object and dense path), the
                    10,000-validator headline batch (cached-table route
                    with the RLC verdict, and the plain route) with
                    tampered and ZIP-215 edge-case lanes compared lane by
                    lane with the pure-Python oracle, a 32-block blocksync
                    window with one corrupted commit, a 4,096-leaf merkle
                    tree through the device level kernel, and one AOT
                    bundle bucket built, saved, reset, loaded, dispatched;
  phase ``node``    a host-pinned validator child makes blocks, then a
                    ``signature_backend = "tpu"`` full node started the
                    way ``python -m cometbft_tpu start`` starts it joins
                    late, block-syncs the backlog on the device, hands off
                    to consensus and follows.

``--chips 4`` runs instead, and only, the headline batch as ONE sharded
dispatch over four chips against one chip and the oracle.

One process holds the chip from start to end; the one helper process is
pinned to the host (``JAX_PLATFORMS=cpu``, ``signature_backend = "cpu"``).
A compiled ed25519 shape costs one to three minutes of XLA:TPU, so before
the phases the product's own warm-up dispatches (zero-filled lanes through
the public dense entries) run concurrently, one thread per bucket family;
the phases then run serially through the normal seams on warm jit caches.

Every earlier stdout line is one JSON object (plus whatever the node
prints); the LAST line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
and is printed only if every phase passed with zero lanes verified
anywhere but the device.  Any other platform than ``tpu`` exits non-zero
before doing work.  Data comes from ``--seed``; nothing needs a network.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
MSG_LEN = 120                   # ~ a canonical vote's sign bytes: 2 blocks
DEVICE_WAIT_S = 600.0           # wait a cold compile out, never abandon it
# concurrent warm dispatches: 8 compiling at once peaked at 16.7 GB of host
# memory in the sandbox; more than that gains nothing (the RLC kernels, one
# family each, are the critical path)
WARM_WORKERS = max(1, min(8, (os.cpu_count() or 2) - 1))


def emit(**obj) -> None:
    print(json.dumps(obj, default=str), flush=True)


class SmokeFailure(AssertionError):
    """A phase gave an answer that differs from the host's, or fell back."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------------ metrics


def parse_metrics(text: str) -> dict:
    """Prometheus text -> {(name, ((label, value), ...)): float}."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, val = line.rpartition(" ")
        name, _, rest = head.partition("{")
        labels = tuple(sorted(
            (kv.partition("=")[0], kv.partition("=")[2].strip('"'))
            for kv in rest.rstrip("}").split(",") if kv))
        try:
            out[(name, labels)] = float(val)
        except ValueError:
            pass
    return out


def metric(m: dict, name: str, **labels) -> float:
    """Sum of the series of ``name`` whose labels include ``labels``."""
    want = set(labels.items())
    return sum(v for (n, ls), v in m.items()
               if n == name and want <= set(ls))


def local_metrics() -> dict:
    from cometbft_tpu.libs import metrics

    return parse_metrics(metrics.DEFAULT.collect())


def device_health(m: dict) -> dict:
    """The counters that say whether anything was verified elsewhere."""
    return {
        "device_lanes": metric(m, "crypto_batch_lanes_total",
                               route="device"),
        "host_fallback_lanes": metric(m, "crypto_batch_lanes_total",
                                      route="host_fallback"),
        "abandoned": metric(m, "crypto_device_abandoned_total"),
        "degraded": metric(m, "crypto_device_degraded"),
    }


def first_dispatches(m: dict) -> dict:
    """{"<kind>:<lanes>": seconds} of every shape first-dispatched here
    (``crypto_kernel_first_dispatch_seconds``: a cold compile when large,
    the persistent compile cache when small)."""
    return {f"{dict(ls)['kind']}:{dict(ls)['lanes']}": round(v, 3)
            for (n, ls), v in sorted(m.items())
            if n == "crypto_kernel_first_dispatch_seconds"}


class JitLedger:
    """What JAX itself says a run's jit start-up cost and the persistent
    compile cache saved (``jax.monitoring`` events, summed over threads):
    the cache spares the XLA compile, never the Python trace and the
    lowering — which is what a first dispatch from a warm cache costs."""

    DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "backend_compile_s",
        "/jax/compilation_cache/compile_time_saved_sec": "cache_saved_s",
    }
    COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        import jax.monitoring

        self.sums = dict.fromkeys(
            [*self.DURATIONS.values(), *self.COUNTS.values()], 0.0)
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._secs)
        jax.monitoring.register_event_listener(self._event)

    def _add(self, key, amount) -> None:
        if key is not None:
            with self._lock:
                self.sums[key] += amount

    def _secs(self, event: str, secs: float, **kw) -> None:
        self._add(self.DURATIONS.get(event), secs)

    def _event(self, event: str, **kw) -> None:
        self._add(self.COUNTS.get(event), 1)

    def report(self) -> dict:
        with self._lock:
            return {k: round(v, 1) for k, v in self.sums.items()}


# --------------------------------------------------------------------- data


def signed_batch(n_vals: int, n_lanes: int, seed: int) -> dict:
    """A commit-shaped dense batch over a ``n_vals`` validator set:
    distinct keys from ``seed``, ``n_lanes`` of them signing a random
    ~vote-sized message each (the layout ``testing.dense_signature_batch``
    makes, at valset scale and with the fast signer).  The last valset
    rows are the ZIP-215 edge-case keys (``testing.zip215_edge_cases``)
    and the last lanes carry their crafted — valid — signatures."""
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    from cometbft_tpu.testing import zip215_edge_cases

    rng = np.random.default_rng(seed)
    edges = zip215_edge_cases(seed, MSG_LEN)[:max(0, min(4, n_vals - 1))]
    n_plain = n_vals - len(edges)
    privs = [Ed25519PrivKey(rng.bytes(32)) for _ in range(n_plain)]
    valset = np.frombuffer(
        b"".join([p.pub_key().bytes() for p in privs]
                 + [e[1] for e in edges]), np.uint8).reshape(n_vals, 32)
    # scope: a seeded choice of plain validators, then the edge rows
    n_sign = n_lanes - len(edges)
    check(0 < n_sign <= n_plain, "more lanes than validators")
    scope = np.concatenate([
        np.sort(rng.permutation(n_plain)[:n_sign]),
        np.arange(n_plain, n_vals)]).astype(np.int64)
    msgs = [rng.bytes(MSG_LEN) for _ in range(n_sign)]
    sigs = [privs[int(v)].sign(m) for v, m in zip(scope[:n_sign], msgs)]
    msgs += [e[2] for e in edges]
    sigs += [e[3] for e in edges]
    return {
        "valset": valset,
        "scope": scope,
        "pubs": np.ascontiguousarray(valset[scope]),
        "sigs": np.frombuffer(b"".join(sigs), np.uint8)
        .reshape(n_lanes, 64).copy(),
        "msgs": np.frombuffer(b"".join(msgs), np.uint8)
        .reshape(n_lanes, MSG_LEN).copy(),
        "lens": np.full((n_lanes,), MSG_LEN, np.int64),
        "edge_lanes": {n_sign + i: e[0] for i, e in enumerate(edges)},
    }


def tampered(batch: dict, seed: int, last: int | None = None
             ) -> tuple[dict, dict]:
    """The same batch with three ordinary lanes (among the ``last`` ones)
    broken — a flipped signature bit, a changed message, S + L
    (non-canonical S) — and the edge-case lanes kept.  Returns
    ``(batch, {lane: label})``."""
    from cometbft_tpu.crypto import _ed25519_py as ref

    rng = np.random.default_rng(seed + 1)
    n = batch["pubs"].shape[0]
    n_plain = n - len(batch["edge_lanes"])
    lo = max(0, n - (last or n))
    out = dict(batch, sigs=batch["sigs"].copy(), msgs=batch["msgs"].copy())
    labels = dict(batch["edge_lanes"])
    lanes = [lo + int(x) for x in rng.permutation(n_plain - lo)[:3]]
    out["sigs"][lanes[0], 5] ^= 1
    labels[lanes[0]] = "bad_signature"
    out["msgs"][lanes[1], 17] ^= 0x40
    labels[lanes[1]] = "wrong_message"
    s = int.from_bytes(out["sigs"][lanes[2], 32:].tobytes(), "little")
    if s + ref.L < 2**256:
        out["sigs"][lanes[2], 32:] = np.frombuffer(
            (s + ref.L).to_bytes(32, "little"), np.uint8)
        labels[lanes[2]] = "s_ge_l"
    return out, labels


def oracle(batch: dict) -> np.ndarray:
    """Per-lane verdicts of the pure-Python ZIP-215 oracle."""
    from cometbft_tpu.crypto import _ed25519_py as ref

    n = batch["pubs"].shape[0]
    return np.fromiter(
        (ref.verify_zip215(batch["pubs"][i].tobytes(),
                           batch["msgs"][i, :int(batch["lens"][i])]
                           .tobytes(),
                           batch["sigs"][i].tobytes()) for i in range(n)),
        bool, n)


# ------------------------------------------------------------ verify: seams


class Sent:
    """Lanes handed to the device seams by this script (the figure
    ``crypto_batch_lanes_total{route="device"}`` must equal)."""

    lanes = 0


def dense(backend: str, batch: dict, last: int | None = None,
          cached: bool = True, device=None):
    """``verify_dense`` over the ``last`` lanes (default all; the edge
    cases ride at the end) — through the cached whole-valset table route,
    or the plain route."""
    from cometbft_tpu.crypto import batch as cryptobatch

    k = min(last or batch["pubs"].shape[0], batch["pubs"].shape[0])
    sl = slice(batch["pubs"].shape[0] - k, None)
    extra = dict(valset_pubs=batch["valset"], scope=batch["scope"][sl]) \
        if cached else {}
    gave_up = device_health(local_metrics())["abandoned"]
    res = cryptobatch.verify_dense(
        backend, batch["pubs"][sl], batch["sigs"][sl], batch["msgs"][sl],
        batch["lens"][sl], device=device, **extra)
    check(res is not None, "no dense-capable backend (native lib missing?)")
    check(device_health(local_metrics())["abandoned"] == gave_up,
          "a device dispatch raised or was abandoned and its lanes were "
          "verified on the host (the crypto log line above says why)")
    Sent.lanes += k
    return res


def timed(fn, *a, **kw):
    t0 = time.perf_counter()
    out = fn(*a, **kw)
    return out, round(time.perf_counter() - t0, 4)


def corrupt_commit(commit, idx: int):
    """A copy of ``commit`` whose signature ``idx`` has one bit flipped."""
    from cometbft_tpu.types.commit import Commit, CommitSig

    sigs = list(commit.signatures)
    cs = sigs[idx]
    bad = bytearray(cs.signature)
    bad[7] ^= 1
    sigs[idx] = CommitSig(cs.block_id_flag, cs.validator_address,
                          cs.timestamp_ns, bytes(bad))
    return Commit(commit.height, commit.round, commit.block_id, sigs)


def light_scope_size(vals) -> int:
    """Lanes VerifyCommitLight selects from a full commit of ``vals``."""
    _, powers = vals.dense()
    needed = vals.total_voting_power() * 2 // 3
    return int(np.nonzero(np.cumsum(powers) > needed)[0][0]) + 1


def check_commit(backend: str, n_vals: int = 150) -> dict:
    """One commit of ``n_vals`` validators through VerifyCommitLight and
    VerifyCommit (the dense path) and through the BatchVerifier object
    (``create_batch_verifier``), whole and with one signature broken."""
    from cometbft_tpu.crypto.batch import (TpuBatchVerifier,
                                           create_batch_verifier)
    from cometbft_tpu.testing import make_light_chain
    from cometbft_tpu.types import validation as V

    lb = make_light_chain(1, n_vals=n_vals, chain_id="smoke-commit")[0]
    cid, vals, commit = "smoke-commit", lb.validators, lb.commit
    check(vals.dense() is not None, "valset has no dense view")
    # dense path, as consensus and blocksync call it
    V.VerifyCommitLight(cid, vals, commit.block_id, 1, commit,
                        backend=backend, use_cache=False)
    Sent.lanes += light_scope_size(vals)
    _, warm_s = timed(V.VerifyCommit, cid, vals, commit.block_id, 1, commit,
                      backend=backend)
    V.VerifyCommit(cid, vals, commit.block_id, 1, commit, backend="cpu")
    bad_idx = n_vals // 3
    bad = corrupt_commit(commit, bad_idx)
    caught = None
    try:
        V.VerifyCommit(cid, vals, bad.block_id, 1, bad, backend=backend)
    except V.ErrInvalidSignature as e:
        caught = e.idx
    Sent.lanes += 2 * n_vals
    check(caught == bad_idx,
          f"dense VerifyCommit named lane {caught}, not {bad_idx}")

    # object path: what a mixed-key valset's commits go through
    def through_object(c):
        bv = create_batch_verifier(backend)
        check(isinstance(bv, TpuBatchVerifier), "not the device verifier")
        for i, cs in enumerate(c.signatures):
            bv.add(vals.get_by_index(i).pub_key,
                   c.vote_sign_bytes(cid, i), cs.signature)
        Sent.lanes += len(bv)
        return bv.verify()

    ok, oks = through_object(commit)
    check(ok and all(oks), "object path refused a valid commit")
    ok, oks = through_object(bad)
    check(not ok and [i for i, o in enumerate(oks) if not o] == [bad_idx],
          "object path did not localize the broken signature")
    return {"validators": n_vals, "light_lanes": light_scope_size(vals),
            "bad_lane_caught": caught, "warm_verifycommit_s": warm_s}


def check_headline(backend: str, n_vals: int = 10_000, n_lanes: int = 8192,
                   plain_lanes: int = 4096, seed: int = 7) -> dict:
    """The headline width: ``n_lanes`` signatures of a ``n_vals`` set
    through the cached-table route (RLC verdict first), ``plain_lanes``
    through the plain route, then the tampered batch — every lane against
    the oracle, a refuted batch still localized lane by lane."""
    batch = signed_batch(n_vals, n_lanes, seed)
    bad, labels = tampered(batch, seed, last=plain_lanes)
    want = oracle(bad)
    check(all(want[i] for i in batch["edge_lanes"]),
          "oracle refused an edge case")
    check(int((~want).sum()) == len(labels) - len(batch["edge_lanes"]),
          "oracle disagrees with the tampering")
    out = {"validators": n_vals, "lanes": n_lanes,
           "tampered": {str(k): v for k, v in sorted(labels.items())}}
    for name, cached, n in (("cached", True, n_lanes),
                            ("plain", False, min(plain_lanes, n_lanes))):
        ok, oks = dense(backend, batch, n, cached)
        check(ok and oks.all(), f"{name} route refused valid lanes "
              f"{np.nonzero(~oks)[0][:8].tolist()}")
        _, out[f"warm_{name}_s"] = timed(dense, backend, batch, n, cached)
        ok, oks = dense(backend, bad, n, cached)
        diff = np.nonzero(oks != want[-n:])[0]
        check(not ok and diff.size == 0,
              f"{name} route differs from the oracle at lanes "
              f"{(diff + n_lanes - n)[:8].tolist()}")
        out[f"{name}_rejected"] = \
            (np.nonzero(~oks)[0] + n_lanes - n).tolist()
    return out


def check_blocksync(backend: str, n_blocks: int = 32, n_vals: int = 1000,
                    corrupt_block: int = 11) -> dict:
    """One blocksync window: ``n_blocks`` commits of one validator set in
    one device batch (``verify_commits_light_batched``) against per-block
    host VerifyCommitLight; then one commit corrupted, caught at its
    height by both."""
    from cometbft_tpu.testing import make_light_chain
    from cometbft_tpu.types import validation as V

    cid = "smoke-sync"
    chain = make_light_chain(n_blocks, n_vals=n_vals, chain_id=cid)
    vals = chain[0].validators
    items = [(lb.commit.block_id, lb.height, lb.commit) for lb in chain]
    per_block = light_scope_size(vals)
    n = V.verify_commits_light_batched(cid, vals, items, backend=backend)
    _, warm_s = timed(V.verify_commits_light_batched, cid, vals, items,
                      backend=backend)
    Sent.lanes += 2 * n
    check(n == n_blocks * per_block, f"window proved {n} lanes")

    def first_bad_height(its):
        for bid, h, c in its:
            try:
                V.VerifyCommitLight(cid, vals, bid, h, c, backend="cpu",
                                    use_cache=False)
            except V.CommitVerificationError:
                return h
        return None

    check(first_bad_height(items) is None, "host refused a valid block")
    k = corrupt_block - 1
    bid, h, c = items[k]
    bad_items = items[:k] + [(bid, h, corrupt_commit(c, per_block // 2))] \
        + items[k + 1:]
    caught = None
    try:
        V.verify_commits_light_batched(cid, vals, bad_items,
                                       backend=backend)
    except V.ErrBatchItemInvalid as e:
        caught = e.height
    Sent.lanes += n
    check(caught == corrupt_block == first_bad_height(bad_items),
          f"corrupted height {corrupt_block}: device caught {caught}")
    return {"blocks": n_blocks, "validators": n_vals, "lanes": n,
            "corrupted_height": corrupt_block, "caught_height": caught,
            "warm_window_s": warm_s}


def check_merkle(n_leaves: int = 4096, seed: int = 7) -> dict:
    """Root and proofs of a ``n_leaves`` tree through the device level
    kernel against the recursive hashlib reference."""
    from cometbft_tpu.crypto import merkle

    rng = np.random.default_rng(seed)
    items = [rng.bytes(40) for _ in range(n_leaves)]
    check(merkle._kernel_wanted(n_leaves),
          "the device merkle kernel is not engaged at this size")
    root, first_s = timed(merkle.hash_from_byte_slices_fast, items)
    (root2, proofs), warm_s = timed(merkle.proofs_from_byte_slices, items)
    check(merkle._kernel_jits() is not None, "merkle kernel unavailable")
    want_root, want_proofs = merkle.proofs_from_byte_slices_reference(items)
    check(root == root2 == want_root == merkle.hash_from_byte_slices(items),
          "device merkle root differs from hashlib (that is a fork)")
    check(proofs == want_proofs, "device merkle proofs differ")
    check(all(p.verify(root, it) for p, it in zip(proofs[::97], items[::97])),
          "a device-built proof does not verify")
    return {"leaves": n_leaves, "root": root.hex(),
            "first_root_s": first_s, "warm_proofs_s": warm_s}


def check_bundle(backend: str, bad: dict, want: np.ndarray,
                 lanes: int = 256, blocks: int = 2, mesh: int = 0) -> dict:
    """One AOT bundle bucket — the per-lane kernel the refuted batch
    ``bad`` lands in: build, save, ``reset()``, ``load``, then a dispatch
    that must be served by the deserialized executable and give the jit
    path's (and the oracle's, ``want``) verdicts."""
    from cometbft_tpu.crypto import aotbundle
    from cometbft_tpu.crypto import plan as deviceplan

    n = bad["pubs"].shape[0]
    _, jit_oks = dense(backend, bad, n, cached=False)
    plan = deviceplan.DevicePlan(
        warm_kinds=("verify",), warm_lanes=(lanes,), warm_blocks=(blocks,),
        mesh_shape=(mesh,) if mesh else ())
    key = f"verify:{lanes}x{blocks}" + (f"@m{mesh}" if mesh else "")
    path = aotbundle.default_path(plan=plan)
    try:
        built, build_s = timed(aotbundle.build, plan=plan, path=path)
        check(built["buckets"].get(key) == "warm", f"build: {built}")
        aotbundle.reset()
        check(aotbundle.lookup(key) is None, "reset left the bucket")
        loaded, load_s = timed(aotbundle.load, path=path, plan=plan)
        check(loaded["status"] == "loaded"
              and loaded["buckets"].get(key) == "warm",
              f"bundle did not deserialize: {loaded['status']} "
              f"{loaded['buckets']}")
        served = []
        real = aotbundle._LOADED[key]
        aotbundle._LOADED[key] = lambda *a: served.append(1) or real(*a)
        (_, aot_oks), dispatch_s = timed(dense, backend, bad, n,
                                         cached=False)
        check(served, "the dispatch did not consult the loaded bucket")
        check((aot_oks == jit_oks).all() and (aot_oks == want).all(),
              "bundled executable's verdicts differ")
        size = os.path.getsize(path)
    finally:
        aotbundle.reset()
        if os.path.exists(path):    # ~90 MB a bucket: not worth keeping
            os.remove(path)
    return {"bucket": key, "status": loaded["buckets"], "path": path,
            "bytes": size, "build_s": build_s, "load_s": load_s,
            "loaded_dispatch_s": dispatch_s}


# ------------------------------------------------- concurrent warm dispatches


def warm_dispatch(lanes: int, n_vals: int = 0, reject: bool = True,
                  device=None) -> None:
    """One of the product's warm-up dispatches (``batch.warmup_device``
    drives the same public dense entries with zero-filled lanes): the
    plain route at ``lanes``, or with ``n_vals`` the cached-table route —
    table build, RLC verdict, and with ``reject`` (lane 0 carries a
    non-canonical S; all-zero lanes verify under ZIP-215) the per-lane
    kernel that localizes a refuted batch."""
    from cometbft_tpu.crypto import batch as cryptobatch

    z = np.zeros((lanes, 32), np.uint8)
    ss = z.copy()
    if reject:
        ss[0] = 0xFF
    msgs = np.zeros((lanes, MSG_LEN), np.uint8)
    lens = np.full((lanes,), MSG_LEN, np.int64)
    if n_vals:
        cryptobatch.device_verify_ed25519_cached(
            np.zeros((n_vals, 32), np.uint8), np.zeros((lanes,), np.int64),
            z, z, ss, msgs, lens, device)
    else:
        cryptobatch.device_verify_ed25519(z, z, ss, msgs, lens, device)


def warm_concurrently(jobs: list, workers: int) -> dict:
    """Run warm dispatches concurrently (XLA compiles outside the GIL);
    the first failure is raised, not swallowed."""
    import concurrent.futures as cf

    t0 = time.perf_counter()
    with cf.ThreadPoolExecutor(max_workers=workers,
                               thread_name_prefix="smoke-warm") as ex:
        futs = {ex.submit(warm_dispatch, **kw): kw for kw in jobs}
        for fut in cf.as_completed(futs):
            fut.result()
    return {"jobs": len(jobs), "workers": workers,
            "wall_s": round(time.perf_counter() - t0, 1)}


def warm_jobs(sizes: dict) -> list:
    """The bucket families the one-chip phases land in, slowest first
    (a family is one thread: its shapes compile in dispatch order)."""
    commit, sync = sizes["commit_vals"], sizes["sync_vals"]
    cap = sizes["lane_cap"]
    light = (2 * commit) // 3 + 1           # below the RLC threshold
    sync_lanes = sizes["sync_blocks"] * ((2 * sync) // 3 + 1)
    return [
        # headline: cached-table route, then the plain route
        dict(lanes=min(sizes["lanes"], cap), n_vals=sizes["vals"]),
        dict(lanes=min(sizes["plain_lanes"], cap)),
        # blocksync window: full chunks, and the remainder chunk (the
        # node's own 1024-lane warm-up is the same shape at 1,000 rows)
        dict(lanes=min(sync_lanes, cap), n_vals=sync),
        dict(lanes=sync_lanes % cap or cap, n_vals=sync, reject=False),
        dict(lanes=1024, reject=False),     # node warm-up, plain route
        # the 150-validator commit: RLC families, and the per-lane
        # kernels VerifyCommitLight's 101 lanes dispatch directly
        dict(lanes=commit, n_vals=commit),
        dict(lanes=commit),
        dict(lanes=light, n_vals=commit),
        dict(lanes=light),
        # the one-validator test net of phase node
        dict(lanes=16, n_vals=1),
        dict(lanes=16),
    ]


# --------------------------------------------------------------- phase: verify


def native_libs() -> dict:
    """Build (or find built for THIS host's CPU) the native libraries the
    path uses; a failed g++ build is printed, not hidden."""
    from cometbft_tpu import native

    out = {}
    for name in ("ed25519", "kvstore", "secp256k1"):
        t0 = time.perf_counter()
        try:
            native.lib_path(name)
            out[name] = {"built": True,
                         "s": round(time.perf_counter() - t0, 2)}
        except Exception as e:
            out[name] = {"built": False, "error": str(e)[-2000:]}
    return out


def small_refuted(sizes: dict) -> tuple:
    """A refuted batch below the RLC threshold (so its dispatch is the
    per-lane kernel alone), its oracle verdicts and its lane bucket."""
    from cometbft_tpu.crypto import plan as deviceplan

    n = max(12, min(100, sizes["commit_vals"]))
    bad, _ = tampered(signed_batch(n + 8, n, sizes["seed"]), sizes["seed"])
    return bad, oracle(bad), deviceplan.bucket_for_lanes(n)


def phase_verify(backend: str, sizes: dict, workers: int) -> dict:
    from cometbft_tpu.crypto import batch as cryptobatch
    from cometbft_tpu.jaxenv import compile_cache_dir

    cryptobatch.set_device_wait(DEVICE_WAIT_S)
    libs = native_libs()
    emit(phase="verify", step="native_libs", libs=libs,
         compile_cache_dir=compile_cache_dir())
    check(libs["ed25519"]["built"] and libs["kvstore"]["built"],
          "native host libraries did not build")
    before = device_health(local_metrics())
    if workers > 1:
        emit(phase="verify", step="warm",
             **warm_concurrently(warm_jobs(sizes), workers),
             first_dispatch_s=first_dispatches(local_metrics()))
    steps = (
        ("commit", lambda: check_commit(backend, sizes["commit_vals"])),
        ("headline", lambda: check_headline(
            backend, sizes["vals"], sizes["lanes"], sizes["plain_lanes"],
            sizes["seed"])),
        ("blocksync", lambda: check_blocksync(
            backend, sizes["sync_blocks"], sizes["sync_vals"],
            sizes["corrupt_block"])),
        ("merkle", lambda: check_merkle(sizes["leaves"], sizes["seed"])),
        ("bundle", lambda: check_bundle(backend, *small_refuted(sizes))),
    )
    for name, fn in steps:
        sent, on_device = Sent.lanes, device_health(local_metrics())
        res, secs = timed(fn)
        emit(phase="verify", step=name, ok=True, seconds=secs,
             lanes_sent=Sent.lanes - sent,
             device_lanes=device_health(local_metrics())["device_lanes"]
             - on_device["device_lanes"], **res)
    m = local_metrics()
    after = device_health(m)
    firsts = first_dispatches(m)
    summary = {
        "lanes_sent": Sent.lanes,
        "device_lanes": after["device_lanes"] - before["device_lanes"],
        "host_fallback_lanes": after["host_fallback_lanes"],
        "abandoned": after["abandoned"], "degraded": after["degraded"],
        "shapes_first_dispatched": len(firsts),
        "first_dispatch_s_sum": round(sum(firsts.values()), 1),
        "first_dispatch_s": firsts,
    }
    emit(phase="verify", ok=True, **summary)
    check(summary["device_lanes"] == Sent.lanes,
          f"device lanes {summary['device_lanes']} != sent {Sent.lanes}")
    check(after["host_fallback_lanes"] == 0 and after["abandoned"] == 0
          and after["degraded"] == 0,
          f"lanes were verified off the device: {after}")
    return summary


# ----------------------------------------------------------------- phase: node


def rpc(port: int, path: str, timeout: float = 5.0):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/{path}",
                                timeout=timeout) as r:
        body = r.read().decode()
    if path == "metrics":
        return body
    return json.loads(body)["result"]


def height_of(port: int) -> int:
    return int(rpc(port, "status")["sync_info"]["latest_block_height"])


def wait_for(pred, timeout: float, what: str, alive=lambda: True):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        check(alive(), f"process died while waiting for {what}")
        try:
            v = pred()
            if v:
                return v
        except (OSError, ValueError, KeyError):
            pass
        time.sleep(0.25)
    raise SmokeFailure(f"timed out after {timeout:.0f}s waiting for {what}")


def phase_node(backend: str, workdir: str, min_blocks: int = 300,
               follow: int = 5, base_port: int = 29900,
               timeout: float = 420.0, warmup: bool = True) -> dict:
    """A host-pinned validator child makes ``min_blocks`` blocks; then the
    chip node — THIS process, started through the CLI's ``start`` — joins
    late as a full node, block-syncs the backlog, hands off to consensus
    and follows ``follow`` more heights."""
    from cometbft_tpu import cmd
    from cometbft_tpu.config import test_consensus_config
    from cometbft_tpu.e2e.gen import HomeSpec, generate_homes

    val = HomeSpec("val0", base_port, base_port + 1, power=10)
    chip = HomeSpec("chip", base_port + 2, base_port + 3, power=None)

    def tweak(spec, cfg) -> None:
        cfg.consensus = test_consensus_config()
        cfg.instrumentation.watchdog_stall_threshold_s = 0
        if spec.name == "val0":
            cfg.base.signature_backend = "cpu"
            return
        cfg.base.signature_backend = backend
        cfg.base.min_device_lanes = 1
        cfg.base.device_wait_s = DEVICE_WAIT_S
        cfg.base.device_warmup = warmup
        cfg.base.compile_bundle_enable = False
        # a window of 16 one-validator commits lands in the 16-lane
        # bucket the node's warm-up compiles anyway
        cfg.blocksync.verify_window = 16

    generate_homes(
        workdir, [val, chip], "smoke-net", tweak=tweak,
        persistent_peers=lambda s: "" if s.name == "val0"
        else f"tcp://127.0.0.1:{val.p2p_port}")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    log = open(os.path.join(workdir, "val0.log"), "wb")
    child = subprocess.Popen(
        [sys.executable, "-m", "cometbft_tpu", "--home",
         os.path.join(workdir, "val0"), "start"],
        env=env, cwd=workdir, stdout=log, stderr=subprocess.STDOUT)
    result: dict = {}
    node_running = threading.Event()

    def alive() -> bool:
        return child.poll() is None and node_running.is_set()

    def handed_off() -> int:
        sync = rpc(chip.rpc_port, "status")["sync_info"]
        return 0 if sync["catching_up"] else int(sync["latest_block_height"])

    def enough_blocks() -> int:
        h = height_of(val.rpc_port)
        return h if h >= min_blocks else 0

    def watch() -> None:
        """Poll both nodes over RPC while the chip node runs on the main
        thread; always ends by asking the node to stop."""
        try:
            t0 = time.monotonic()
            wait_for(lambda: height_of(chip.rpc_port) >= 1, timeout,
                     "the chip node's first synced block", alive)
            tip = wait_for(handed_off, timeout, "the blocksync handoff",
                           alive)
            result["synced_s"] = round(time.monotonic() - t0, 1)
            result["handoff_height"] = tip
            wait_for(lambda: height_of(chip.rpc_port) >= tip + follow,
                     timeout, f"{follow} heights after the handoff", alive)
            st = rpc(chip.rpc_port, "status")
            result["status"] = {
                "height": int(st["sync_info"]["latest_block_height"]),
                "catching_up": st["sync_info"]["catching_up"],
                "verify_device": st.get("verify_device"),
                "compile_bundle": st.get("compile_bundle"),
                "fatal_error": st["consensus_info"].get("fatal_error"),
            }
            heights = sorted({1, backlog // 2, backlog, tip, tip + follow})
            result["block_ids_equal"] = {
                h: json.dumps(rpc(chip.rpc_port, f"block?height={h}")
                              ["block_id"], sort_keys=True)
                == json.dumps(rpc(val.rpc_port, f"block?height={h}")
                              ["block_id"], sort_keys=True)
                for h in heights}
            result["metrics"] = parse_metrics(rpc(chip.rpc_port, "metrics"))
        except Exception as e:
            result["error"] = repr(e)
        finally:
            if node_running.is_set():       # cmd start: shut down
                os.kill(os.getpid(), signal.SIGTERM)

    try:
        t0 = time.monotonic()
        backlog = wait_for(
            enough_blocks, timeout,
            f"{min_blocks} blocks from the validator",
            lambda: child.poll() is None)
        emit(phase="node", step="validator", blocks=backlog,
             seconds=round(time.monotonic() - t0, 1))
        before = device_health(local_metrics())
        watcher = threading.Thread(target=watch, name="smoke-watch",
                                   daemon=True)
        node_running.set()
        watcher.start()
        try:
            rc = cmd.main(["--home", os.path.join(workdir, "chip"),
                           "start"])
        finally:
            node_running.clear()
        watcher.join(timeout=30)
    finally:
        child.terminate()
        try:
            child.wait(timeout=20)
        except subprocess.TimeoutExpired:
            child.kill()
            child.wait()
        log.close()
    check(rc == 0, f"the chip node's start returned {rc}")
    check("error" not in result, f"node phase: {result.get('error')}")
    after = device_health(result.pop("metrics"))
    summary = dict(
        result, backlog=backlog,
        device_lanes=after["device_lanes"] - before["device_lanes"],
        host_fallback_lanes=after["host_fallback_lanes"],
        abandoned=after["abandoned"], degraded=after["degraded"])
    emit(phase="node", ok=True, **summary)
    st = summary["status"]
    check(st["verify_device"] and st["verify_device"]["route"] == "device"
          and st["fatal_error"] is None and not st["catching_up"],
          f"/status: {st}")
    check(all(summary["block_ids_equal"].values()),
          f"block ids differ from the validator's: "
          f"{summary['block_ids_equal']}")
    check(summary["device_lanes"] > 0, "the node verified nothing on "
          "the device")
    check(after["host_fallback_lanes"] == 0 and after["abandoned"] == 0
          and after["degraded"] == 0,
          f"the node verified lanes off the device: {after}")
    return summary


# -------------------------------------------------------------- --chips 4 path


def run_four_chips(backend: str, sizes: dict, workers: int) -> None:
    """The headline batch and its tampered twin as ONE sharded dispatch
    over four chips — the automatic route (no mesh declared) and the
    declared ``mesh_shape=(4,)`` — against the same batch pinned to one
    chip and against the oracle; the sharded RLC; one ``@m4`` bundle
    bucket; and a look at where the shards really live."""
    import jax

    from cometbft_tpu.crypto import batch as cryptobatch
    from cometbft_tpu.crypto import plan as deviceplan

    cryptobatch.set_device_wait(DEVICE_WAIT_S)
    devs = tuple(jax.devices())
    lanes, n_vals = sizes["lanes"], sizes["vals"]
    check(deviceplan.resolve_devices(None) == devs,
          "a multi-chip host does not shard automatically")
    if workers > 1:
        cap = sizes["lane_cap"]
        jobs = [dict(lanes=lanes, n_vals=n_vals), dict(lanes=lanes),
                dict(lanes=min(lanes, cap), n_vals=n_vals, device=devs[0]),
                dict(lanes=min(lanes, cap), device=devs[0])]
        emit(phase="chips4", step="warm",
             **warm_concurrently(jobs, workers),
             first_dispatch_s=first_dispatches(local_metrics()))
    batch = signed_batch(n_vals, lanes, sizes["seed"])
    bad, labels = tampered(batch, sizes["seed"])
    want = oracle(bad)
    out = {"lanes": lanes, "devices": [str(d) for d in devs]}
    verdicts = {}
    for route in ("auto", "mesh4", "one_chip"):
        if route == "mesh4":
            deviceplan.configure(mesh_shape=(4,))
        device = devs[0] if route == "one_chip" else None
        for cached in (True, False):
            name = f"{route}_{'cached' if cached else 'plain'}"
            ok, oks = dense(backend, batch, lanes, cached, device)
            check(ok and oks.all(), f"{name}: refused valid lanes")
            _, out[f"warm_{name}_s"] = timed(
                dense, backend, batch, lanes, cached, device)
            ok, oks = dense(backend, bad, lanes, cached, device)
            check(not ok and (oks == want).all(),
                  f"{name}: differs from the oracle at "
                  f"{np.nonzero(oks != want)[0][:8].tolist()}")
            verdicts[name] = oks
        if route == "mesh4":
            deviceplan.configure(mesh_shape=())
    check(all((v == verdicts["one_chip_cached"]).all()
              for v in verdicts.values()),
          "sharded and single-chip verdicts differ")
    out["rejected"] = np.nonzero(~want)[0].tolist()
    out["tampered"] = {str(k): v for k, v in sorted(labels.items())}
    m = local_metrics()
    out["sharded_rlc_lanes"] = metric(m, "crypto_batch_lanes_total",
                                      route="device_rlc_sharded")
    out["sharded_dispatches"] = metric(m, "crypto_mesh_dispatch_total",
                                       route="sharded")
    check(out["sharded_rlc_lanes"] >= 4 * lanes
          or lanes < deviceplan.active().rlc_min_lanes,
          "the sharded RLC verdict never accepted a valid batch")
    # where the slabs live: the very program the dispatch runs
    from cometbft_tpu.crypto import aotbundle

    args = aotbundle.sample_args(deviceplan.CompileBucket("verify", lanes, 2))
    res = cryptobatch._compiled_verify_sharded(devs)(*args)
    shards = [(str(s.device), tuple(s.data.shape))
              for s in res.addressable_shards]
    out["shards"] = shards
    check(len({d for d, _ in shards}) == 4
          and all(shape == (lanes // 4,) for _, shape in shards),
          f"the batch is not split over four chips: {shards}")
    emit(phase="chips4", step="sharded", ok=True, **out)
    deviceplan.configure(mesh_shape=(4,))
    try:
        emit(phase="chips4", step="bundle", ok=True,
             **check_bundle(backend, bad, want, lanes=lanes, mesh=4))
    finally:
        deviceplan.configure(mesh_shape=())
    after = device_health(local_metrics())
    firsts = first_dispatches(local_metrics())
    emit(phase="chips4", ok=True, lanes_sent=Sent.lanes, **after,
         first_dispatch_s_sum=round(sum(firsts.values()), 1),
         first_dispatch_s=firsts)
    check(after["device_lanes"] == Sent.lanes
          and after["host_fallback_lanes"] == 0 and after["abandoned"] == 0
          and after["degraded"] == 0,
          f"lanes were verified off the device: {after}, sent {Sent.lanes}")


# ------------------------------------------------------------------------ main


def default_sizes(seed: int) -> dict:
    from cometbft_tpu.crypto import plan as deviceplan

    return {
        "seed": seed,
        "commit_vals": 150,             # BASELINE.json configs[1]
        "vals": 10_000, "lanes": 8192, "plain_lanes": 4096,
        "sync_blocks": 32, "sync_vals": 1000, "corrupt_block": 11,
        "leaves": 4096,
        "lane_cap": deviceplan.active().lane_buckets[-1],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the sharded four-chip path")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax

    import cometbft_tpu  # noqa: F401  (fail here, before any work, if absent)

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found {device}",
              file=sys.stderr)
        return 2
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips}, but JAX found "
              f"{len(devs)} devices", file=sys.stderr)
        return 2
    t0 = time.monotonic()
    jit = JitLedger()
    emit(phase="start", device=device, chips=args.chips, seed=args.seed,
         workers=WARM_WORKERS, jax=jax.__version__)
    sizes = default_sizes(args.seed)
    try:
        if args.chips == 4:
            run_four_chips("tpu", sizes, WARM_WORKERS)
        else:
            phase_verify("tpu", sizes, WARM_WORKERS)
            with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
                phase_node("tpu", tmp)
    except SmokeFailure as e:
        emit(ok=False, error=str(e),
             seconds=round(time.monotonic() - t0, 1))
        return 1
    emit(phase="done", seconds=round(time.monotonic() - t0, 1),
         jit=jit.report())
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
