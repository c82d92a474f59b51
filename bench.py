"""Headline benchmark: 10k-validator ExtendedCommit-shaped signature batch.

Mirrors BASELINE.json's metric ("ed25519 sig-verifies/sec/chip; p50
Commit.VerifyCommit latency @10k vals") and the reference's bench harness
(``crypto/ed25519/bench_test.go:31-67``, which benches BatchVerify at fixed
sig counts): ed25519 signatures over ~120-byte vote-sign-bytes messages,
verified on the accelerator via the ZIP-215 kernel.

In ``commit`` mode two explicit comparison fields are emitted:
``vs_single_loop`` (speedup over a host single-verify loop) and
``vs_reference_batch_est`` (that number / 2 — curve25519-voi's CPU batch
mode runs ~2x its single path, so this estimates the speedup over the
reference's REAL baseline).  ``vs_baseline`` equals the reference-relative
estimate on every backend, so the driver's one JSON line can never be
misread as parity with the reference when it is only parity with our own
single-verify loop.

Process contract: the parent process NEVER imports jax (a parent that
touched JAX would hold the chip its child needs) and runs ONE measuring
child at a time with a hard timeout.  The chip attempt runs unless
``BENCH_BACKEND=cpu`` or ``JAX_PLATFORMS=cpu`` asks for the host (the
full-stack modes always measure the host).  There is no fallback: one
backend is attempted, its JSON line is printed, and a failed attempt
exits non-zero.

``BENCH_MODE`` selects what is measured (default "commit"):
- commit:    10k-validator ExtendedCommit-shaped batch (the headline)
- blocksync: K-block replay with cross-block commit batching vs
             one-commit-per-block (BASELINE configs[4],
             internal/blocksync/reactor.go:495 redesign)
- light:     1000-header sequential light sync on the batched verifier
             (BASELINE configs[3], light/client.go:609 redesign)
- merkle:    10k-leaf root+proofs + part-set proof build through the
             level-order dispatch vs the recursive hashlib reference
- light-serve: one validator serving a simulated skipping-client fleet
             through the light/serve.py tier — proofs/s + request p99
             with /status probed throughout, vs the per-proof re-hash
             baseline
- bls:       the r20 aggregate-commit fast path — BLS aggregate verify
             (two pairings, O(1) in N) vs the Ed25519 batched dense
             path over the 100/1k/10k-validator curve, plus wire sizes
- mesh:      the r19 true-SPMD path — weak-scaling over 1/2/4/8 devices
             (ONE sharded dispatch per bucket), blocksync window
             occupancy, a sharded-vs-single equal-work guard, the
             10k-validator commit p50 at full mesh width, and the
             fresh-process sharded-bundle first-dispatch gauge
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))


# --------------------------------------------------------------------------
# child: does the actual measurement on one backend, prints one JSON line
# --------------------------------------------------------------------------

def _mode_child_setup(tag: str, backend: str):
    """Shared scaffolding for the light/blocksync mode children: stderr
    note(), backend forcing, compile cache, and the same
    claims-TPU-but-got-CPU guard as the commit mode (a CPU box must fail
    the 'tpu' attempt so the parent re-runs it honestly labeled cpu)."""
    def note(msg):
        print(f"[bench:{tag}:{backend}] {msg}", file=sys.stderr, flush=True)

    from cometbft_tpu.jaxenv import enable_compile_cache, force_cpu_backend

    enable_compile_cache()
    if backend == "cpu":
        force_cpu_backend()
        # device kernel emulated on one CPU core is not a meaningful
        # fallback: measure the batching seam over host crypto instead
        return note, "cpu"
    import jax

    if jax.devices()[0].platform == "cpu":
        raise RuntimeError("requested accelerator but got CPU backend")
    return note, "jax"


def _timed_cold_warm(fn):
    t0 = time.perf_counter()
    fn()
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    fn()
    return cold, time.perf_counter() - t0


def _child_light(backend: str, n_headers: int, n_vals: int) -> None:
    """1000-header sequential sync: batched device path vs per-header
    verification (BASELINE configs[3])."""
    note, kernel_backend = _mode_child_setup("light", backend)

    from cometbft_tpu.light import verify_adjacent, verify_sequential_batched
    from cometbft_tpu.testing import make_light_chain

    note(f"building {n_headers}-header chain @ {n_vals} validators")
    chain = make_light_chain(n_headers, n_vals=n_vals)
    now = chain[-1].header.time_ns + 60_000_000_000
    period = 3600 * 10**9

    note("batched sync (cold: includes compile)")
    cold, warm = _timed_cold_warm(lambda: verify_sequential_batched(
        "light-chain", chain[0], chain[1:], period, now,
        backend=kernel_backend))

    note("per-header baseline (host one-by-one)")
    t0 = time.perf_counter()
    prev = chain[0]
    for lb in chain[1:]:
        verify_adjacent("light-chain", prev, lb, period, now, backend="cpu")
        prev = lb
    per_header = time.perf_counter() - t0

    print(json.dumps({
        "metric": "light-client sequential sync, headers/sec "
                  f"({n_headers} headers @ {n_vals} vals, batched)",
        "value": round((n_headers - 1) / warm, 1),
        "unit": "headers/s",
        "vs_baseline": round(per_header / warm, 2),
        "batched_warm_s": round(warm, 3),
        "batched_cold_s": round(cold, 3),
        "per_header_s": round(per_header, 3),
        "backend": backend,
    }), flush=True)


def _child_blocksync(backend: str, n_blocks: int, n_vals: int) -> None:
    """K-block replay: the r13 cross-block ACCUMULATOR (deep
    verify-window dispatches, the shape `blocksync/reactor.py` stages
    during catch-up) vs the r06-r12 per-window baseline (32-block
    dispatches) vs one VerifyCommitLight per block (the reference's loop,
    BASELINE configs[4]).  ``BENCH_CHURN=k`` rotates one validator every
    k blocks, so batching is bounded by same-valset windows exactly like
    the reactor's valset-hash prefix check.  Reports batched vs
    unbatched sig-verifies/s and the mesh-occupancy of the accumulated
    dispatches; writes the JSON to ``BENCH_OUT`` (default
    ``docs/bench/r13-blocksync-mesh-cpu.json``)."""
    note, kernel_backend = _mode_child_setup("bs", backend)

    from cometbft_tpu.crypto import plan as deviceplan
    from cometbft_tpu.testing import make_light_chain
    from cometbft_tpu.types.validation import (VerifyCommitLight,
                                               verify_commits_light_batched)

    churn = int(os.environ.get("BENCH_CHURN", "0"))
    # the old reactor's fixed window vs the accumulator's default-deep one
    win_base = int(os.environ.get("BENCH_WINDOW", "32"))
    win_acc = int(os.environ.get("BENCH_ACC_WINDOW", "256"))
    note(f"building {n_blocks}-block chain @ {n_vals} validators"
         + (f", churn every {churn}" if churn else ""))
    chain = make_light_chain(n_blocks, n_vals=n_vals, rotate_every=churn)
    # group into same-valset runs (the reactor batches exactly such
    # prefixes); without churn this is one run covering the whole chain
    runs = []
    for lb in chain:
        vh = lb.validators.hash()
        if not runs or runs[-1][0] != vh:
            runs.append((vh, lb.validators, []))
        runs[-1][2].append((lb.commit.block_id, lb.height, lb.commit))

    def windowed(depth, occs=None):
        """One full verification pass at the given dispatch depth;
        records per-dispatch lane counts/occupancy in place so the
        TIMED pass supplies the occupancy figure (no extra replay of
        the whole workload just to re-count lanes)."""
        lanes = 0
        for _vh, vals_r, items_r in runs:
            for s in range(0, len(items_r), depth):
                lanes_w = verify_commits_light_batched(
                    "light-chain", vals_r, items_r[s:s + depth],
                    backend=kernel_backend)
                lanes += lanes_w
                if occs is not None:
                    occs.append(deviceplan.mesh_occupancy(lanes_w))
        return lanes

    reps = int(os.environ.get("BENCH_BS_REPS", "3"))

    def best_of(fn):
        # min over reps like the other modes: noise on a shared box must
        # not decide the accumulator-vs-window comparison
        t = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            t = min(t, time.perf_counter() - t0)
        return t

    note(f"accumulated verification (window {win_acc}) over {len(runs)} "
         f"same-valset run(s) (cold: includes compile; best of {reps})")
    occs: list = []
    n_lanes = 0

    def acc_pass():
        nonlocal n_lanes
        occs.clear()
        n_lanes = windowed(win_acc, occs)

    cold, _ = _timed_cold_warm(acc_pass)
    warm = best_of(acc_pass)

    note(f"per-window baseline (window {win_base}, the pre-r13 reactor)")
    warm_win = best_of(lambda: windowed(win_base))

    note("per-block baseline (the reference's loop shape, host crypto)")

    def per_block_pass():
        for lb in chain:
            VerifyCommitLight("light-chain", lb.validators,
                              lb.commit.block_id, lb.height, lb.commit,
                              backend="cpu")

    per_block = best_of(per_block_pass)

    # mesh occupancy of the accumulated dispatches: how full the padded
    # compiled shapes run, averaged over every window the pass dispatches
    occupancy = sum(occs) / len(occs) if occs else 0.0

    result = {
        "metric": "blocksync replay, blocks/sec "
                  f"({n_blocks} blocks @ {n_vals} vals, cross-block "
                  f"accumulator w={win_acc}"
                  + (f", churn@{churn}" if churn else "") + ")",
        "value": round(n_blocks / warm, 1),
        "unit": "blocks/s",
        "vs_baseline": round(per_block / warm, 2),
        "vs_window_baseline": round(warm_win / warm, 2),
        "batched_sigs_per_s": round(n_lanes / warm, 1),
        "window_sigs_per_s": round(n_lanes / warm_win, 1),
        "unbatched_sigs_per_s": round(n_lanes / per_block, 1),
        "mesh_occupancy": round(occupancy, 4),
        "verify_window": win_acc,
        "window_baseline": win_base,
        "batched_warm_s": round(warm, 3),
        "batched_cold_s": round(cold, 3),
        "window_warm_s": round(warm_win, 3),
        "per_block_s": round(per_block, 3),
        "lanes": n_lanes,
        "valset_windows": len(runs),
        "backend": backend,
    }
    out_path = os.environ.get(
        "BENCH_OUT", os.path.join(REPO, "docs", "bench",
                                  "r13-blocksync-mesh-cpu.json"))
    try:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
        note(f"wrote {out_path}")
    except OSError as e:
        note(f"could not write {out_path}: {e}")
    print(json.dumps(result), flush=True)


def _child_verifycommit(backend: str, n_vals: int) -> None:
    """One VerifyCommitLight call at commit scale (BASELINE configs[2]:
    150-validator commit, CPU vs TPU backend through the seam)."""
    note, kernel_backend = _mode_child_setup("vc", backend)

    from cometbft_tpu.testing import make_light_chain
    from cometbft_tpu.types.validation import VerifyCommitLight

    note(f"building one commit @ {n_vals} validators")
    lb = make_light_chain(1, n_vals=n_vals)[0]

    note("seam verification (cold: includes compile)")
    cold, warm = _timed_cold_warm(lambda: VerifyCommitLight(
        "light-chain", lb.validators, lb.commit.block_id, lb.height,
        lb.commit, backend=kernel_backend))

    # Reference-faithful baseline: verifyCommitSingle's per-signature
    # loop (types/validation.go:303 — sign-bytes per lane + one verify
    # each), like the commit mode.  vs_baseline is that speedup / 2, the
    # curve25519-voi CPU-batch estimate — NOT a self-comparison (the r3
    # artifact divided two runs of the same RLC path, so its 0.9 was
    # noise around 1.0 by construction, not a deficit vs the reference).
    note("host baseline: reference-style single-verify loop")
    sigs = lb.commit.signatures
    # same early-exit semantics as the measured path (verifyCommitSingle
    # with countAllSignatures=false stops once tally > 2/3), and min over
    # 3 passes like _single_verify_us so one noisy pass can't inflate
    # the ratio
    needed = lb.validators.total_voting_power() * 2 // 3

    def single_loop():
        tally = 0
        for idx, cs in enumerate(sigs):
            if not cs.is_commit():
                continue
            val = lb.validators.get_by_index(idx)
            msg = lb.commit.vote_sign_bytes("light-chain", idx)
            if not val.pub_key.verify_signature(msg, cs.signature):
                raise RuntimeError("baseline verify failed")
            tally += val.voting_power
            if tally > needed:
                break

    single = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        single_loop()
        single = min(single, time.perf_counter() - t0)
    vs_single = single / warm

    print(json.dumps({
        "metric": f"VerifyCommitLight latency ({n_vals}-validator commit)",
        "value": round(warm * 1e3, 3),
        "unit": "ms",
        "vs_baseline": round(vs_single / 2.0, 2),
        "vs_single_loop": round(vs_single, 2),
        "vs_reference_batch_est": round(vs_single / 2.0, 2),
        "cold_s": round(cold, 3),
        "single_loop_s": round(single, 4),
        "backend": backend,
    }), flush=True)


def _child_stress(backend: str, n_vals: int, secp_pct: int) -> None:
    """BASELINE configs[5]: ExtendedCommit-scale batch with vote
    extensions and mixed secp256k1 keys.  Two signatures per validator
    (precommit + extension); ed25519 lanes ride the device, secp256k1
    lanes take the CPU route inside the same TpuBatchVerifier — the
    mixed-routing improvement over the reference's refusal to batch
    mixed key sets (types/validation.go:13-19)."""
    note, kernel_backend = _mode_child_setup("stress", backend)

    from cometbft_tpu.crypto.batch import create_batch_verifier
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    from cometbft_tpu.crypto.secp256k1 import Secp256k1PrivKey
    from cometbft_tpu.types.block_id import BlockID, PartSetHeader
    from cometbft_tpu.types.canonical import (
        canonical_vote_extension_sign_bytes, canonical_vote_sign_bytes)
    from cometbft_tpu.types.vote import PRECOMMIT_TYPE

    n_secp = n_vals * secp_pct // 100
    note(f"building {n_vals}-val extended commit ({n_secp} secp256k1)")
    bid = BlockID(b"\x11" * 32, PartSetHeader(1, b"\x22" * 32))
    items = []                      # (pub, msg, sig) x2 per validator
    for i in range(n_vals):
        if i < n_secp:
            priv = Secp256k1PrivKey.from_secret(b"stress%d" % i)
        else:
            priv = Ed25519PrivKey.from_secret(b"stress%d" % i)
        sb = canonical_vote_sign_bytes("stress", PRECOMMIT_TYPE, 5, 0,
                                       bid, 1_700_000_000_000_000_000 + i)
        eb = canonical_vote_extension_sign_bytes("stress", 5, 0,
                                                 b"ext%d" % i)
        items.append((priv.pub_key(), sb, priv.sign(sb)))
        items.append((priv.pub_key(), eb, priv.sign(eb)))

    def run_batch():
        bv = create_batch_verifier(kernel_backend)
        for pub, msg, sig in items:
            bv.add(pub, msg, sig)
        ok, _ = bv.verify()
        assert ok

    note("mixed batch verification (cold: includes compile)")
    cold, warm = _timed_cold_warm(run_batch)

    note("host baseline (single verifies, stride-sampled so the "
         "key-type mix matches the batch)")
    sample = items[::max(1, len(items) // 512)]
    t0 = time.perf_counter()
    for pub, msg, sig in sample:
        assert pub.verify_signature(msg, sig)
    host = (time.perf_counter() - t0) / len(sample) * len(items)

    print(json.dumps({
        "metric": f"mixed-key extended-commit verify ({n_vals} vals, "
                  f"{secp_pct}% secp256k1, 2 sigs/val)",
        "value": round(len(items) / warm, 1),
        "unit": "sigs/s",
        "vs_baseline": round(host / warm, 2),
        "p50_batch_latency_ms": round(warm * 1e3, 3),
        "cold_s": round(cold, 3),
        "backend": backend,
    }), flush=True)


def _child_merkle(backend: str, n_leaves: int, block_kb: int) -> None:
    """Merkle subsystem bench: 10k-leaf root+proofs build and a part-set
    proof build, production dispatch vs the recursive hashlib reference
    (the seed implementation).  On an accelerator backend the level
    kernel engages through the normal gate; on cpu the native/hashlib
    engines serve (the kernel measured slower than hashlib on host)."""
    import numpy as np

    def note(msg):
        print(f"[bench:merkle:{backend}] {msg}", file=sys.stderr, flush=True)

    if backend == "cpu":
        from cometbft_tpu.jaxenv import force_cpu_backend

        force_cpu_backend()
    else:
        from cometbft_tpu.jaxenv import enable_compile_cache

        enable_compile_cache()
        import jax

        if jax.devices()[0].platform == "cpu":
            raise RuntimeError("requested accelerator but got CPU backend")

    from cometbft_tpu.crypto import merkle
    from cometbft_tpu.types.part_set import PartSet

    rng = np.random.default_rng(2024)
    leaves = [rng.integers(0, 256, 32, dtype=np.uint8).tobytes()
              for _ in range(n_leaves)]

    def best(fn, reps=5):
        t = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            t = min(t, time.perf_counter() - t0)
        return t

    note(f"{n_leaves}-leaf root+proofs: production dispatch vs recursive")
    ref_root, _ = merkle.proofs_from_byte_slices_reference(leaves)
    root, _ = merkle.proofs_from_byte_slices(leaves)
    assert root == ref_root, "engine mismatch — dispatch is NOT bit-identical"
    t_batched = best(lambda: merkle.proofs_from_byte_slices(leaves))
    t_recursive = best(lambda: merkle.proofs_from_byte_slices_reference(
        leaves))

    note("root-only (app-hash shape)")
    t_root = best(lambda: merkle.hash_from_byte_slices_fast(leaves))
    t_root_ref = best(lambda: merkle.hash_from_byte_slices(leaves))

    note(f"part-set proof build ({block_kb} kB block, 1 kB parts)")
    data = rng.integers(0, 256, block_kb * 1024, dtype=np.uint8).tobytes()
    chunks = [data[i:i + 1024] for i in range(0, len(data), 1024)]
    t_ps = best(lambda: PartSet.from_data(data, part_size=1024))
    t_ps_ref = best(lambda: merkle.proofs_from_byte_slices_reference(chunks))

    print(json.dumps({
        "metric": f"merkle {n_leaves}-leaf root+proofs build "
                  "(level-order dispatch vs recursive hashlib)",
        "value": round(t_batched * 1e3, 3),
        "unit": "ms",
        "vs_baseline": round(t_recursive / t_batched, 2),
        "recursive_ms": round(t_recursive * 1e3, 3),
        "root_only_ms": round(t_root * 1e3, 3),
        "root_only_vs_recursive": round(t_root_ref / t_root, 2),
        "partset_build_ms": round(t_ps * 1e3, 3),
        "partset_vs_recursive": round(t_ps_ref / t_ps, 2),
        "n_leaves": n_leaves,
        "backend": backend,
    }), flush=True)


def _child_p50commit(backend: str, n_vals: int) -> None:
    """BASELINE's latency bar: p50 VerifyCommit @10k validators < 5 ms.
    Times the PRODUCTION dense dispatch (``crypto/batch.verify_dense``
    with the whole-valset cached-table route) end to end — host packing,
    coefficient draw, transfer, kernel, sync — and reports a
    pack/dispatch breakdown so the next latency fix targets the
    measured stage."""
    note, kernel_backend = _mode_child_setup("p50", backend)

    import numpy as np

    from cometbft_tpu.crypto import batch as cb
    from cometbft_tpu.testing import dense_signature_batch

    note(f"building {n_vals}-validator commit-shaped batch")
    args, host_items = dense_signature_batch(n_vals, msg_len=120, seed=77,
                                             n_keys=min(n_vals, 256))
    pubs = np.asarray(args[0], np.uint8)
    sigs = np.concatenate([np.asarray(args[1], np.uint8),
                           np.asarray(args[2], np.uint8)], axis=1)
    msgs = np.stack([np.frombuffer(m, np.uint8).copy()
                     for _, m, _ in host_items])
    lens = np.full((n_vals,), msgs.shape[1], np.int64)
    # a REAL 10k valset has 10k distinct rows; the signing keys repeat
    # (sign cost), but the pubkey matrix identity drives the table cache
    scope = np.arange(n_vals, dtype=np.int64)

    def one_commit():
        out = cb.verify_dense(kernel_backend, pubs, sigs, msgs, lens,
                              valset_pubs=pubs, scope=scope)
        assert out is not None and out[0], "commit batch failed"

    note("cold call (compiles + builds the valset table)")
    cold, _ = _timed_cold_warm(one_commit)
    note(f"cold took {cold:.1f}s; timing warm commits")
    reps = int(os.environ.get("BENCH_REPS", "15"))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        one_commit()
        times.append(time.perf_counter() - t0)
    p50 = float(np.percentile(times, 50))

    # breakdown (device path only — the native CPU route never packs
    # lane matrices): host packing (lane padding + SHA block assembly +
    # RLC coefficient draw) vs everything after dispatch, over the SAME
    # chunk sequence the measured commit actually runs (n_vals > the
    # lane cap dispatches several chunks, each paying its own pack)
    pack_ms = dispatch_ms = None
    if kernel_backend != "cpu":
        cap = cb._LANE_BUCKETS[-1]
        t0 = time.perf_counter()
        for _ in range(reps):
            for start in range(0, n_vals, cap):
                end = min(start + cap, n_vals)
                bb = cb._chunk_bucket(end - start, ())
                sl = slice(start, end)
                cb._padded_lane_args(pubs[sl], sigs[sl, :32],
                                     sigs[sl, 32:], msgs[sl], lens[sl], bb)
                cb._rlc_args(bb, end - start)
        pack_ms = round((time.perf_counter() - t0) / reps * 1e3, 3)
        dispatch_ms = round(p50 * 1e3 - pack_ms, 3)

    print(json.dumps({
        "metric": f"p50 VerifyCommit latency @{n_vals} validators "
                  f"(production dense dispatch)",
        "value": round(p50 * 1e3, 3),
        "unit": "ms",
        # BASELINE bar: < 5 ms p50; >1 means the bar is met
        "vs_baseline": round(5.0 / (p50 * 1e3), 3),
        "p50_ms": round(p50 * 1e3, 3),
        "p90_ms": round(float(np.percentile(times, 90)) * 1e3, 3),
        "pack_ms": pack_ms,
        "dispatch_ms": dispatch_ms,
        "cold_s": round(cold, 3),
        "n_validators": n_vals,
        "backend": backend,
    }), flush=True)


def _child_mesh(backend: str, out_path: str) -> None:
    """True-SPMD mesh bench (r19): every number measured from INSIDE the
    timed pass of the production dispatch, on ONE sharded program per
    bucket over an explicit device mesh.

    Sections of the artifact:
    - weak_scaling: the same per-device lane load (BENCH_MESH_LANES,
      default 256) over 1/2/4/8 devices (CPU host-device emulation
      locally, real chips when present) — per-bucket p50, occupancy,
      sigs/s.
    - window: the staged-window lane count the mesh-aware blocksync
      accumulator produces (plan.window_blocks) and its full-mesh
      occupancy (acceptance: >= 0.85).
    - equal_work_guard: the full-mesh lane count dispatched sharded vs
      single-device; the child EXITS NONZERO if sharded is slower than
      BENCH_MESH_TOL x single (default 1.25 on CPU emulation, 1.0 on a
      real accelerator).
    - commit10k: the BASELINE headline — p50 VerifyCommit @10k
      validators through the cached-valset route at full mesh width,
      recorded against the <5ms / >=20x-Go-batch targets.
    - first_dispatch: a sharded rlc bundle built here must load in a
      FRESH process and dispatch < 1s on the PR 5
      crypto_kernel_first_dispatch_seconds gauge.

    TPU projection methodology (for the committed CPU artifact): the
    emulated host devices SHARE the box's physical cores, so
    per-dispatch latency cannot drop with mesh width here — on CPU the
    weak-scaling curve validates that the sharded program adds no
    overhead (flat-ish p50 at D x the work = near-linear weak scaling),
    and the equal-work guard enforces the invariant that must hold on
    any backend.  The <5ms absolute bar is a per-chip-throughput
    number: project it from a real chip's single-device sigs/s times
    the mesh width (lanes are independent; the RLC fold crosses
    O(windows) points per verdict), then confirm on hardware with this
    same mode, which runs unchanged on a TPU host.
    """
    counts = sorted({int(x) for x in os.environ.get(
        "BENCH_MESH_COUNTS", "1,2,4,8").split(",") if int(x) > 0})
    if backend == "cpu":
        # BEFORE any jax import: the weak-scaling sweep needs emulated
        # host devices on a CPU-only box
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count"
                f"={max(counts)}").strip()
    note, _ = _mode_child_setup("mesh", backend)

    import dataclasses
    import tempfile

    import jax
    import numpy as np

    from cometbft_tpu.crypto import aotbundle
    from cometbft_tpu.crypto import batch as cb
    from cometbft_tpu.crypto import plan as deviceplan
    from cometbft_tpu.testing import dense_signature_batch

    ndev = len(jax.devices())
    counts = [c for c in counts if c <= ndev] or [1]
    per_dev = int(os.environ.get("BENCH_MESH_LANES", "256"))
    reps = int(os.environ.get("BENCH_MESH_REPS", "7"))
    max_d = max(counts)
    max_lanes = per_dev * max_d
    note(f"devices={ndev} counts={counts} per_device_lanes={per_dev}")

    note(f"building {max_lanes}-lane all-valid batch")
    args, items = dense_signature_batch(max_lanes, msg_len=120, seed=19,
                                        n_keys=256)
    pubs = np.asarray(args[0], np.uint8)
    rs8 = np.asarray(args[1], np.uint8)
    ss8 = np.asarray(args[2], np.uint8)
    msgs = np.stack([np.frombuffer(m, np.uint8).copy()
                     for _, m, _ in items])
    lens = np.full((max_lanes,), msgs.shape[1], np.int64)

    def set_mesh(d):
        deviceplan.configure(mesh_shape=(d,) if d > 1 else ())

    def run_lanes(n):
        out = cb.device_verify_ed25519(pubs[:n], rs8[:n], ss8[:n],
                                       msgs[:n], lens[:n])
        assert bool(out.all()), "all-valid batch rejected"

    def timed_pass(fn):
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            times.append(time.perf_counter() - t0)
        return (float(np.percentile(times, 50)),
                float(np.percentile(times, 90)))

    # ---- weak scaling: per-device load held constant over mesh width
    weak = []
    for d in counts:
        set_mesh(d)
        lanes = per_dev * d
        bb = deviceplan.chunk_bucket(
            lanes, tuple(range(d)) if d > 1 else ())
        note(f"[weak] D={d} lanes={lanes} bucket={bb}: cold dispatch")
        cold, _ = _timed_cold_warm(lambda: run_lanes(lanes))
        p50, p90 = timed_pass(lambda: run_lanes(lanes))
        weak.append({
            "devices": d, "lanes": lanes, "bucket": bb,
            "occupancy": round(deviceplan.mesh_occupancy(lanes, d), 4),
            "cold_s": round(cold, 3),
            "p50_ms": round(p50 * 1e3, 3),
            "p90_ms": round(p90 * 1e3, 3),
            "sigs_per_s": round(lanes / p50, 1),
        })
        note(f"[weak] D={d} p50={p50 * 1e3:.2f}ms "
             f"{lanes / p50:,.0f} sigs/s")
    for w in weak:
        w["scaling_vs_1dev"] = round(
            w["sigs_per_s"] / weak[0]["sigs_per_s"], 3)

    # ---- the blocksync staged-window workload at full mesh width
    set_mesh(max_d)
    bs_vals = int(os.environ.get("BENCH_MESH_WINDOW_VALS", "100"))
    bs_window = int(os.environ.get("BENCH_MESH_WINDOW", "32"))
    blocks = deviceplan.window_blocks(bs_window, bs_vals)
    win_lanes = blocks * bs_vals
    window = {
        "verify_window": bs_window, "n_vals": bs_vals,
        "staged_blocks": blocks, "lanes": win_lanes,
        "occupancy": round(
            deviceplan.mesh_occupancy(win_lanes, max_d), 4),
    }
    note(f"[window] {bs_window} cfg blocks x {bs_vals} vals -> "
         f"{blocks} staged blocks, occupancy {window['occupancy']}")

    # ---- equal-work guard: full-mesh lanes, sharded vs single-device
    tol = float(os.environ.get(
        "BENCH_MESH_TOL", "1.25" if backend == "cpu" else "1.0"))
    sharded_p50 = weak[-1]["p50_ms"]
    set_mesh(1)
    note(f"[guard] single-device equal work: {max_lanes} lanes")
    _timed_cold_warm(lambda: run_lanes(max_lanes))
    sp50, _ = timed_pass(lambda: run_lanes(max_lanes))
    guard = {
        "lanes": max_lanes,
        "sharded_p50_ms": sharded_p50,
        "single_p50_ms": round(sp50 * 1e3, 3),
        "tol": tol,
        "ratio": round(sharded_p50 / (sp50 * 1e3), 3),
        "ok": bool(sharded_p50 <= tol * sp50 * 1e3),
    }
    note(f"[guard] sharded/single = {guard['ratio']} (tol {tol})")

    # ---- BASELINE headline: 10k-validator commit p50, cached route
    n_vals = int(os.environ.get("BENCH_MESH_VALS", "10000"))
    commit = None
    if n_vals > 0:
        note(f"[commit] building {n_vals}-validator commit batch")
        cargs, citems = dense_signature_batch(n_vals, msg_len=120,
                                              seed=77, n_keys=256)
        cp = np.asarray(cargs[0], np.uint8)
        cr = np.asarray(cargs[1], np.uint8)
        cs = np.asarray(cargs[2], np.uint8)
        cm = np.stack([np.frombuffer(m, np.uint8).copy()
                       for _, m, _ in citems])
        cl = np.full((n_vals,), cm.shape[1], np.int64)
        scope = np.arange(n_vals, dtype=np.int64)

        def one_commit():
            out = cb.device_verify_ed25519_cached(cp, scope, cp, cr, cs,
                                                  cm, cl)
            assert bool(out.all()), "commit batch rejected"

        commit = {"n_validators": n_vals, "target_p50_ms": 5.0,
                  "target_vs_go_batch": 20.0}
        for tag, d in (("single", 1), ("sharded", max_d)):
            set_mesh(d)
            note(f"[commit] {tag} D={d}: cold (table + compiles)")
            cold, _ = _timed_cold_warm(one_commit)
            note(f"[commit] {tag} cold {cold:.1f}s; timing")
            p50, p90 = timed_pass(one_commit)
            commit[tag] = {
                "devices": d,
                "p50_ms": round(p50 * 1e3, 3),
                "p90_ms": round(p90 * 1e3, 3),
                "cold_s": round(cold, 3),
            }
            note(f"[commit] {tag} p50={p50 * 1e3:.2f}ms")
        commit["vs_target"] = round(
            5.0 / commit["sharded"]["p50_ms"], 4)
        commit["sharded_vs_single"] = round(
            commit["single"]["p50_ms"] / commit["sharded"]["p50_ms"], 3)

    # ---- PR 5 gauge: sharded bundle loads warm in a FRESH process
    first = None
    if max_d > 1 and int(os.environ.get("BENCH_MESH_GAUGE", "1")):
        set_mesh(max_d)
        # TWO sharded buckets: the rlc executable is the production
        # target but its serialized form can hit the known XLA CPU
        # deserialize quirk ("Symbols not found") in a fresh process —
        # in which case it reports degraded:deserialize (by design) and
        # the merkle bucket carries the warm-load proof instead
        gplan = dataclasses.replace(
            deviceplan.active(), warm_kinds=("rlc",), warm_tables=(),
            warm_merkle=(max_lanes,), warm_lanes=(max_lanes,),
            warm_blocks=(2,))
        with tempfile.TemporaryDirectory(prefix="bench-mesh-aot-") as td:
            bpath = os.path.join(td, "bundle.aot")
            t0 = time.perf_counter()
            binfo = aotbundle.build(plan=gplan, path=bpath)
            t_build = time.perf_counter() - t0
            note(f"[gauge] sharded bundle build {t_build:.1f}s "
                 f"-> {binfo['buckets']}")
            if "warm" in binfo["buckets"].values():
                proc = subprocess.run(
                    [sys.executable, os.path.abspath(__file__),
                     "--_mesh_gauge", bpath, str(max_d), str(max_lanes)],
                    env=dict(os.environ), timeout=300,
                    stdout=subprocess.PIPE, stderr=sys.stderr)
                parsed = None
                for line in reversed(
                        proc.stdout.decode(errors="replace").splitlines()):
                    if line.strip().startswith("{"):
                        parsed = json.loads(line)
                        break
                if parsed and parsed.get("seconds") is not None:
                    first = {
                        "key": parsed.get("key"),
                        "build_s": round(t_build, 2),
                        "fresh_process_first_dispatch_s":
                            round(parsed["seconds"], 4),
                        "warm": bool(parsed["seconds"] < 1.0),
                        "bucket_statuses": parsed.get("buckets"),
                    }
                    note(f"[gauge] fresh-process first dispatch "
                         f"{parsed['seconds'] * 1e3:.1f}ms via "
                         f"{parsed.get('key')}")
    set_mesh(1)

    top = weak[-1]
    doc = {
        "metric": ("sharded SPMD verify: full-mesh sigs/s, ONE dispatch "
                   f"over {max_d} devices (weak-scaling workload)"),
        "value": top["sigs_per_s"],
        "unit": "sigs/s",
        # the invariant every backend must hold: sharded >= single-device
        # throughput at equal work (>1 = sharding helps outright)
        "vs_baseline": round(
            guard["single_p50_ms"] / guard["sharded_p50_ms"], 3),
        "weak_scaling": weak,
        "window": window,
        "equal_work_guard": guard,
        "commit10k": commit,
        "first_dispatch": first,
        "devices_visible": ndev,
        "per_device_lanes": per_dev,
        "reps": reps,
        "projection": (
            "CPU host-device emulation shares the box's cores, so "
            "per-dispatch latency cannot drop with mesh width here; "
            "project chip throughput as single-device sigs/s x mesh "
            "width (lanes independent, RLC fold crosses O(windows) "
            "points), then confirm on hardware with this same mode."),
        "backend": backend,
    }
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps(doc), flush=True)
    if not guard["ok"]:
        note("EQUAL-WORK GUARD FAILED: sharded slower than single")
        sys.exit(3)


def _mesh_gauge_child(path: str, nd: int, lanes: int) -> None:
    """Fresh-process half of the mesh bench's first-dispatch proof."""
    import dataclasses

    from cometbft_tpu.jaxenv import enable_compile_cache

    enable_compile_cache()

    from cometbft_tpu.crypto import aotbundle
    from cometbft_tpu.crypto import plan as deviceplan
    from cometbft_tpu.libs import metrics

    plan = dataclasses.replace(
        deviceplan.DevicePlan(), warm_kinds=("rlc",), warm_tables=(),
        warm_merkle=(lanes,), warm_lanes=(lanes,), warm_blocks=(2,),
        mesh_shape=(nd,))
    info = aotbundle.load(path=path, plan=plan)
    # prefer the production rlc executable; fall back to the merkle
    # bucket when rlc hit the fresh-process deserialize quirk (its
    # status then reads degraded:deserialize — reported upstream)
    candidates = (
        (f"rlc:{lanes}x2@m{nd}", deviceplan.CompileBucket("rlc", lanes, 2)),
        (f"merkle_level:{lanes}@m{nd}",
         deviceplan.CompileBucket("merkle_level", lanes)),
    )
    hit, secs = None, None
    if info["status"] == "loaded":
        for key, bucket in candidates:
            if info["buckets"].get(key) != "warm":
                continue
            aotbundle.timed_call(key, *aotbundle.sample_args(bucket))
            g = metrics.gauge("crypto_kernel_first_dispatch_seconds", "")
            hit = key
            secs = g.value(kind=bucket.kind, lanes=str(lanes))
            break
    print(json.dumps({"loaded": info["status"] == "loaded", "key": hit,
                      "seconds": secs, "buckets": info.get("buckets")}),
          flush=True)


def _child_node(rate: float, duration_s: float, tx_size: int) -> None:
    """Single-node end-to-end throughput: one validator committing load
    txs through the FULL stack (RPC -> mempool -> consensus -> ABCI
    kvstore -> storage).  Reference baseline: ~700-723 tx/s single-node
    (docs/references/storage/README.md:193)."""
    import shutil
    import tempfile

    def note(msg):
        print(f"[bench:node] {msg}", file=sys.stderr, flush=True)

    base = tempfile.mkdtemp(prefix="bench-node-")
    home = os.path.join(base, "n0")
    try:
        from cometbft_tpu import loadtime
        from cometbft_tpu.config import test_consensus_config
        from cometbft_tpu.e2e.gen import HomeSpec, generate_homes
        from cometbft_tpu.rpc import HTTPClient

        rpc_port = int(os.environ.get("BENCH_NODE_RPC", "28657"))
        # unique per run so the readiness check can DETECT a stale node
        # from a previous run squatting on the port
        chain_id = f"bench-node-{os.getpid()}"

        def tweak(spec, cfg):
            cfg.base.signature_backend = "cpu"
            cfg.consensus = test_consensus_config()
            cfg.mempool.size = 20000

        generate_homes(base, [HomeSpec(name="n0", p2p_port=rpc_port - 1,
                                       rpc_port=rpc_port, power=10)],
                       chain_id, tweak=tweak)
        note("starting node process")
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
        # `timeout` wrapper: even if this child is SIGKILLed (parent
        # attempt timeout), the node cannot outlive the run and squat on
        # the port for the next one
        ttl = int(duration_s) + 120
        with open(os.path.join(base, "node.log"), "ab") as lf:
            proc = subprocess.Popen(
                ["timeout", str(ttl), sys.executable, "-m",
                 "cometbft_tpu", "--home", home, "start"],
                stdout=lf, stderr=subprocess.STDOUT, env=env, cwd=REPO)
        try:
            import asyncio

            conns = int(os.environ.get("BENCH_NODE_CONNS", "8"))
            batch = int(os.environ.get("BENCH_NODE_BATCH", "4"))

            async def drive():
                cli = HTTPClient("127.0.0.1", rpc_port)
                for _ in range(120):           # wait for RPC
                    try:
                        st = await cli.call("status")
                        if st["node_info"]["network"] != chain_id:
                            # a STALE node from another run holds the
                            # port: driving it would record a bogus 0
                            raise RuntimeError(
                                f"port {rpc_port} is serving chain "
                                f"{st['node_info']['network']!r}, not "
                                f"the bench node")
                        break
                    except RuntimeError:
                        raise
                    except Exception:
                        await asyncio.sleep(0.25)
                else:
                    raise RuntimeError(
                        "bench node RPC never came up (see node.log)")
                note(f"driving {rate:.0f} tx/s for {duration_s:.0f}s "
                     f"({tx_size}B txs, {conns} connections, "
                     f"batch {batch})")
                gen = await loadtime.generate(cli, rate, duration_s,
                                              tx_size=tx_size,
                                              connections=conns,
                                              batch=batch)
                # let the backlog commit: a saturating drive leaves a
                # mempool tail, and counting only the mid-drive window
                # would understate committed throughput
                for _ in range(60):
                    un = await cli.call("num_unconfirmed_txs")
                    if int(un.get("n_txs", 0)) == 0:
                        break
                    await asyncio.sleep(0.5)
                await asyncio.sleep(1.0)
                rep = await loadtime.report(cli, run_id=gen["run_id"])
                return gen, rep

            gen, rep = asyncio.run(drive())
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
        tput = rep.get("throughput_tx_s") or 0.0
        print(json.dumps({
            "metric": f"single-node end-to-end throughput "
                      f"({tx_size}B txs, builtin kvstore)",
            "value": tput,
            "unit": "tx/s",
            # reference storage study: ~700 tx/s single node
            "vs_baseline": round(tput / 700.0, 2),
            "sent": gen["sent"],
            "send_errors": gen["errors"],
            "committed": rep.get("txs", 0),
            "p50_latency_s": rep.get("p50_s"),
            "p99_latency_s": rep.get("p99_s"),
            "blocks": rep.get("blocks"),
            "load_connections": conns,
            "load_batch": batch,
            "backend": "cpu",
        }), flush=True)
    finally:
        shutil.rmtree(base, ignore_errors=True)


def _child_lightserve(n_clients: int, n_conns: int, n_txs: int,
                      proofs_per_req: int) -> None:
    """Light-serving tier under a simulated skipping-client fleet: one
    validator node serves ``n_clients`` logical light clients (each a
    coroutine doing the real bootstrap round trips — a batched
    ``light_blocks`` fetch, a ``light_proofs`` batch, and a
    ``light_verify`` trust-anchor check — multiplexed over ``n_conns``
    keep-alive connections), while a prober hits ``/status`` throughout.

    Reports proofs/s and request p50/p99, the /status latency under
    load (the admission gate + worker-thread discipline is what keeps it
    flat), the tier's cache hit tallies, and ``vs_baseline``: the
    server-side cost of the SAME proof workload through the per-proof
    re-hash baseline (one reference tree build per proof — the seed's
    ``_tx_proof_provider`` shape without a cache) over the tier's
    cached-tree batch path."""
    import asyncio

    def note(msg):
        print(f"[bench:light-serve] {msg}", file=sys.stderr, flush=True)

    from cometbft_tpu.jaxenv import force_cpu_backend

    force_cpu_backend()

    import numpy as np

    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.config import Config, test_consensus_config
    from cometbft_tpu.crypto import merkle
    from cometbft_tpu.node import Node
    from cometbft_tpu.rpc import HTTPClient
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
    from cometbft_tpu.types.priv_validator import MockPV

    async def drive() -> dict:
        cfg = Config(consensus=test_consensus_config())
        cfg.p2p.laddr = "tcp://127.0.0.1:0"
        cfg.rpc.laddr = "tcp://127.0.0.1:0"
        cfg.mempool.size = max(20000, n_txs * 2)
        cfg.base.signature_backend = "cpu"
        pv = MockPV.from_secret(b"bench-lightserve")
        doc = GenesisDoc(chain_id="bench-ls",
                         validators=[GenesisValidator(pv.get_pub_key(),
                                                      10)])
        node = await Node.create(doc, KVStoreApplication(),
                                 priv_validator=pv, config=cfg,
                                 name="bench-ls")
        await node.start()
        try:
            note(f"seeding a block with {n_txs} txs")
            for i in range(n_txs):
                await node.mempool.check_tx(b"bls%d=v" % i)
            deadline = time.monotonic() + 60
            tx_height, tx_count = None, 0
            while time.monotonic() < deadline:
                await asyncio.sleep(0.05)
                if node.mempool.size() == 0 and \
                        node.block_store.height() >= 2:
                    for h in range(1, node.block_store.height() + 1):
                        blk = node.block_store.load_block(h)
                        if blk is not None and len(blk.data.txs) > tx_count:
                            tx_height, tx_count = h, len(blk.data.txs)
                    break
            if tx_height is None:
                raise RuntimeError("seed txs never committed")
            # one more height so tx_height's commit is canonical
            target = node.block_store.height() + 1
            while node.block_store.height() < target and \
                    time.monotonic() < deadline:
                await asyncio.sleep(0.05)
            note(f"serving block: height {tx_height} with {tx_count} txs")

            host, port = node.rpc_addr
            tip = node.block_store.height()
            boot_heights = list(range(max(1, tip - 7), tip + 1))
            cli0 = HTTPClient(host, port)
            ent = await cli0.call("light_block", height=tx_height)
            hot_anchors = [{"height": tx_height,
                            "commit": ent["light_block"]["commit"]}]
            rng = np.random.default_rng(2026)
            idx_sets = [sorted(rng.choice(tx_count,
                                          size=min(proofs_per_req,
                                                   tx_count),
                                          replace=False).tolist())
                        for _ in range(64)]

            lat = {"light_blocks": [], "light_proofs": [],
                   "light_verify": []}
            served = {"proofs": 0}
            clients = [HTTPClient(host, port) for _ in range(n_conns)]

            async def one_client(i: int) -> None:
                cli = clients[i % n_conns]
                t0 = time.perf_counter()
                await cli.call("light_blocks", heights=boot_heights)
                lat["light_blocks"].append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                pr = await cli.call("light_proofs", height=tx_height,
                                    kind="tx",
                                    indexes=idx_sets[i % len(idx_sets)])
                lat["light_proofs"].append(time.perf_counter() - t0)
                served["proofs"] += len(pr["proofs"])
                t0 = time.perf_counter()
                await cli.call("light_verify",
                               anchors=[hot_anchors[0]])
                lat["light_verify"].append(time.perf_counter() - t0)

            status_lat = []
            stop_probe = asyncio.Event()

            async def probe_status() -> None:
                pc = HTTPClient(host, port)
                while not stop_probe.is_set():
                    t0 = time.perf_counter()
                    await pc.call("status")
                    status_lat.append(time.perf_counter() - t0)
                    try:
                        await asyncio.wait_for(stop_probe.wait(), 0.05)
                    except asyncio.TimeoutError:
                        pass
                await pc.close()

            note(f"driving {n_clients} simulated skipping clients over "
                 f"{n_conns} connections (3 RPCs each)")
            prober = asyncio.create_task(probe_status())
            t_wall = time.perf_counter()
            await asyncio.gather(*(one_client(i)
                                   for i in range(n_clients)))
            t_wall = time.perf_counter() - t_wall
            stop_probe.set()
            await prober
            for c in clients:
                await c.close()

            st = await cli0.call("status")
            ls_stats = st.get("light_serve") or {}
            await cli0.close()

            # ---- server-side baseline: per-proof re-hash ----------------
            note("server-side baseline: per-proof re-hash vs cached tree")
            from cometbft_tpu.types.header import tx_hash as _txh

            blk = node.block_store.load_block(tx_height)
            leaves = [_txh(t) for t in blk.data.txs]
            idxs = idx_sets[0]
            tier = node.light_serve
            reps = 20

            t0 = time.perf_counter()
            for _ in range(reps):
                tier.proofs(tx_height, "tx", idxs)
            t_cached = (time.perf_counter() - t0) / reps

            t0 = time.perf_counter()
            for _ in range(3):
                for i in idxs:           # one full re-hash PER PROOF
                    _root, prs = merkle.proofs_from_byte_slices_reference(
                        leaves)
                    _ = prs[i]
            t_rehash = (time.perf_counter() - t0) / 3

            all_lat = sorted(lat["light_blocks"] + lat["light_proofs"]
                             + lat["light_verify"])
            nreq = len(all_lat)

            def pct(v, q):
                return float(np.percentile(v, q)) if v else 0.0

            return {
                "metric": f"light-serve proofs/s ({n_clients} simulated "
                          f"skipping clients, {tx_count}-tx block, "
                          f"{len(idxs)} proofs/req)",
                "value": round(served["proofs"] / t_wall, 1),
                "unit": "proofs/s",
                # per-proof re-hash baseline vs the cached-tree batch
                # path, same proof workload, measured server-side
                "vs_baseline": round(t_rehash / t_cached, 2),
                "requests_per_s": round(nreq / t_wall, 1),
                "p50_request_ms": round(pct(all_lat, 50) * 1e3, 2),
                "p99_request_ms": round(pct(all_lat, 99) * 1e3, 2),
                "p99_bootstrap_ms": round(
                    pct(lat["light_blocks"], 99) * 1e3, 2),
                "p99_proofs_ms": round(
                    pct(lat["light_proofs"], 99) * 1e3, 2),
                "p99_verify_ms": round(
                    pct(lat["light_verify"], 99) * 1e3, 2),
                "status_p99_ms": round(pct(status_lat, 99) * 1e3, 2),
                "status_max_ms": round(
                    max(status_lat) * 1e3 if status_lat else 0.0, 2),
                "status_samples": len(status_lat),
                "wall_s": round(t_wall, 3),
                "proofs_served": served["proofs"],
                "cached_batch_ms": round(t_cached * 1e3, 3),
                "rehash_batch_ms": round(t_rehash * 1e3, 3),
                "header_cache_hit_rate": round(
                    ls_stats.get("header_hits", 0)
                    / max(1, ls_stats.get("header_hits", 0)
                          + ls_stats.get("header_misses", 0)), 4),
                "verify_memo_hit_rate": round(
                    ls_stats.get("verify_hits", 0)
                    / max(1, ls_stats.get("verify_hits", 0)
                          + ls_stats.get("verify_misses", 0)), 4),
                "proof_cache_hits": ls_stats.get("proof_hits", 0),
                "clients": n_clients,
                "connections": n_conns,
                "txs_in_block": tx_count,
                "backend": "cpu",
            }
        finally:
            await node.stop()

    result = asyncio.run(drive())
    out_path = os.environ.get(
        "BENCH_OUT", os.path.join(REPO, "docs", "bench",
                                  "r14-light-serve-cpu.json"))
    try:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
        note(f"wrote {out_path}")
    except OSError as e:
        note(f"could not write {out_path}: {e}")
    print(json.dumps(result), flush=True)


def _child_votegossip(backend: str, n_vals: int, dup_k: int,
                      n_slots: int) -> None:
    """Synthetic N-peer vote-gossip storm: every validator's precommit
    arrives ``dup_k`` times (re-gossip by k peers), across ``n_slots``
    height/round slots, each slot ending in a VerifyCommitLight over the
    assembled commit — the steady-state shape live consensus sees.

    Two passes over the identical stream:
    - per-vote baseline (today's default without a scheduler): each
      unique vote verifies one-at-a-time inside ``VoteSet.add_vote``;
      duplicates dedup in the vote set; the commit re-verifies every
      signature through the uncached dense batch.
    - scheduler path: all arrivals pre-verify concurrently through the
      coalescing ``VerificationScheduler`` (micro-batches through the
      routed BatchVerifier, in-flight dedup), then the same
      ``add_vote``/``VerifyCommitLight`` calls ride the verified-sig
      cache.

    Writes the JSON result to ``BENCH_OUT`` (default
    ``docs/bench/r07-vote-sched-cpu.json``) in addition to stdout."""
    note, kernel_backend = _mode_child_setup("votegossip", backend)

    import asyncio
    import random as _random

    from cometbft_tpu.crypto import scheduler as vsched
    from cometbft_tpu.crypto.keys import gen_priv_key
    from cometbft_tpu.types.block_id import BlockID
    from cometbft_tpu.types.part_set import PartSetHeader
    from cometbft_tpu.types.validation import VerifyCommitLight
    from cometbft_tpu.types.validator_set import Validator, ValidatorSet
    from cometbft_tpu.types.vote import PRECOMMIT_TYPE, Vote
    from cometbft_tpu.types.vote_set import VoteSet

    chain_id = "bench-votegossip"
    note(f"building {n_slots} slots x {n_vals} validators, "
         f"x{dup_k} gossip duplication")
    privs = [gen_priv_key() for _ in range(n_vals)]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}

    slots = []            # (events, commit, block_id) per slot
    rng = _random.Random(2026)
    for s in range(n_slots):
        height = s + 1
        bid = BlockID(bytes([s + 1]) * 32,
                      PartSetHeader(1, bytes([s + 2]) * 32))
        votes = []
        for i in range(n_vals):
            v = vals.get_by_index(i)
            vote = Vote(type=PRECOMMIT_TYPE, height=height, round=0,
                        block_id=bid, timestamp_ns=10_000 + i,
                        validator_address=v.address, validator_index=i)
            vote.signature = by_addr[v.address].sign(
                vote.sign_bytes(chain_id))
            votes.append(vote)
        vs = VoteSet(chain_id, height, 0, PRECOMMIT_TYPE, vals)
        for vote in votes:
            vs.add_vote(vote)
        commit = vs.make_commit()
        events = votes * dup_k
        rng.shuffle(events)
        slots.append((events, commit, bid, height))
    n_events = sum(len(ev) for ev, *_ in slots)

    def drive_stream() -> float:
        """One pass over every slot: add_vote per arrival + the final
        commit verification.  Identical call sequence in both passes —
        only the registered scheduler differs."""
        t0 = time.perf_counter()
        for events, commit, bid, height in slots:
            vs = VoteSet(chain_id, height, 0, PRECOMMIT_TYPE, vals)
            for vote in events:
                vs.add_vote(vote)
            VerifyCommitLight(chain_id, vals, bid, height, commit,
                              backend=kernel_backend)
        return time.perf_counter() - t0

    reps = int(os.environ.get("BENCH_VG_REPS", "5"))
    note(f"per-vote baseline pass (no scheduler), best of {reps}")
    assert vsched.get_scheduler() is None
    t_base = min(drive_stream() for _ in range(reps))

    async def sched_pass() -> tuple[float, dict]:
        sched = await vsched.acquire_scheduler(
            backend=kernel_backend, max_wait_ms=2.0, max_lanes=256)
        try:
            t0 = time.perf_counter()
            for events, commit, bid, height in slots:
                # concurrent arrival from k peers: every gossip copy is
                # submitted fire-and-forget like the reactor prefetch,
                # coalescing into micro-batches with in-flight dedup; one
                # barrier future stands in for the state queue
                loop = asyncio.get_running_loop()
                done = loop.create_future()
                remaining = len(events)

                def _arrived(_ok, _d=done):
                    nonlocal remaining
                    remaining -= 1
                    if remaining == 0 and not _d.done():
                        _d.set_result(None)

                for v in events:
                    sched.submit_nowait(
                        vals.get_by_index(v.validator_index).pub_key,
                        v.sign_bytes(chain_id), v.signature,
                        on_done=_arrived)
                await done
                vs = VoteSet(chain_id, height, 0, PRECOMMIT_TYPE, vals)
                for vote in events:
                    vs.add_vote(vote)       # cache hits
                VerifyCommitLight(chain_id, vals, bid, height, commit,
                                  backend=kernel_backend)
            dt = time.perf_counter() - t0
            return dt, sched.stats()
        finally:
            await vsched.release_scheduler()

    note(f"scheduler pass (coalescing + verified-sig cache), "
         f"best of {reps}")
    # best-of-N like the baseline (noise on a shared box must not decide
    # the comparison); each pass gets a FRESH scheduler + cache (stats
    # are per-instance), so every run re-verifies everything rather than
    # riding warm entries; the reported stats are the first pass's.
    t_sched, stats = asyncio.run(sched_pass())
    for _ in range(reps - 1):
        t2, _s2 = asyncio.run(sched_pass())
        t_sched = min(t_sched, t2)

    result = {
        "metric": f"vote-gossip verification storm, arrivals/sec "
                  f"({n_slots} slots x {n_vals} vals x{dup_k} dup, "
                  f"commit re-check included)",
        "value": round(n_events / t_sched, 1),
        "unit": "events/s",
        "vs_baseline": round(t_base / t_sched, 2),
        "baseline_events_per_s": round(n_events / t_base, 1),
        "baseline_s": round(t_base, 3),
        "scheduler_s": round(t_sched, 3),
        "cache_hit_rate": round(stats["cache_hit_rate"], 3),
        "dedup_inflight": stats["dedup_inflight"],
        "mean_batch_lanes": round(stats["mean_batch_lanes"], 1),
        "batches": stats["batches"],
        "n_events": n_events,
        "backend": backend,
    }
    out_path = os.environ.get(
        "BENCH_OUT", os.path.join(REPO, "docs", "bench",
                                  "r07-vote-sched-cpu.json"))
    try:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
        note(f"wrote {out_path}")
    except OSError as e:
        note(f"could not write {out_path}: {e}")
    print(json.dumps(result), flush=True)


def _single_verify_us(host_items) -> float:
    """Single-verify baseline in us, min over 3 passes: a noisy shared
    box inflates one-shot timings, which would overstate vs_baseline (a
    faster batch number should come from the batch getting faster, not
    the baseline getting slower)."""
    from cometbft_tpu.crypto.keys import verify_ed25519_zip215

    sample = host_items[:min(256, len(host_items))]
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for pk, msg, sig in sample:
            assert verify_ed25519_zip215(pk, msg, sig)
        best = min(best, (time.perf_counter() - t0) / len(sample))
    return best * 1e6


def _child_scenarios(out_path: str) -> None:
    """``--mode scenarios``: sweep the scenario lab's curated suite
    (``cometbft_tpu.sim.scenario.curated_suite``) on the virtual clock,
    re-running the first scenario to enforce the replay contract, and
    write the full verdict JSON to ``out_path`` — the liveness analog
    of the perf guards: a regression that forks a net, loses recovery,
    or breaks replay determinism fails this run the same way a slow
    kernel fails a perf bar.

    The headline value is simulated-virtual-seconds per real second
    (how much adversarial time one CPU buys), but the pass/fail payload
    is the verdicts."""
    from cometbft_tpu.jaxenv import force_cpu_backend

    force_cpu_backend()
    from cometbft_tpu.sim.scenario import (chaos_signature_of,
                                           curated_suite, run_scenario)

    def note(msg):
        print(f"[bench:scenarios] {msg}", file=sys.stderr, flush=True)

    suite = curated_suite()
    only = os.environ.get("BENCH_SCENARIOS", "")
    if only:
        names = {n.strip() for n in only.split(",") if n.strip()}
        suite = [s for s in suite if s.name in names]
    verdicts = []
    failures_: list[str] = []
    total_virtual = 0.0
    t_all = time.perf_counter()
    replay_checked = False
    for scn in suite:
        note(f"running {scn.name} ({scn.n_nodes} nodes, "
             f"target h{scn.target_height})")
        t0 = time.perf_counter()
        if not replay_checked:
            v, sig1 = chaos_signature_of(scn)
            real_s = time.perf_counter() - t0
            # the replay double-run: its virtual seconds count toward
            # the headline total (the work really ran) but its real
            # time must not be billed to the scenario's own real_s
            v2, sig2 = chaos_signature_of(scn)
            if sig1 != sig2 or \
                    json.dumps(v, sort_keys=True) != \
                    json.dumps(v2, sort_keys=True):
                failures_.append(f"{scn.name}: replay diverged")
            total_virtual += v2["virtual_duration_s"]
            replay_checked = True
        else:
            v = run_scenario(scn)
            real_s = time.perf_counter() - t0
        v["real_s"] = round(real_s, 1)     # informational; excluded from
        # the replay compare above (which ran on the pristine dicts)
        verdicts.append(v)
        total_virtual += v["virtual_duration_s"]
        if not v["fork_free"]:
            failures_.append(f"{scn.name}: FORK")
        if not v["reached_target"]:
            failures_.append(
                f"{scn.name}: stuck at {v['common_height']}")
        note(f"  {scn.name}: h{v['common_height']} in "
             f"{v['virtual_duration_s']}s virtual / {real_s:.1f}s real, "
             f"fork_free={v['fork_free']}")
    real_total = time.perf_counter() - t_all
    doc = {"scenarios": verdicts, "failures": failures_,
           "real_total_s": round(real_total, 1),
           "virtual_total_s": round(total_virtual, 1)}
    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        note(f"verdicts -> {out_path}")
    print(json.dumps({
        "metric": f"scenario lab: adversarial virtual-seconds simulated "
                  f"per real second ({len(verdicts)} scenarios, "
                  f"fork-free + replay-identical required)",
        "value": round(total_virtual / max(real_total, 1e-9), 2),
        "unit": "virtual-s/s",
        "vs_baseline": 1.0 if not failures_ else 0.0,
        "scenarios_passed": len(verdicts) - len(
            {f.split(":")[0] for f in failures_}),
        "scenarios_total": len(verdicts),
        "failures": failures_,
        "virtual_total_s": round(total_virtual, 1),
        "real_total_s": round(real_total, 1),
        "backend": "cpu",
    }), flush=True)
    if failures_:
        raise SystemExit(1)


def _child_mempool(out_path: str) -> None:
    """``--mode mempool``: the r16 admission path under a signature-
    checking app — the mempool analog of the vote-gossip storm bench.

    Three measurements, one JSON:

    - **admission**: a seeded backlog of sig-carrying txs pushed through
      ``check_tx`` at high concurrency (sharded gates + per-shard
      CheckTx coalescer + VerificationScheduler micro-batching under
      the app).  Reports sustained admitted tx/s and p99 admission
      latency.
    - **recheck**: the same backlog rechecked two ways — the OLD serial
      loop (one awaited CheckTx per tx, direct single verification:
      exactly what ``update()`` did before r16) vs the batched pass
      (chunked concurrent CheckTx, signature checks coalesced into
      batch-verifier micro-batches).  The acceptance bar is >=2x.
    - **gossip bytes**: bytes-on-wire to re-gossip the whole pool to a
      peer set that ALREADY HOLDS every tx — full-body re-flood (old
      protocol) vs content-addressed announcements (32-byte hashes).
    """
    import asyncio

    from cometbft_tpu.jaxenv import force_cpu_backend

    force_cpu_backend()
    import msgpack

    from cometbft_tpu.abci.types import CheckTxResponse
    from cometbft_tpu.crypto import scheduler as vsched
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    from cometbft_tpu.mempool.clist_mempool import CListMempool
    from cometbft_tpu.mempool.reactor import MEMPOOL_CHANNEL, MempoolReactor

    def note(msg):
        print(f"[bench:mempool] {msg}", file=sys.stderr, flush=True)

    n_txs = int(os.environ.get("BENCH_MEMPOOL_TXS", "8192"))
    concurrency = int(os.environ.get("BENCH_MEMPOOL_CONC", "512"))
    shards = int(os.environ.get("BENCH_MEMPOOL_SHARDS", "4"))
    n_peers = int(os.environ.get("BENCH_MEMPOOL_PEERS", "8"))

    note(f"signing {n_txs} txs (32B pub + 64B sig + payload)")
    priv = Ed25519PrivKey.generate()
    pub = priv.pub_key()
    pub_b = pub.bytes()
    payloads = [b"mp%06d" % i + b"p" * 90 for i in range(n_txs)]
    txs = [pub_b + priv.sign(p) + p for p in payloads]

    class SigApp:
        """CheckTx = verify the embedded ed25519 signature.  With a
        VerificationScheduler running the verify coalesces into its
        micro-batches (what a production app using the repo's verify
        seam gets); without one it is a direct single verification —
        the pre-r16 serial-recheck cost model."""

        async def check_tx(self, tx: bytes, recheck: bool = False):
            p, sig, msg = tx[:32], tx[32:96], tx[96:]
            assert p == pub_b
            sched = vsched.get_scheduler()
            if sched is not None and sched.is_running:
                # the fire-and-forget submission path (what the
                # consensus reactor uses): no wait_for/shield per item
                fut = asyncio.get_running_loop().create_future()
                sched.submit_nowait(pub, msg, sig, on_done=fut.set_result)
                ok = await fut
            else:
                ok = pub.verify_signature(msg, sig)
            return CheckTxResponse(code=0 if ok else 1, gas_wanted=1)

    async def drive() -> dict:
        # cache_size=0: every tx is unique and the dedup cache must not
        # turn the second recheck pass into a no-op measurement
        sched = vsched.VerificationScheduler(
            backend="cpu", max_wait_ms=2.0, max_lanes=256, cache_size=0)
        await sched.start()
        vsched.set_scheduler(sched)
        app = SigApp()
        mp = CListMempool(app, max_txs=n_txs + 16, shards=shards,
                          cache_size=n_txs + 16, metrics_node="bench")

        # ---- admission: seeded backlog at bounded concurrency -------
        lat: list[float] = []
        sem = asyncio.Semaphore(concurrency)

        async def admit(tx: bytes) -> None:
            async with sem:
                t0 = time.perf_counter()
                await mp.check_tx(tx)
                lat.append(time.perf_counter() - t0)

        t0 = time.perf_counter()
        await asyncio.gather(*(admit(tx) for tx in txs))
        admit_s = time.perf_counter() - t0
        assert mp.size() == n_txs, mp.size()
        lat.sort()
        admit_p99_ms = lat[int(0.99 * (len(lat) - 1))] * 1e3
        note(f"admitted {n_txs} in {admit_s:.2f}s "
             f"({n_txs / admit_s:.0f} tx/s, p99 {admit_p99_ms:.1f} ms) "
             f"shards={mp.stats()['shards']}")

        # ---- recheck: batched pass vs the old serial loop -----------
        t0 = time.perf_counter()
        async with mp.lock():
            await mp.update(2, [], [])     # nothing committed: all
        batched_s = time.perf_counter() - t0   # survivors recheck
        assert mp.size() == n_txs
        await sched.stop()
        vsched.set_scheduler(None)         # serial baseline: direct
        t0 = time.perf_counter()           # verification per awaited tx
        for tx in txs:
            res = await app.check_tx(tx, recheck=True)
            assert res.is_ok
        serial_s = time.perf_counter() - t0
        speedup = serial_s / batched_s if batched_s > 0 else 0.0
        note(f"recheck: batched {batched_s:.2f}s vs serial "
             f"{serial_s:.2f}s -> {speedup:.2f}x")

        # ---- gossip bytes to an already-synced peer set -------------
        class CountingPeer:
            def __init__(self, pid):
                self.id = pid
                self.bytes = 0
                self.frames = 0

            def send(self, channel_id, msg):
                self.bytes += len(msg)
                self.frames += 1
                return True

        async def settle(reactor, peers):
            deadline = time.perf_counter() + 30
            while time.perf_counter() < deadline:
                await asyncio.sleep(0.05)
                if all(p.frames and p.bytes for p in peers):
                    # one idle gossip interval with no growth = settled
                    snap = [(p.frames, p.bytes) for p in peers]
                    await asyncio.sleep(0.1)
                    if snap == [(p.frames, p.bytes) for p in peers]:
                        return
            raise RuntimeError("gossip never settled")

        full_bytes = ann_bytes = 0
        for mode_name in ("full", "announce"):
            reactor = MempoolReactor(mp, gossip_sleep=0.01,
                                     gossip_mode=mode_name)
            peers = [CountingPeer(f"synced-{mode_name}-{i}")
                     for i in range(n_peers)]
            for p in peers:
                if mode_name == "announce":
                    # peer advertises the new protocol (hello)
                    reactor.receive(MEMPOOL_CHANNEL, p, msgpack.packb(
                        {"hi": 1}, use_bin_type=True))
                reactor.add_peer(p)
            await settle(reactor, peers)
            total = sum(p.bytes for p in peers)
            await reactor.stop()
            if mode_name == "full":
                full_bytes = total
            else:
                ann_bytes = total
        reduction = full_bytes / ann_bytes if ann_bytes else 0.0
        note(f"gossip to {n_peers} synced peers: full-body "
             f"{full_bytes / 1e6:.2f} MB vs announce "
             f"{ann_bytes / 1e6:.3f} MB ({reduction:.1f}x less wire)")

        total_checks = 3 * n_txs           # admit + 2 recheck passes
        total_s = admit_s + batched_s + serial_s
        return {
            "n_txs": n_txs,
            "concurrency": concurrency,
            "shards": shards,
            "admit_tx_s": round(n_txs / admit_s, 1),
            "admit_p99_ms": round(admit_p99_ms, 2),
            "recheck_batched_s": round(batched_s, 3),
            "recheck_serial_s": round(serial_s, 3),
            "recheck_batched_tx_s": round(n_txs / batched_s, 1),
            "recheck_speedup": round(speedup, 2),
            "gossip_peers": n_peers,
            "gossip_full_body_bytes": full_bytes,
            "gossip_announce_bytes": ann_bytes,
            "gossip_wire_reduction": round(reduction, 2),
            "sustained_checks_s": round(total_checks / total_s, 1),
        }

    loop = asyncio.new_event_loop()
    try:
        doc = loop.run_until_complete(drive())
    finally:
        loop.close()
    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        note(f"results -> {out_path}")
    value = doc["admit_tx_s"]
    print(json.dumps({
        "metric": "mempool admission+recheck throughput (sharded pool, "
                  "coalesced CheckTx, sig-verifying app)",
        "value": value,
        "unit": "tx/s",
        # the acceptance bar is the batched-recheck speedup over the
        # pre-r16 serial loop, normalized at the >=2x requirement
        "vs_baseline": round(doc["recheck_speedup"] / 2.0, 2),
        "backend": "cpu",
        **{k: doc[k] for k in (
            "admit_p99_ms", "recheck_speedup", "recheck_batched_tx_s",
            "gossip_wire_reduction", "sustained_checks_s")},
    }), flush=True)


def _child_statesync(out_path: str) -> None:
    """``--mode statesync``: the r18 snapshot fabric — three
    measurements, one JSON:

    - **serving**: chunks/s served through the reactor's byte-budgeted
      LRU + admission gate (cold pass loads from the app, warm passes
      hit RAM) and the warm cache hit ratio.
    - **bootstrap**: restore wall-clock over per-peer-bandwidth-limited
      serving peers, 1 peer vs 4 peers — multi-peer round-robin fetch
      must turn peer count into bandwidth (the ±-free speedup is the
      acceptance bar).
    - **fleet**: the 50-node scenario-lab program (40 concurrent
      bootstrappers, 4 seeds, gray failures + a byzantine seed serving
      corrupt chunks) run TWICE: verdicts must be byte-identical
      (replay contract), every bootstrapper must complete, the byzantine
      seed must be banned by all, and restore resets must be zero.
    """
    from cometbft_tpu.jaxenv import force_cpu_backend

    force_cpu_backend()

    import asyncio
    from types import SimpleNamespace

    from cometbft_tpu.abci import types as abci_t
    from cometbft_tpu.abci.client import LocalClient
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.sim.statesync_lab import (curated_statesync_scenario,
                                                run_statesync_scenario)
    from cometbft_tpu.statesync.reactor import StatesyncReactor
    from cometbft_tpu.statesync.syncer import Syncer

    def note(msg):
        print(f"[bench:statesync] {msg}", file=sys.stderr, flush=True)

    n_serves = int(os.environ.get("BENCH_SS_SERVES", "3000"))
    n_chunks = int(os.environ.get("BENCH_SS_CHUNKS", "64"))
    serve_delay = 0.005        # per-chunk service time per peer

    async def serving_leg() -> dict:
        app = KVStoreApplication()
        client = LocalClient(app)
        # ~1.5 MB of state -> ~24 chunks of 64 KiB
        await client.finalize_block(abci_t.FinalizeBlockRequest(
            txs=[b"bk%02d=" % i + b"v" * 32768 for i in range(48)],
            height=1, time_ns=0))
        await client.commit()
        snaps = await client.list_snapshots()
        snap = snaps[-1]
        reactor = StatesyncReactor(SimpleNamespace(snapshot=client),
                                   name="bench.ss")
        sink = SimpleNamespace(id="bench-peer",
                               send=lambda chan, msg: True)
        # cold pass (loads + fills the LRU), then the timed warm passes
        for i in range(snap.chunks):
            await reactor._serve_chunk(sink, {"h": snap.height,
                                              "f": snap.format, "i": i})
        t0 = time.perf_counter()
        for k in range(n_serves):
            i = k % snap.chunks
            await reactor._serve_chunk(sink, {"h": snap.height,
                                              "f": snap.format, "i": i})
        dt = time.perf_counter() - t0
        served = n_serves
        return {
            "snapshot_chunks": snap.chunks,
            "serves": served,
            "chunks_per_s": round(served / dt, 1),
            "warm_hit_ratio": round(served / (served + snap.chunks), 4),
            "cache_bytes": reactor._cache.bytes,
        }

    class _SerialPeerReactor:
        """Each peer is a serial worker: one chunk every serve_delay —
        aggregate throughput is proportional to peer count only if the
        fetcher spreads requests (same harness shape as
        tests/test_statesync.py)."""

        def __init__(self, box):
            self.box = box
            self.queues: dict[str, asyncio.Queue] = {}
            self.workers: list = []

        def request_chunk(self, peer, height, format_, index, h):
            if peer not in self.queues:
                self.queues[peer] = asyncio.Queue()
                self.workers.append(asyncio.get_event_loop().create_task(
                    self._serve(peer)))
            self.queues[peer].put_nowait((height, format_, index, h))

        async def _serve(self, peer):
            while True:
                height, format_, index, h = await self.queues[peer].get()
                await asyncio.sleep(serve_delay)
                self.box[0].add_chunk(peer, height, format_, index,
                                      b"DATA-%d" % index, h)

    async def bootstrap_leg(n_peers: int) -> float:
        class SnapConn:
            async def offer_snapshot(self, snapshot, app_hash):
                return abci_t.OFFER_SNAPSHOT_ACCEPT

            async def apply_snapshot_chunk(self, index, chunk, sender):
                return abci_t.APPLY_CHUNK_ACCEPT

        class QueryConn:
            async def info(self):
                return abci_t.InfoResponse(last_block_height=7,
                                           last_block_app_hash=b"\xab" *
                                           32)

        class Provider:
            async def app_hash(self, h):
                return b"\xab" * 32

            async def state(self, h):
                return "S"

            async def commit(self, h):
                return "C"

        conns = SimpleNamespace(snapshot=SnapConn(), query=QueryConn())
        box = [None]
        reactor = _SerialPeerReactor(box)
        syncer = Syncer(conns, Provider(), reactor=reactor,
                        in_memory_spool=True)
        box[0] = syncer
        snapshot = abci_t.Snapshot(height=7, format=1, chunks=n_chunks,
                                   hash=b"\xcd" * 32, metadata=b"")
        for k in range(n_peers):
            syncer.add_snapshot(f"peer{k}", snapshot)
        t0 = time.perf_counter()
        await syncer._restore(syncer._snapshots[(7, 1, b"\xcd" * 32)])
        dt = time.perf_counter() - t0
        for w in reactor.workers:
            w.cancel()
        syncer._pool.close()
        return dt

    async def drive() -> dict:
        serving = await serving_leg()
        note(f"serving: {serving['chunks_per_s']} chunks/s warm "
             f"({serving['snapshot_chunks']}-chunk snapshot)")
        t1 = await bootstrap_leg(1)
        t4 = await bootstrap_leg(4)
        note(f"bootstrap {n_chunks} chunks: 1 peer {t1:.2f}s, "
             f"4 peers {t4:.2f}s ({t1 / t4:.2f}x)")
        return {"serving": serving,
                "bootstrap": {
                    "n_chunks": n_chunks,
                    "serve_delay_s": serve_delay,
                    "single_peer_s": round(t1, 3),
                    "multi_peer_s": round(t4, 3),
                    "multi_peer_speedup": round(t1 / t4, 2)}}

    loop = asyncio.new_event_loop()
    try:
        doc = loop.run_until_complete(drive())
    finally:
        loop.close()

    failures_: list[str] = []
    scn = curated_statesync_scenario()
    note(f"fleet: {scn.n_bootstrappers} bootstrappers / "
         f"{scn.n_seeds} seeds / byzantine {scn.byzantine_seeds}")
    t0 = time.perf_counter()
    v1 = run_statesync_scenario(scn)
    fleet_real = time.perf_counter() - t0
    v2 = run_statesync_scenario(scn)
    if json.dumps(v1, sort_keys=True) != json.dumps(v2, sort_keys=True):
        failures_.append("fleet scenario: replay diverged")
    if v1["completed"] != scn.n_bootstrappers:
        failures_.append(f"fleet scenario: only {v1['completed']} of "
                         f"{scn.n_bootstrappers} completed")
    if v1["syncer_tallies"].get("restore_resets", 0) != 0:
        failures_.append("fleet scenario: corrupt chunk caused a "
                         "restore reset")
    if len(v1["byzantine_banned_by"]) < scn.n_bootstrappers:
        failures_.append("fleet scenario: byzantine seed not banned "
                         "by the whole fleet")
    v1["real_s"] = round(fleet_real, 1)
    doc["fleet"] = v1
    doc["failures"] = failures_
    dist = {k: x for k, x in v1["time_to_serving_height_s"].items()
            if k != "all"}
    replay_ok = "fleet scenario: replay diverged" not in failures_
    note(f"fleet: completed={v1['completed']} dist={dist} "
         f"replay_ok={replay_ok}")

    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        note(f"results -> {out_path}")
    print(json.dumps({
        "metric": "statesync fabric: chunks/s served warm through the "
                  "serving LRU (vs_baseline = 4-peer bootstrap speedup "
                  "over 1 peer; fleet scenario replay-identical, "
                  "reset-free, byzantine seed banned)",
        "value": doc["serving"]["chunks_per_s"],
        "unit": "chunks/s",
        "vs_baseline": 0.0 if failures_ else
        doc["bootstrap"]["multi_peer_speedup"],
        "multi_peer_speedup": doc["bootstrap"]["multi_peer_speedup"],
        "warm_hit_ratio": doc["serving"]["warm_hit_ratio"],
        "fleet_completed": v1["completed"],
        "fleet_time_to_serving_p50_s":
        v1["time_to_serving_height_s"]["p50"],
        "fleet_time_to_serving_max_s":
        v1["time_to_serving_height_s"]["max"],
        "failures": failures_,
        "backend": "cpu",
    }), flush=True)
    if failures_:
        raise SystemExit(1)


def _child_bls(out_path: str) -> None:
    """``--mode bls``: the aggregate-commit fast path — at each point of
    the 100/1k/10k-validator curve, a warm ``VerifyCommitLight`` over an
    aggregate BLS commit (bitmap decode + complement pubkey fold + two
    pairings, O(1) in N) against the same call over an Ed25519 dense
    commit (the production batched host path, O(N)), plus the wire size
    of both commits.  2% of the cohort is absent so the complement fold
    does real point arithmetic instead of returning the cached
    full-cohort sum.

    Headline ``value`` is the 10k-validator speedup; ``vs_baseline`` is
    that speedup / 10 (the acceptance bar is >= 10x, so > 1 means the
    bar is met).  The full curve goes to ``out_path``."""
    from cometbft_tpu.jaxenv import force_cpu_backend

    force_cpu_backend()

    def note(msg):
        print(f"[bench:bls] {msg}", file=sys.stderr, flush=True)

    from cometbft_tpu.crypto.bls12381 import aggregate_signatures
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    from cometbft_tpu.testing import bls_priv_from_secret
    from cometbft_tpu.types import codec
    from cometbft_tpu.types.block_id import BlockID
    from cometbft_tpu.types.canonical import canonical_vote_sign_bytes
    from cometbft_tpu.types.commit import (
        BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_AGGREGATE,
        BLOCK_ID_FLAG_COMMIT, Commit, CommitSig, signer_bitmap)
    from cometbft_tpu.types.part_set import PartSetHeader
    from cometbft_tpu.types.validation import VerifyCommitLight
    from cometbft_tpu.types.validator_set import Validator, ValidatorSet
    from cometbft_tpu.types.vote import PRECOMMIT_TYPE

    chain_id = "bench-bls"
    height = 7
    bid = BlockID(b"\x01" * 32, PartSetHeader(1, b"\x02" * 32))
    reps = int(os.environ.get("BENCH_REPS", "5"))
    curve_ns = [int(x) for x in os.environ.get(
        "BENCH_BLS_CURVE", "100,1000,10000").split(",")]

    def warm_min(fn):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    curve = []
    for n in curve_ns:
        # ---- aggregate side: all-BLS valset, 2% absent
        note(f"n={n}: building BLS valset + aggregate commit")
        privs = [bls_priv_from_secret(b"bench-bls%d" % i) for i in range(n)]
        vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
        by_addr = {p.pub_key().address(): p for p in privs}
        absent = set(range(0, n, 50)) if n >= 100 else set()
        msg = canonical_vote_sign_bytes(chain_id, PRECOMMIT_TYPE, height,
                                        0, bid, 0)
        lanes, signers, sigs = [], [], []
        for i, v in enumerate(vals.validators):
            if i in absent:
                lanes.append(CommitSig(BLOCK_ID_FLAG_ABSENT))
                continue
            signers.append(i)
            sigs.append(by_addr[v.address].sign(msg))
            lanes.append(CommitSig(BLOCK_ID_FLAG_AGGREGATE, v.address,
                                   1_000_000 + i, b""))
        agg_commit = Commit(height, 0, bid, lanes,
                            aggregate_signatures(sigs, check=False),
                            signer_bitmap(signers, n))
        note(f"n={n}: cold aggregate verify (builds the cohort table)")
        t0 = time.perf_counter()
        VerifyCommitLight(chain_id, vals, bid, height, agg_commit)
        bls_cold = time.perf_counter() - t0
        bls_warm = warm_min(lambda: VerifyCommitLight(
            chain_id, vals, bid, height, agg_commit))

        # ---- dense side: all-Ed25519 valset, same shape/absentees
        note(f"n={n}: building Ed25519 valset + dense commit")
        eprivs = [Ed25519PrivKey.from_secret(b"bench-ed%d" % i)
                  for i in range(n)]
        evals = ValidatorSet([Validator(p.pub_key(), 10) for p in eprivs])
        eby_addr = {p.pub_key().address(): p for p in eprivs}
        elanes = []
        for i, v in enumerate(evals.validators):
            if i in absent:
                elanes.append(CommitSig(BLOCK_ID_FLAG_ABSENT))
                continue
            ts = 1_000_000 + i
            sb = canonical_vote_sign_bytes(chain_id, PRECOMMIT_TYPE,
                                           height, 0, bid, ts)
            elanes.append(CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, ts,
                                    eby_addr[v.address].sign(sb)))
        ed_commit = Commit(height, 0, bid, elanes)
        note(f"n={n}: cold dense verify (builds the valset table)")
        t0 = time.perf_counter()
        VerifyCommitLight(chain_id, evals, bid, height, ed_commit,
                          backend="cpu")
        ed_cold = time.perf_counter() - t0
        ed_warm = warm_min(lambda: VerifyCommitLight(
            chain_id, evals, bid, height, ed_commit, backend="cpu"))

        bls_wire = len(codec.pack(agg_commit))
        ed_wire = len(codec.pack(ed_commit))
        point = {
            "n_vals": n,
            "signers": len(signers),
            "absent": len(absent),
            "bls_agg_verify_ms": round(bls_warm * 1e3, 3),
            "ed25519_batched_ms": round(ed_warm * 1e3, 3),
            "speedup": round(ed_warm / bls_warm, 2),
            "bls_wire_bytes": bls_wire,
            "ed25519_wire_bytes": ed_wire,
            "wire_reduction": round(ed_wire / bls_wire, 2),
            "bls_cold_s": round(bls_cold, 3),
            "ed25519_cold_s": round(ed_cold, 3),
        }
        note(f"n={n}: agg {point['bls_agg_verify_ms']}ms vs dense "
             f"{point['ed25519_batched_ms']}ms -> {point['speedup']}x, "
             f"wire {bls_wire}B vs {ed_wire}B")
        curve.append(point)

    head = curve[-1]
    doc = {"metric": "BLS aggregate-commit verify vs Ed25519 batched "
                     "dense path (warm VerifyCommitLight, CPU host "
                     "crypto)",
           "curve": curve, "backend": "cpu"}
    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        note(f"curve -> {out_path}")
    print(json.dumps({
        "metric": f"BLS aggregate-commit verify speedup vs Ed25519 "
                  f"batched path @{head['n_vals']} validators",
        "value": head["speedup"],
        "unit": "x",
        # acceptance bar: >= 10x at 10k validators; > 1 means met
        "vs_baseline": round(head["speedup"] / 10.0, 2),
        "bls_agg_verify_ms": head["bls_agg_verify_ms"],
        "ed25519_batched_ms": head["ed25519_batched_ms"],
        "wire_reduction": head["wire_reduction"],
        "curve": curve,
        "backend": "cpu",
    }), flush=True)


def _child_profile(out_path: str) -> None:
    """``--mode profile``: the hot-path profiling harness — run one
    scenario-lab scenario (default ``megamix-100``, the 100-node mixed-
    adversary fleet) under ``tracemalloc`` + ``cProfile`` and write a
    ranked top-allocators / top-callers report to ``out_path``.

    This is a *diagnostic* mode, not a guard: its job is to point at
    the dominant allocator and the dominant CPU sink so an optimisation
    PR can kill them and commit before/after reports side by side.
    Numbers here are NOT comparable to ``--mode scenarios`` wall times —
    tracemalloc alone multiplies allocation cost several-fold."""
    import cProfile
    import pstats
    import tracemalloc

    from cometbft_tpu.jaxenv import force_cpu_backend

    force_cpu_backend()
    from cometbft_tpu.sim.scenario import curated_suite, run_scenario

    def note(msg):
        print(f"[bench:profile] {msg}", file=sys.stderr, flush=True)

    want = os.environ.get("BENCH_PROFILE_SCENARIO", "megamix-100")
    cands = [s for s in curated_suite() if s.name == want]
    if not cands:
        raise SystemExit(f"unknown BENCH_PROFILE_SCENARIO {want!r}")
    scn = cands[0]
    top_n = int(os.environ.get("BENCH_PROFILE_TOP", "25"))

    def _rel(path: str) -> str:
        if path.startswith(REPO):
            return path[len(REPO):].lstrip(os.sep)
        # site-packages / stdlib frames: keep the last 3 components
        return os.sep.join(path.split(os.sep)[-3:])

    note(f"profiling {scn.name} ({scn.n_nodes} nodes, "
         f"target h{scn.target_height}) under tracemalloc+cProfile")
    tracemalloc.start(1)           # 1 frame: rank by allocation site
    prof = cProfile.Profile()
    t0 = time.perf_counter()
    prof.enable()
    verdict = run_scenario(scn)
    prof.disable()
    real_s = time.perf_counter() - t0
    snap = tracemalloc.take_snapshot()
    peak_b = tracemalloc.get_traced_memory()[1]
    tracemalloc.stop()

    snap = snap.filter_traces((
        tracemalloc.Filter(False, tracemalloc.__file__),
        tracemalloc.Filter(False, "<frozen importlib._bootstrap>"),
    ))
    allocs = []
    for stat in snap.statistics("lineno")[:top_n]:
        fr = stat.traceback[0]
        allocs.append({"site": f"{_rel(fr.filename)}:{fr.lineno}",
                       "size_kb": round(stat.size / 1024, 1),
                       "count": stat.count})

    st = pstats.Stats(prof)
    rows = []   # (file, line, func, ncalls, tottime, cumtime)
    for (fn, line, func), (_cc, nc, tt, ct, _cal) in st.stats.items():
        rows.append((fn, line, func, nc, tt, ct))

    def _fmt(r):
        fn, line, func, nc, tt, ct = r
        where = func if fn == "~" else f"{_rel(fn)}:{line}({func})"
        return {"func": where, "ncalls": nc,
                "tottime_s": round(tt, 3), "cumtime_s": round(ct, 3)}

    by_tot = [_fmt(r) for r in
              sorted(rows, key=lambda r: -r[4])[:top_n]]
    by_cum = [_fmt(r) for r in
              sorted(rows, key=lambda r: -r[5])[:top_n]]

    doc = {
        "metric": "hot-path profile: one scenario-lab run under "
                  "tracemalloc(1 frame) + cProfile (diagnostic; not "
                  "comparable to --mode scenarios timings)",
        "scenario": scn.name,
        "real_s": round(real_s, 1),
        "virtual_s": verdict["virtual_duration_s"],
        "reached_target": verdict["reached_target"],
        "fork_free": verdict["fork_free"],
        "peak_traced_mb": round(peak_b / 1e6, 1),
        "top_allocators": allocs,
        "top_functions_by_tottime": by_tot,
        "top_functions_by_cumtime": by_cum,
        "backend": "cpu",
    }
    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1)
        note(f"report -> {out_path}")
    top_alloc = allocs[0] if allocs else {}
    note(f"peak traced {doc['peak_traced_mb']} MB; top allocator "
         f"{top_alloc.get('site')} ({top_alloc.get('size_kb')} KB live, "
         f"{top_alloc.get('count')} blocks)")
    print(json.dumps({
        "metric": doc["metric"],
        "value": doc["peak_traced_mb"],
        "unit": "MB-peak",
        "vs_baseline": 1.0 if verdict["reached_target"] else 0.0,
        "scenario": scn.name,
        "real_s": doc["real_s"],
        "top_allocator": top_alloc.get("site"),
        "report": out_path,
        "backend": "cpu",
    }), flush=True)


def _child_main(backend: str, nsig: int) -> None:
    mode = os.environ.get("BENCH_MODE", "commit")
    if mode == "mempool":
        return _child_mempool(
            os.environ.get("BENCH_OUT",
                           os.path.join(REPO, "docs", "bench",
                                        "r16-mempool-cpu.json")))
    if mode == "scenarios":
        return _child_scenarios(
            os.environ.get("BENCH_OUT",
                           os.path.join(REPO, "docs", "bench",
                                        "r16-scenarios-cpu.json")))
    if mode == "statesync":
        return _child_statesync(
            os.environ.get("BENCH_OUT",
                           os.path.join(REPO, "docs", "bench",
                                        "r18-statesync-cpu.json")))
    if mode == "bls":
        return _child_bls(
            os.environ.get("BENCH_OUT",
                           os.path.join(REPO, "docs", "bench",
                                        "r20-bls-cpu.json")))
    if mode == "profile":
        return _child_profile(
            os.environ.get("BENCH_OUT",
                           os.path.join(REPO, "docs", "bench",
                                        "r21-profile-cpu.json")))
    if mode == "node":
        return _child_node(float(os.environ.get("BENCH_RATE", "2000")),
                           float(os.environ.get("BENCH_DURATION", "20")),
                           int(os.environ.get("BENCH_TX_SIZE", "256")))
    if mode == "light-serve":
        return _child_lightserve(
            int(os.environ.get("BENCH_LS_CLIENTS", "10000")),
            int(os.environ.get("BENCH_LS_CONNS", "32")),
            int(os.environ.get("BENCH_LS_TXS", "512")),
            int(os.environ.get("BENCH_LS_PROOFS", "8")))
    if mode == "light":
        return _child_light(backend,
                            int(os.environ.get("BENCH_HEADERS", "1000")),
                            int(os.environ.get("BENCH_VALS", "32")))
    if mode == "blocksync":
        return _child_blocksync(backend,
                                int(os.environ.get("BENCH_BLOCKS", "500")),
                                int(os.environ.get("BENCH_VALS", "32")))
    if mode == "verifycommit":
        return _child_verifycommit(backend,
                                   int(os.environ.get("BENCH_VALS", "150")))
    if mode == "stress":
        return _child_stress(backend,
                             int(os.environ.get("BENCH_VALS", "10000")),
                             int(os.environ.get("BENCH_SECP_PCT", "10")))
    if mode == "p50commit":
        return _child_p50commit(backend,
                                int(os.environ.get("BENCH_VALS", "10000")))
    if mode == "merkle":
        return _child_merkle(backend,
                             int(os.environ.get("BENCH_MERKLE_LEAVES",
                                                "10000")),
                             int(os.environ.get("BENCH_MERKLE_BLOCK_KB",
                                                "4096")))
    if mode == "vote-gossip":
        return _child_votegossip(backend,
                                 int(os.environ.get("BENCH_VALS", "256")),
                                 int(os.environ.get("BENCH_DUP_K", "3")),
                                 int(os.environ.get("BENCH_SLOTS", "4")))
    if mode == "mesh":
        return _child_mesh(backend, os.environ.get(
            "BENCH_OUT", os.path.join(REPO, "docs", "bench",
                                      f"r19-mesh-{backend}.json")))

    def note(msg):
        print(f"[bench:{backend}] {msg}", file=sys.stderr, flush=True)

    import numpy as np

    from cometbft_tpu.crypto.keys import verify_ed25519_zip215
    from cometbft_tpu.jaxenv import enable_compile_cache, force_cpu_backend
    from cometbft_tpu.testing import dense_signature_batch

    note("building signature batch")
    batch_args, host_items = dense_signature_batch(nsig, msg_len=120,
                                                   seed=2024)

    if backend == "cpu":
        # No accelerator: the device kernel emulated on one CPU core is
        # not what a CPU-only node runs.  Measure the production CPU
        # fallback (crypto/batch CpuBatchVerifier over host crypto)
        # against the single-verify loop instead.
        force_cpu_backend()
        from cometbft_tpu.crypto.batch import create_batch_verifier

        def run_batch():
            bv = create_batch_verifier("cpu")
            from cometbft_tpu.crypto.keys import Ed25519PubKey

            for pk, msg, sig in host_items:
                bv.add(Ed25519PubKey(pk), msg, sig)
            ok, _ = bv.verify()
            assert ok

        note("timing production CPU batch path")
        run_batch()
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            run_batch()
            times.append(time.perf_counter() - t0)
        p50 = float(np.percentile(times, 50))

        cpu_per_sig = _single_verify_us(host_items) / 1e6

        vs_single = (cpu_per_sig * nsig) / p50
        print(json.dumps({
            "metric": "ed25519 sig-verifies/sec/chip "
                      "(extended-commit-shaped batch)",
            "value": round(nsig / p50, 1),
            "unit": "sigs/s",
            # reference-relative: voi's CPU batch path is ~2x its single
            # verify, so the honest comparison halves the single-loop win
            "vs_baseline": round(vs_single / 2.0, 2),
            "vs_single_loop": round(vs_single, 2),
            "vs_reference_batch_est": round(vs_single / 2.0, 2),
            "p50_batch_latency_ms": round(p50 * 1e3, 3),
            "batch_size": nsig,
            "backend": "cpu",
            "device": "host (no accelerator; production CPU fallback path)",
            "cpu_single_verify_us": round(cpu_per_sig * 1e6, 1),
        }), flush=True)
        return

    import jax

    from cometbft_tpu.ops import ed25519, rlc

    enable_compile_cache()

    note("initializing backend")
    dev = jax.devices()[0]
    note(f"device = {dev}")
    if backend == "tpu" and dev.platform == "cpu":
        # jax silently fell back to CPU: fail so the parent runs the
        # properly-sized CPU attempt instead of mislabeling this one.
        raise RuntimeError("requested accelerator but got CPU backend")
    fn = jax.jit(ed25519.verify_padded)
    args = jax.device_put(batch_args, dev)
    note("compiling + first run (per-lane straus)")
    t0 = time.perf_counter()
    out = np.asarray(fn(*args))
    note(f"compile+run took {time.perf_counter() - t0:.1f}s")
    assert out.all(), "benchmark batch failed verification"

    reps = int(os.environ.get("BENCH_REPS", "10" if backend != "cpu" else "5"))
    profile_dir = os.environ.get("BENCH_PROFILE", "")
    if profile_dir:
        # tracing/profiling hook (SURVEY §5): captures an XLA/JAX trace of
        # the timed loop, viewable in TensorBoard/Perfetto
        note(f"capturing jax profiler trace to {profile_dir}")
        jax.profiler.start_trace(profile_dir)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args)[0].block_until_ready()
        times.append(time.perf_counter() - t0)
    if profile_dir:
        jax.profiler.stop_trace()
    p50_straus = float(np.percentile(times, 50))

    # RLC batch kernel: the production fast path for batches >= the RLC
    # threshold (one all-or-nothing verdict; ~3x less group-op work)
    note("compiling + first run (rlc batch)")
    z = rlc.host_rlc_coeffs(nsig, np.ones(nsig, bool))
    rfn = jax.jit(rlc.verify_batch_rlc)
    rargs = jax.device_put(batch_args + (z,), dev)
    from cometbft_tpu.crypto import rlc_finish

    t0 = time.perf_counter()
    rok, _ = rlc_finish.finish(rfn(*rargs))
    note(f"compile+run took {time.perf_counter() - t0:.1f}s")
    assert rok, "RLC rejected the benchmark batch"
    rtimes = []
    for _ in range(reps):
        t0 = time.perf_counter()
        rlc_finish.finish(rfn(*rargs))      # the verdict: sums + host fold
        rtimes.append(time.perf_counter() - t0)
    p50_rlc = float(np.percentile(rtimes, 50))

    # the production router dispatches RLC first at this batch size, so
    # the headline is the better of the two (they verify the same batch)
    p50 = min(p50_straus, p50_rlc)
    sigs_per_sec = nsig / p50

    # Host baseline: single-verify over a sample, extrapolated to nsig.
    cpu_per_sig = _single_verify_us(host_items) / 1e6
    vs_single = (cpu_per_sig * nsig) / p50

    print(json.dumps({
        "metric": "ed25519 sig-verifies/sec/chip "
                  "(extended-commit-shaped batch)",
        "value": round(sigs_per_sec, 1),
        "unit": "sigs/s",
        "vs_baseline": round(vs_single / 2.0, 2),
        "vs_single_loop": round(vs_single, 2),
        "vs_reference_batch_est": round(vs_single / 2.0, 2),
        "p50_batch_latency_ms": round(p50 * 1e3, 3),
        "straus_sigs_per_sec": round(nsig / p50_straus, 1),
        "rlc_sigs_per_sec": round(nsig / p50_rlc, 1),
        "rlc_vs_straus": round(p50_straus / p50_rlc, 2),
        "batch_size": nsig,
        "backend": backend,
        "device": str(dev),
        "cpu_single_verify_us": round(cpu_per_sig * 1e6, 1),
    }), flush=True)


# --------------------------------------------------------------------------
# parent: runs the one attempt; never imports jax; fails when it fails
# --------------------------------------------------------------------------

def _run_attempt(backend: str, nsig: int, timeout_s: float) -> dict | None:
    env = dict(os.environ)
    if backend == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, os.path.abspath(__file__),
           "--_child", backend, str(nsig)]
    print(f"[bench] attempt backend={backend} nsig={nsig} "
          f"timeout={timeout_s:.0f}s", file=sys.stderr, flush=True)
    try:
        proc = subprocess.run(cmd, env=env, timeout=timeout_s,
                              stdout=subprocess.PIPE, stderr=sys.stderr)
    except subprocess.TimeoutExpired:
        print(f"[bench] backend={backend} TIMED OUT after {timeout_s:.0f}s",
              file=sys.stderr, flush=True)
        return None
    if proc.returncode != 0:
        print(f"[bench] backend={backend} exited rc={proc.returncode}",
              file=sys.stderr, flush=True)
        return None
    for line in reversed(proc.stdout.decode(errors="replace").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


def main() -> None:
    nsig_tpu = int(os.environ.get("BENCH_NSIG", "10240"))
    # the headline shape is a 10k-validator EXTENDED commit (2 sigs/val,
    # chunked at the 4096-lane cap): production CPU batches are huge,
    # so a small default would UNDERstate the per-sig rate the node
    # actually sees (Pippenger's per-point cost falls with batch size)
    nsig_cpu = int(os.environ.get("BENCH_NSIG_CPU", "8192"))
    t_tpu = float(os.environ.get("BENCH_TPU_TIMEOUT", "480"))
    t_cpu = float(os.environ.get("BENCH_CPU_TIMEOUT", "900"))

    forced = os.environ.get("BENCH_BACKEND", "").strip().lower()
    pinned = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    # the full-stack modes' children hard-force CPU: their bottleneck is
    # the node, not a device leg
    host_mode = os.environ.get("BENCH_MODE") in (
        "node", "light-serve", "scenarios", "mempool", "statesync", "bls",
        "profile")
    if forced == "cpu" or pinned or host_mode:
        if forced == "tpu":
            print("[bench] BENCH_BACKEND=tpu, but "
                  + ("JAX_PLATFORMS pins cpu" if pinned else
                     "this mode measures the host"),
                  file=sys.stderr, flush=True)
            sys.exit(1)
        attempt = ("cpu", nsig_cpu, t_cpu)
    else:
        attempt = ("tpu", nsig_tpu, t_tpu)
    result = _run_attempt(*attempt)
    if result is None:
        print(f"[bench] backend={attempt[0]} attempt failed; no result",
              file=sys.stderr, flush=True)
        sys.exit(1)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--_child":
        _child_main(sys.argv[2], int(sys.argv[3]))
    elif len(sys.argv) >= 5 and sys.argv[1] == "--_mesh_gauge":
        # fresh-process half of `--mode mesh`'s first-dispatch proof
        _mesh_gauge_child(sys.argv[2], int(sys.argv[3]), int(sys.argv[4]))
    else:
        # `--mode X` is sugar for BENCH_MODE=X (the env var wins if both
        # are set, matching every other BENCH_* knob)
        argv = sys.argv[1:]
        if "--mode" in argv:
            i = argv.index("--mode")
            if i + 1 >= len(argv):
                print("--mode requires a value", file=sys.stderr)
                sys.exit(2)
            os.environ.setdefault("BENCH_MODE", argv[i + 1])
        main()
