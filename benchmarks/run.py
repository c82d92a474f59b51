#!/usr/bin/env python3
"""One cell of BENCHMARK.json, one process, one result line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, native libraries, data from the seed, the cell's programs
warmed) is timed as ``setup_s``; then the cell's driver offers its traffic for
``--seconds``; then, with the window closed and the peak memory read, every
answer the window gave is compared with the plain reference's.  The last line
of standard output is the result.  Anything but a TPU exits non-zero, unless
``--rehearse`` (tests only) says that a CPU will do: its line says so.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse                                             # noqa: E402
import importlib                                            # noqa: E402
import json                                                 # noqa: E402
import os                                                   # noqa: E402
import sys                                                  # noqa: E402
import threading                                            # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SETUP_DEVICE_WAIT_S = 900.0     # wait a cold compile out, never abandon it
WARM_MESSAGE_BYTES = 120        # any width that pads to the same two blocks


def note(**obj) -> None:
    """An earlier line of standard output: context, never the result."""
    print(json.dumps(obj, default=str), flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, rehearse: bool = False) -> dict:
    """The cell's entry of BENCHMARK.json with its configuration, its traffic
    mix and its metrics, each found by name."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(ROOT, cfg_entry["file"])
    mix = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if rehearse:                # tests: the 16-lane bucket tier-1 compiles
        cfg.update(cfg.get("rehearse", {}))
        mix.update(mix.get("rehearse", {}))

    def mine(m):
        return name in m.get("workloads", [name])
    return {"cell": cell, "config": cfg, "mix": mix,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def find_devices(chips: int, rehearse: bool):
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not rehearse:
        raise SystemExit(f"benchmark needs a TPU; JAX found {platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"cell needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def warm(ring, lanes: int) -> None:
    """Compile (or load) what a dispatch of ``lanes`` runs, by the device
    entry itself: zero-filled lanes over the ring's own validator table, with
    a non-canonical S in lane 0, so that the batch verdict refutes and the
    per-lane kernel that localizes it runs too (all-zero lanes verify)."""
    import numpy as np
    from cometbft_tpu.crypto import batch as cryptobatch

    z = np.zeros((lanes, 32), np.uint8)
    ss = z.copy()
    ss[0] = 0xFF
    cryptobatch.device_verify_ed25519_cached(
        ring.vals.dense()[0], np.zeros((lanes,), np.int64), z, z, ss,
        np.zeros((lanes, WARM_MESSAGE_BYTES), np.uint8),
        np.full((lanes,), WARM_MESSAGE_BYTES, np.int64))


def warm_all(ring, lanes: list) -> None:
    errors = []

    def one(n):
        try:
            warm(ring, n)
        except BaseException as e:
            errors.append(e)
    threads = [threading.Thread(target=one, args=(n,), name=f"bench-warm-{n}")
               for n in lanes]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def traced_verify_dense():
    """Wrap the backend seam in a span (traced runs only); returns the undo."""
    import jax
    from cometbft_tpu.crypto import batch as cryptobatch

    real = cryptobatch.verify_dense

    def verify_dense(*a, **kw):
        with jax.profiler.TraceAnnotation("bench:verify_dense"):
            return real(*a, **kw)
    cryptobatch.verify_dense = verify_dense
    return lambda: setattr(cryptobatch, "verify_dense", real)


def start_trace():
    """The profiler's own session (what ``jax.profiler.start_trace`` wraps),
    stopped later WITHOUT its export: writing the ``.xplane.pb`` and the trace
    viewer's JSON of one or two seconds of these kernels took 104-156 s and
    some 370 MB of disk; the serialized trace is read from memory instead."""
    import jax
    from jax._src.lib import _profiler

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return _profiler.ProfilerSession(opts)


def breakdown(trace) -> dict:
    """The device operations that took most time, and the chip's idle time by
    the harness span the host was in (innermost first; what no span covers
    is the loop's own)."""
    from benchmarks.reduce.xplane import Intervals

    gaps = trace.busy[trace.chips[0]].gaps(*trace.window)
    idle, union, seen = {}, [], 0.0
    for name in ("bench:verify_dense", "bench:entry", "bench:present"):
        union += trace.spans_named(name)        # callers' spans may overlap
        merged = Intervals(*zip(*union)) if union else None
        inside = sum(gaps.covered(s, e) for s, e in
                     zip(merged.starts, merged.ends)) if merged else 0.0
        idle[name], seen = inside - seen, inside
    idle["bench:window"] = gaps.total() - seen

    def top(d):
        return sorted(([k, v] for k, v in d.items() if v > 0),
                      key=lambda kv: -kv[1])[:10]
    return {"device_ops": top(trace.op_seconds), "idle_gaps": top(idle)}


def judge(driver, ref, calls, health: tuple, jit: tuple) -> tuple:
    """Every number ``correct`` compares (each with the limit 0), and the calls
    whose answer differs from the plain reference's: ``ref`` judges each
    distinct commit once, whatever the window's calls made of it."""
    wrong = []
    for c in calls:
        want = driver.expected(ref, driver.units(c.unit))
        if c.verdict != want:
            wrong.append((c.unit, c.verdict, want))
    (h0, h1), (j0, j1) = health, jit
    sent = len(calls) * driver.lanes_per_call
    return {
        "wrong_answers": len(wrong),
        "lanes_off_device": max(sent - (h1["device_lanes"] - h0["device_lanes"]),
                                h1["host_lanes"] - h0["host_lanes"]),
        "abandoned_dispatches": h1["abandoned"] - h0["abandoned"],
        "device_degraded": h1["degraded"],
        "programs_built_in_window": j1["programs"] - j0["programs"],
    }, wrong


def per_layer_values(spec: dict, ctx: dict) -> dict:
    """Each per-layer metric of the cell through its own reader; one that finds
    nothing to read is left out."""
    values = {}
    for m in spec["per_layer"]:
        how = load_json(HERE, "metrics", m["name"] + ".json")
        got = importlib.import_module(
            f"benchmarks.reduce.{how['reducer']}").reduce(ctx, **how["args"])
        if got is not None:
            values[m["name"]] = got
    return values


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: run on the CPU at the rehearsal sizes")
    ap.add_argument("--control", default="",
                    help="drive a stand-in that breaks one guarantee "
                         "(benchmarks/reference.py); must come out not correct")
    args = ap.parse_args(argv)
    t_start = T_START if argv is None else time.time()

    spec = load_cell(args.workload, args.rehearse)
    cell, cfg, mix = spec["cell"], spec["config"], spec["mix"]
    devices = find_devices(cell["chips"], args.rehearse)
    backend = "jax" if args.rehearse else "tpu"

    from cometbft_tpu import native
    from cometbft_tpu.crypto import batch as cryptobatch
    from cometbft_tpu.crypto import plan as deviceplan
    from cometbft_tpu.jaxenv import compile_cache_dir

    from benchmarks import counters, data, loop, reference

    ledger = counters.JitLedger()
    native.lib_path("ed25519")          # built here by g++ on a first run
    t0 = time.time()
    ring = data.make_ring(cfg, args.seed,
                          min(mix["ring_blocks"], cfg.get("blocks", 1 << 30)))
    driver = importlib.import_module(
        f"benchmarks.drivers.{mix['driver']}").Driver(ring, mix, args.seed)
    data_s = time.time() - t0

    def real_entry(blocks, presented):
        return driver.call(blocks, presented, backend)
    entry = real_entry
    if args.control == "host_route":    # the program's own host path
        entry = lambda blocks, presented: driver.call(blocks, presented, "cpu")  # noqa: E731
    elif args.control:
        stand_in = reference.control(args.control, ring)
        entry = lambda blocks, presented: stand_in(driver.kind, blocks)  # noqa: E731

    # warm only what this cell's traffic dispatches, then drive the real entry
    # once with a clean and once with a tampered unit
    t0 = time.time()
    cryptobatch.set_device_wait(SETUP_DEVICE_WAIT_S)
    if not args.control:        # a stand-in never reaches the device
        warm_all(ring, driver.warm_lanes(deviceplan.active().lane_buckets[-1]))
        for i in driver.prime_units():
            blocks = driver.units(i)
            real_entry(blocks, [data.present(ring, b) for b in blocks])
    cryptobatch.set_device_wait(mix["device_wait_s"])
    health0, jit0 = counters.device_health(), ledger.report()
    note(step="setup", workload=cell["name"], seed=args.seed, data_s=data_s,
         warm_s=time.time() - t0, compile_cache_dir=compile_cache_dir(),
         jit=jit0, first_dispatch_s=health0["first_dispatch_s"])
    # a few seconds of these kernels are millions of device events: a traced
    # run measures the mix's shorter stretch
    seconds = min(args.seconds, mix["trace_seconds"]) if args.trace else args.seconds
    undo = traced_verify_dense() if args.trace else None
    session = start_trace() if args.trace else None
    setup_s = time.time() - t_start

    # ------------------------------------------------------------ the window
    try:
        calls, w0, w1 = loop.run(driver.units, lambda b: data.present(ring, b),
                                 entry, mix["callers"], seconds, traced=bool(args.trace))
    finally:
        if args.trace:
            t0 = time.time()
            xspace = session.stop()
            undo()
            note(step="trace_stopped", seconds=time.time() - t0, bytes=len(xspace))
    health1, jit1 = counters.device_health(), ledger.report()
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)
    note(step="window", window_s=w1 - w0, **driver.rate_line(calls, w0, w1))

    t0 = time.time()
    ref = reference.Reference(ring)
    checks, wrong = judge(driver, ref, calls, (health0, health1), (jit0, jit1))
    correct = bool(calls) and all(v == 0 for v in checks.values())
    failed = min(len(calls), len(wrong) + -(-int(checks["lanes_off_device"])
                                            // driver.lanes_per_call))
    note(step="reference", seconds=time.time() - t0,
         lanes_checked=ref.lanes_checked, first_wrong=wrong[:3])

    # the program's own host path beside the device's, for scale (not a metric)
    clean = driver.units(driver.prime_units()[0])
    t0 = time.perf_counter()
    for _ in range(mix["host_rate_calls"]):
        driver.call(clean, [data.present(ring, b) for b in clean], "cpu")
    host_s = (time.perf_counter() - t0) / mix["host_rate_calls"]
    note(step="host_path", backend="cpu", call_s=host_s,
         blocks_per_s=len(clean) / host_s,
         sigs_per_s=driver.lanes_per_call / host_s)

    # ---------------------------------------------------------------- metrics
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(calls), "failed": failed}
    if not args.trace:
        values = dict(driver.end_to_end(calls, w0, seconds), setup_s=setup_s)
        wanted = spec["end_to_end"]
    else:
        from benchmarks.reduce import device_idle, xplane

        t0 = time.time()
        trace = xplane.read(xspace)
        del xspace
        note(step="trace_read", seconds=time.time() - t0)
        busy, window = device_idle.busy_and_window(trace)
        device.update(busy_s=busy, window_s=window)
        ctx = {"trace": trace, "calls": calls, "device_kind": dev.device_kind,
               "lanes_per_call": driver.lanes_per_call,
               "message_bytes": len(data.vote_sign_bytes(
                   ring.chain_id, 1, b"\0" * 32, 1, b"\0" * 32, data.BASE_TIME_NS))}
        values = per_layer_values(spec, ctx)
        wanted = spec["per_layer"]
        if trace.chips:
            result["breakdown"] = breakdown(trace)
    result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                         for m in wanted if m["name"] in values}
    result["device"] = device
    result["checks"] = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    print(json.dumps(result), flush=True)
    for k, v in checks.items():
        print(f"check {k}: {v} (limit 0)", file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
