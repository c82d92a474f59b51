"""Multi-node throughput artifact: an N-validator
testnet ON ONE BOX driven with timestamped load, reported the way the
reference's QA method does (tx/s, latency percentiles, blocks/min —
docs/references/qa/CometBFT-QA-v1.md:152-171 + test/loadtime/).

Honesty: the reference's headline (~400 tx/s saturation) comes from a
200-node multi-region DO testnet; this artifact is 4 validators sharing
ONE CPU core with emulated p2p latency — same methodology, not the same
hardware.  The JSON records both.

  python scripts/testnet_bench.py [--nodes 4] [--rate 1000] [--duration 30]
        [--latency-ms 50] [--out docs/bench/r04-testnet.json]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BASE_P2P = 29100
BASE_RPC = 29200


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--rate", type=float, default=1000.0)
    ap.add_argument("--duration", type=float, default=30.0)
    ap.add_argument("--latency-ms", type=float, default=50.0)
    ap.add_argument("--tx-size", type=int, default=256)
    ap.add_argument("--out", default="docs/bench/r04-testnet.json")
    args = ap.parse_args()

    from cometbft_tpu.e2e.gen import HomeSpec, generate_homes

    base = tempfile.mkdtemp(prefix="testnet-bench-")
    chain_id = f"testnet-bench-{os.getpid()}"
    specs = [HomeSpec(name=f"n{i}", p2p_port=BASE_P2P + i,
                      rpc_port=BASE_RPC + i, power=10)
             for i in range(args.nodes)]

    def tweak(spec, cfg):
        from cometbft_tpu.config import MS, ConsensusConfig

        cfg.base.signature_backend = "cpu"
        # QA-representative timeouts scaled for one shared core: long
        # enough that a CheckTx burst cannot starve a proposal round
        # into churn (the stock test config's 80ms propose collapses
        # under saturation load on this box), short enough for useful
        # block cadence
        cfg.consensus = ConsensusConfig(
            timeout_propose=1000 * MS, timeout_propose_delta=500 * MS,
            timeout_prevote=500 * MS, timeout_prevote_delta=250 * MS,
            timeout_precommit=500 * MS, timeout_precommit_delta=250 * MS,
            timeout_commit=500 * MS, peer_gossip_sleep_duration=20 * MS)
        cfg.mempool.size = 20000
        cfg.p2p.emulated_latency_ms = args.latency_ms

    generate_homes(base, specs, chain_id, tweak=tweak)

    procs = []
    ttl = int(args.duration) + 240
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    try:
        for spec in specs:
            lf = open(os.path.join(base, f"{spec.name}.log"), "ab")
            procs.append(subprocess.Popen(
                ["timeout", str(ttl), sys.executable, "-m", "cometbft_tpu",
                 "--home", os.path.join(base, spec.name), "start"],
                stdout=lf, stderr=subprocess.STDOUT, env=env, cwd=REPO))
        result = asyncio.run(_drive(args, specs, chain_id))
        result["nodes"] = args.nodes
        result["emulated_latency_ms"] = args.latency_ms
        result["note"] = (
            f"{args.nodes} validators sharing one CPU core on one box, "
            f"{args.latency_ms}ms emulated p2p latency; QA-method load/"
            "report (loadtime), NOT the reference's 200-node multi-region "
            "testnet hardware")
        out = json.dumps(result)
        print(out, flush=True)
        if args.out:
            with open(os.path.join(REPO, args.out), "w") as f:
                f.write(out + "\n")
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        # keep logs on failure for diagnosis; remove on success
        if "result" in dir():
            shutil.rmtree(base, ignore_errors=True)
        else:
            print(f"[testnet-bench] logs kept under {base}",
                  file=sys.stderr)


async def _drive(args, specs, chain_id) -> dict:
    from cometbft_tpu import loadtime
    from cometbft_tpu.rpc import HTTPClient

    ports = [s.rpc_port for s in specs]
    clis = [HTTPClient("127.0.0.1", p) for p in ports]

    def note(msg):
        print(f"[testnet-bench] {msg}", file=sys.stderr, flush=True)

    note(f"waiting for {len(ports)} nodes + full mesh")
    deadline = time.monotonic() + 120
    while True:
        try:
            sts = [await c.call("status") for c in clis]
            if all(s["node_info"]["network"] == chain_id for s in sts):
                nets = [await c.call("net_info") for c in clis]
                if all(n["n_peers"] >= len(ports) - 1 for n in nets):
                    break
        except Exception:
            pass
        if time.monotonic() > deadline:
            raise RuntimeError("testnet failed to form a full mesh")
        await asyncio.sleep(1.0)

    note("mesh up; waiting for first committed blocks")
    while (await clis[0].call("status"))["sync_info"][
            "latest_block_height"] < 2:
        await asyncio.sleep(0.5)

    h0 = (await clis[0].call("status"))["sync_info"]["latest_block_height"]
    t_load0 = time.time()
    note(f"driving {args.rate} tx/s for {args.duration}s at node 0")
    gen = await loadtime.generate(clis[0], args.rate, args.duration,
                                  tx_size=args.tx_size, connections=6,
                                  batch=8)

    # drain-poll on a cheap signal (tip height + block tx counts would
    # still rescan; num_unconfirmed_txs is O(1)) and run the full
    # chain-scan report ONCE afterwards — re-reporting from genesis every
    # poll is O(blocks^2) RPC load against the node being measured
    note(f"sent {gen['sent']} txs; waiting for drain")
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        try:
            unc = await clis[0].call("num_unconfirmed_txs")
            if int(unc.get("n_txs", unc.get("total", 0))) == 0:
                break
        except Exception:
            pass
        await asyncio.sleep(1.0)
    load_wall_s = time.time() - t_load0

    rep = await loadtime.report(clis[0], run_id=gen["run_id"],
                                min_height=max(1, h0))
    sts = [await c.call("status") for c in clis]
    heights = [s["sync_info"]["latest_block_height"] for s in sts]
    h1 = max(heights)

    # liveness: every node within a couple of blocks of the max
    assert h1 - min(heights) <= 3, f"node fell behind: {heights}"

    blocks = h1 - h0
    return {
        "metric": f"{len(ports)}-validator testnet throughput "
                  f"({args.tx_size}B txs, kvstore)",
        "value": rep.get("throughput_tx_s") or round(
            rep.get("txs", 0) / max(load_wall_s, 1e-9), 2),
        "unit": "tx/s",
        "vs_baseline": round((rep.get("throughput_tx_s") or 0.0) / 400.0,
                             2),
        "sent": gen["sent"],
        "committed": rep.get("txs", 0),
        "send_errors": gen.get("errors", 0),
        "p50_latency_s": rep.get("p50_s"),
        "p90_latency_s": rep.get("p90_s"),
        "p99_latency_s": rep.get("p99_s"),
        "blocks": blocks,
        "blocks_per_min": round(blocks / max(load_wall_s / 60, 1e-9), 1),
        "heights": heights,
        "backend": "cpu",
    }


if __name__ == "__main__":
    main()
