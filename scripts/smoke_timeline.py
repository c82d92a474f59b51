#!/usr/bin/env python
"""CI height-timeline smoke: boot a tracing-enabled validator plus a
TCP-connected observer, commit 3 heights, then fetch the waterfall
projection the way an operator would —

- ``GET /consensus_timeline?n=K`` must answer 200 with per-height
  waterfalls for every committed height,
- each complete waterfall's phases must be a prefix-ordered subset of
  the canonical classification (propose -> gossip -> prevote -> precommit ->
  commit) with contiguous, non-negative segments,
- the residual buckets (gossip_wait/verify/app/wal/idle) must sum to
  the measured commit latency — never more,
- ``height=H`` must select exactly height H,
- ``/dump_trace?sub=consensus&height=H`` must serve only records
  stamped with that height (the filter discipline ``libs/timeline``
  keys on).

Exit 0 on success, 1 with a reason on any failure.  Used by the lint
workflow's smoke job (`.github/workflows/lint.yml`); runnable locally:

    JAX_PLATFORMS=cpu python scripts/smoke_timeline.py
"""

import asyncio
import json
import os
import sys
import urllib.error
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TARGET_HEIGHT = 3


def fetch(url: str) -> tuple[int, bytes]:
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def check_waterfall(wf: dict, phase_order: list) -> str | None:
    """Return a failure reason, or None if the waterfall is sound."""
    phases = [p["phase"] for p in wf["phases"]]
    # present phases must appear in classification order (absent marks — a
    # catch-up commit, an evicted record — drop phases, never reorder)
    idx = [phase_order.index(p) for p in phases if p in phase_order]
    if len(idx) != len(phases) or idx != sorted(idx):
        return f"phases out of order: {phases}"
    if "propose" not in phases:
        return f"missing propose phase: {phases}"
    cursor = 0.0
    for p in wf["phases"]:
        if p["dur_s"] < 0 or p["start_s"] < cursor - 1e-5:
            return f"non-contiguous segment {p} (cursor {cursor})"
        cursor = p["start_s"] + p["dur_s"]
    if cursor > wf["total_s"] + 1e-5:
        return f"phases overrun total: {cursor} > {wf['total_s']}"
    bsum = sum(wf["buckets"].values())
    if bsum > wf["total_s"] + 1e-5:
        return f"buckets exceed commit latency: {bsum} > {wf['total_s']}"
    if any(v < 0 for v in wf["buckets"].values()):
        return f"negative bucket: {wf['buckets']}"
    return None


async def main() -> int:
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.config import Config, test_consensus_config
    from cometbft_tpu.node import Node
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
    from cometbft_tpu.types.priv_validator import MockPV

    def _cfg() -> Config:
        cfg = Config(consensus=test_consensus_config())
        cfg.p2p.laddr = "tcp://127.0.0.1:0"
        cfg.rpc.laddr = "tcp://127.0.0.1:0"
        cfg.instrumentation.tracing = True
        return cfg

    pv = MockPV.from_secret(b"smoke-timeline")
    doc = GenesisDoc(chain_id="smoke-tl-net",
                     validators=[GenesisValidator(pv.get_pub_key(), 10)])
    node = await Node.create(doc, KVStoreApplication(), priv_validator=pv,
                             config=_cfg(), name="tl0")
    await node.start()
    cfg2 = _cfg()
    cfg2.rpc.laddr = ""
    observer = await Node.create(doc, KVStoreApplication(), config=cfg2,
                                 name="tl1")
    await observer.start()
    loop = asyncio.get_running_loop()
    try:
        await observer.dial_peer(node.listen_addr, persistent=False)
        for _ in range(600):
            if node.block_store.height() >= TARGET_HEIGHT:
                break
            await asyncio.sleep(0.05)
        else:
            print(f"FAIL: never reached height {TARGET_HEIGHT}",
                  file=sys.stderr)
            return 1
        host, port = node.rpc_addr
        base = f"http://{host}:{port}"

        status, body = await loop.run_in_executor(
            None, fetch, base + "/consensus_timeline?n=10")
        if status != 200:
            print(f"FAIL: /consensus_timeline -> HTTP {status}",
                  file=sys.stderr)
            return 1
        result = json.loads(body).get("result") or {}
        if not result.get("enabled"):
            print("FAIL: /consensus_timeline reports tracing disabled",
                  file=sys.stderr)
            return 1
        order = result.get("phases") or []
        if order[:2] != ["propose", "gossip"]:
            print(f"FAIL: bad phase classification {order}", file=sys.stderr)
            return 1
        wfs = result.get("waterfalls") or []
        done = [w for w in wfs if w["complete"]]
        if len(done) < TARGET_HEIGHT:
            print(f"FAIL: {len(done)} complete waterfalls, want "
                  f">= {TARGET_HEIGHT} (of {len(wfs)})", file=sys.stderr)
            return 1
        for wf in done:
            reason = check_waterfall(wf, order)
            if reason:
                print(f"FAIL: h{wf['height']}: {reason}", file=sys.stderr)
                return 1
        # the steady-state heights saw the full vote ladder
        full = [w for w in done
                if [p["phase"] for p in w["phases"]] == order]
        if not full:
            print("FAIL: no waterfall shows all five phases",
                  file=sys.stderr)
            return 1

        status, body = await loop.run_in_executor(
            None, fetch, base + "/consensus_timeline?height=2")
        one = (json.loads(body).get("result") or {}).get("waterfalls") or []
        if {w["height"] for w in one} != {2}:
            print(f"FAIL: height=2 filter returned "
                  f"{[w['height'] for w in one]}", file=sys.stderr)
            return 1

        status, body = await loop.run_in_executor(
            None, fetch, base + "/dump_trace?sub=consensus&height=2&limit=500")
        recs = (json.loads(body).get("result") or {}).get("records") or []
        if not recs:
            print("FAIL: filtered /dump_trace returned nothing",
                  file=sys.stderr)
            return 1
        for r in recs:
            if r["sub"] != "consensus":
                print(f"FAIL: sub filter leaked {r['sub']}", file=sys.stderr)
                return 1
            a = r["attrs"]
            h_ok = a.get("height") == 2 or \
                (a.get("h_lo", 99) <= 2 <= a.get("h_hi", -1))
            if not h_ok:
                print(f"FAIL: height filter leaked {a}", file=sys.stderr)
                return 1

        print(f"smoke ok: height={node.block_store.height()} "
              f"waterfalls={len(wfs)} complete={len(done)} "
              f"full_phase={len(full)} "
              f"p50_total={sorted(w['total_s'] for w in done)[len(done)//2]}s")
        return 0
    finally:
        await observer.stop()
        await node.stop()


if __name__ == "__main__":
    sys.exit(asyncio.run(main()))
