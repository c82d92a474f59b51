"""CLI + multi-process e2e: `testnet` generates wired homes, `start` runs
real node processes, RPC drives them — the reference's e2e tier
(``test/e2e/README.md``) on one machine:
"the tier-2 testnet driven through the CLI + RPC instead of test harness
internals"."""

import asyncio
import json
import os
import signal
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.timeout(150)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASE_PORT = 28600


def _run_cli(*args, home=None):
    cmd = [sys.executable, "-m", "cometbft_tpu"]
    if home:
        cmd += ["--home", home]
    cmd += list(args)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=60)


def test_cli_init_and_key_commands(tmp_path):
    home = str(tmp_path / "node")
    res = _run_cli("init", "--chain-id", "cli-chain", "--moniker", "m0",
                   home=home)
    assert res.returncode == 0, res.stderr
    assert os.path.exists(f"{home}/config/config.toml")
    assert os.path.exists(f"{home}/config/genesis.json")
    assert os.path.exists(f"{home}/config/node_key.json")
    assert os.path.exists(f"{home}/config/priv_validator_key.json")

    rid = _run_cli("show-node-id", home=home)
    assert rid.returncode == 0 and len(rid.stdout.strip()) == 40

    rv = _run_cli("show-validator", home=home)
    assert rv.returncode == 0
    assert json.loads(rv.stdout)["type"] == "ed25519"

    rgv = _run_cli("gen-validator", home=home)
    assert rgv.returncode == 0
    assert "priv_key" in json.loads(rgv.stdout)

    rver = _run_cli("version", home=home)
    assert rver.returncode == 0 and rver.stdout.strip()

    # config round-trips through the TOML loader
    from cometbft_tpu.config import Config

    cfg = Config.load(f"{home}/config/config.toml")
    assert cfg.base.moniker == "m0"

    rr = _run_cli("unsafe-reset-all", home=home)
    assert rr.returncode == 0, rr.stderr



def _patch_testnet_configs(base, n=4):
    """Shrink consensus timeouts + pin the CPU backend for test speed."""
    from cometbft_tpu.config import Config

    for i in range(n):
        cfgp = f"{base}/node{i}/config/config.toml"
        cfg = Config.load(cfgp)
        cfg.consensus.timeout_propose = 300_000_000
        cfg.consensus.timeout_propose_delta = 100_000_000
        cfg.consensus.timeout_prevote = 150_000_000
        cfg.consensus.timeout_prevote_delta = 50_000_000
        cfg.consensus.timeout_precommit = 150_000_000
        cfg.consensus.timeout_precommit_delta = 50_000_000
        cfg.consensus.timeout_commit = 100_000_000
        cfg.base.signature_backend = "cpu"
        cfg.save(cfgp)


def _spawn_node(base, i):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    return subprocess.Popen(
        [sys.executable, "-m", "cometbft_tpu",
         "--home", f"{base}/node{i}", "start"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, env=env, cwd=REPO)


def test_cli_testnet_multiprocess_commits_blocks(tmp_path):
    """4 real OS processes, launched by the CLI, commit blocks; txs and
    queries flow through RPC only."""
    base = str(tmp_path / "net")
    res = _run_cli("testnet", "--v", "4", "--output-dir", base,
                   "--base-port", str(BASE_PORT), "--chain-id", "proc-net")
    assert res.returncode == 0, res.stderr

    _patch_testnet_configs(base)
    procs = []
    try:
        for i in range(4):
            procs.append(_spawn_node(base, i))

        asyncio.run(_drive_rpc())
    finally:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


async def _drive_rpc():
    sys.path.insert(0, REPO)
    from cometbft_tpu.rpc import HTTPClient, RPCError

    clients = [HTTPClient("127.0.0.1", BASE_PORT + 2 * i + 1)
               for i in range(4)]

    async def wait_rpc(cli, timeout=60.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                return await cli.call("status")
            except (OSError, RPCError, asyncio.TimeoutError):
                await asyncio.sleep(0.3)
        raise TimeoutError("rpc never came up")

    for cli in clients:
        await wait_rpc(cli)

    # a tx submitted to node0 must commit (gossip to whoever proposes)
    res = await clients[0].call("broadcast_tx_commit", tx=b"pk=pv".hex())
    assert res["tx_result"]["code"] == 0
    h = res["height"]

    # every node reaches that height and agrees on the block hash
    hashes = set()
    for cli in clients:
        deadline = time.monotonic() + 60
        while True:
            st = await cli.call("status")
            if st["sync_info"]["latest_block_height"] >= h:
                break
            assert time.monotonic() < deadline, "node stuck"
            await asyncio.sleep(0.3)
        blk = await cli.call("block", height=h)
        hashes.add(blk["block_id"]["hash"]["~b"])
    assert len(hashes) == 1, f"fork: {hashes}"

    # the app state is queryable through any node
    q = await clients[3].call("abci_query", path="/key", data=b"pk".hex())
    assert bytes.fromhex(q["response"]["value"]) == b"pv"


def test_cli_testnet_kill_and_restart_node(tmp_path):
    """The reference e2e runner's perturbations (test/e2e/runner/perturb.go)
    shrunk to one machine: SIGKILL a validator process mid-chain, the rest
    keep committing, the restarted process recovers from its WAL/stores and
    catches back up to the live chain."""
    base = str(tmp_path / "pnet")
    kill_port = BASE_PORT + 100
    res = _run_cli("testnet", "--v", "4", "--output-dir", base,
                   "--base-port", str(kill_port), "--chain-id", "perturb")
    assert res.returncode == 0, res.stderr

    _patch_testnet_configs(base)

    def spawn(i):
        return _spawn_node(base, i)

    procs = {i: spawn(i) for i in range(4)}
    try:
        asyncio.run(_drive_perturbation(procs, spawn, kill_port))
    finally:
        for p in procs.values():
            try:
                p.send_signal(signal.SIGTERM)
            except Exception:
                pass
        for p in procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


async def _drive_perturbation(procs, spawn, base_port):
    sys.path.insert(0, REPO)
    from cometbft_tpu.rpc import HTTPClient, RPCError

    def cli(i):
        return HTTPClient("127.0.0.1", base_port + 2 * i + 1)

    async def height(i):
        st = await cli(i).call("status")
        return st["sync_info"]["latest_block_height"]

    async def wait_height(i, h, timeout=60.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                if await height(i) >= h:
                    return
            except (OSError, RPCError, asyncio.TimeoutError):
                pass
            await asyncio.sleep(0.3)
        raise TimeoutError(f"node{i} never reached height {h}")

    for i in range(4):
        await wait_height(i, 1)

    # SIGKILL node3 — a hard crash, no cleanup
    procs[3].kill()
    procs[3].wait(timeout=10)

    # the remaining 3/4 (>2/3) keep committing
    h_at_kill = await height(0)
    await wait_height(0, h_at_kill + 5)

    # restart the crashed node: it must recover and catch up to the tip
    procs[3] = spawn(3)
    target = await height(0) + 3
    await wait_height(3, target, timeout=90)

    # all four agree on a recent block hash
    check_h = target
    hashes = set()
    for i in range(4):
        blk = await cli(i).call("block", height=check_h)
        hashes.add(blk["block_id"]["hash"]["~b"])
    assert len(hashes) == 1, f"fork after restart: {hashes}"


def test_start_option_overrides(tmp_path):
    """--option section.key=value overrides config.toml for one run
    (the reference binds a cobra flag per config field)."""
    home = str(tmp_path / "node")
    res = _run_cli("init", "--chain-id", "opt-chain", home=home)
    assert res.returncode == 0, res.stderr

    # bad forms fail fast with a clean error, not a traceback
    for bad in ("nonsense", "rpc.laddr", "bogus.key=1",
                "consensus.timeout_commit=abc", "p2p.pex=maybe",
                "__class__.__name__=X"):
        r = _run_cli("start", "-o", bad, home=home)
        assert r.returncode == 1, (bad, r.stdout)
        assert "Traceback" not in r.stderr, (bad, r.stderr)

    # a good override takes effect: node binds the overridden RPC port
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.Popen(
        [sys.executable, "-m", "cometbft_tpu", "--home", home, "start",
         "-o", "rpc.laddr=tcp://127.0.0.1:28799",
         "-o", "consensus.timeout_commit=100000000",
         "-o", "base.signature_backend=cpu"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO)
    try:
        import urllib.request

        deadline = time.monotonic() + 60
        while True:
            try:
                body = urllib.request.urlopen(
                    "http://127.0.0.1:28799/status", timeout=2).read()
                break
            except Exception:
                assert time.monotonic() < deadline and proc.poll() is None
                time.sleep(0.3)
        assert b"opt-chain" in body
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


def test_sigusr_stack_dumps(tmp_path):
    """SIGUSR1 dumps thread stacks, SIGUSR2 dumps asyncio tasks — the
    reference debug command's goroutine-dump analogue — without stopping
    the node."""
    home = str(tmp_path / "node")
    res = _run_cli("init", "--chain-id", "dump-chain", home=home)
    assert res.returncode == 0, res.stderr
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    out_path = str(tmp_path / "node.log")
    with open(out_path, "wb") as out:
        proc = subprocess.Popen(
            [sys.executable, "-m", "cometbft_tpu", "--home", home, "start",
             "-o", "base.signature_backend=cpu",
             "-o", "rpc.laddr=tcp://127.0.0.1:28811"],
            stdout=out, stderr=subprocess.STDOUT, env=env, cwd=REPO)
    try:
        import urllib.request

        deadline = time.monotonic() + 60
        while True:
            try:
                urllib.request.urlopen(
                    "http://127.0.0.1:28811/health", timeout=2)
                break
            except Exception:
                assert time.monotonic() < deadline and proc.poll() is None
                time.sleep(0.3)
        proc.send_signal(signal.SIGUSR1)
        proc.send_signal(signal.SIGUSR2)
        deadline = time.monotonic() + 30
        while True:
            data = open(out_path).read()
            if "asyncio tasks ===" in data and "Current thread" in data:
                break
            assert time.monotonic() < deadline
            time.sleep(0.3)
        # node survived the dumps
        urllib.request.urlopen("http://127.0.0.1:28811/health", timeout=5)
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
