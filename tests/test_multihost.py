"""Multi-host device mesh smoke test (SURVEY §2.7
cross-host DCN path): two OS processes bootstrap one jax.distributed
CPU cluster through ``parallel/mesh.py::init_multihost`` and run a
lane-sharded verification step over the shared 4-device global mesh —
the claim "init_multihost exists" becomes an executed path.  On real
TPU pods the same code rides ICI/DCN; the CPU backend exercises the
identical process-coordination and GSPMD machinery."""

import os
import socket
import subprocess
import sys
import time

import pytest

pytestmark = [pytest.mark.timeout(360), pytest.mark.slow]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHILD = os.path.join(REPO, "tests", "_multihost_child.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_distributed_mesh_sharded_verify():
    port = _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)        # children set their own device count
    procs = [
        subprocess.Popen([sys.executable, CHILD, str(port), str(i)],
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         text=True, env=env, cwd=REPO)
        for i in range(2)
    ]
    deadline = time.monotonic() + 300
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=max(5, deadline -
                                               time.monotonic()))
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multihost children timed out")
        outs.append(out)
    joined = "\n---\n".join(outs)
    if any(p.returncode != 0 for p in procs):
        # a sandboxed box that cannot run the coordination service is an
        # environment limitation, not a framework bug
        if "UNAVAILABLE" in joined or "Failed to connect" in joined or \
                "permission" in joined.lower():
            pytest.skip(f"distributed service unavailable:\n{joined[-800:]}")
        pytest.fail(f"multihost child failed:\n{joined[-3000:]}")
    assert "MULTIHOST_OK 0" in joined and "MULTIHOST_OK 1" in joined
