"""Test configuration.

Force JAX onto a virtual 8-device CPU mesh *before* any backend init:
multi-chip sharding (parallel/) is exercised on host CPU exactly the way the
driver's dryrun does, and tests never contend for (or hang on) the real TPU.
The force-CPU + compile-cache defenses live in cometbft_tpu.jaxenv.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cometbft_tpu.jaxenv import enable_compile_cache, force_cpu_backend  # noqa: E402

force_cpu_backend(min_devices=8)
enable_compile_cache()

# kernel tests must exercise the device code path even when a cold compile
# outlasts the production watchdog (which would silently host-fallback)
from cometbft_tpu.crypto import batch as _batch  # noqa: E402
from cometbft_tpu.crypto import plan as _plan  # noqa: E402

_batch.set_device_wait(900)


# ---------------------------------------------------------------------------
# Real per-test timeout enforcement. ``pytest-timeout`` is not installed in
# this image, so ``pytest.mark.timeout(N)`` marks would silently be no-ops;
# this hook honors them (default 180 s) via SIGALRM, which interrupts even a
# stuck asyncio loop on the main thread.
# ---------------------------------------------------------------------------

import signal  # noqa: E402

import pytest  # noqa: E402

_DEFAULT_TEST_TIMEOUT = 180


class TestTimeoutExit(SystemExit):
    """Raised by the SIGALRM watchdog.  MUST derive from SystemExit: an
    alarm that fires while the main thread is inside an asyncio callback
    lands in ``Handle._run`` / ``Task.__step``, which swallow every
    ordinary exception (they log-and-continue), so a ``TimeoutError``
    there never fails the test and a stuck event loop eats the whole
    tier-1 budget.  SystemExit (and KeyboardInterrupt) are the only
    classes those frames re-raise; pytest records SystemExit as a plain
    test failure and moves on to the next test."""


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    marker = item.get_closest_marker("timeout")
    seconds = int(marker.args[0]) if marker and marker.args \
        else _DEFAULT_TEST_TIMEOUT

    def _on_alarm(signum, frame):
        raise TestTimeoutExit(
            f"test exceeded {seconds}s timeout (conftest SIGALRM)")

    old = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(seconds)
    try:
        return (yield)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "timeout(seconds): per-test wall-clock limit "
        "(enforced by conftest SIGALRM)")


@pytest.fixture(autouse=True)
def _dispatch_state_restored():
    """``Node.start`` writes the device plan (``min_device_lanes = 64``)
    and the 2 s device wait process-wide, and the benchmark's rehearsals
    set their own wait.  Left behind in an xdist worker, they sent a later
    file's small batches to the host: the two rehearsal cases that failed
    under ``--dist loadfile`` and passed alone.  Every test ends on the
    state this file set up."""
    yield
    _plan.reset()
    _batch.set_device_wait(900)
