"""Test environment: force an 8-device virtual CPU mesh.

Mirrors the reference's testing strategy (SURVEY §4): the whole distributed
surface is exercised in-process — unistore fakes a TiKV cluster in one Go
process; we fake an 8-chip TPU pod slice with XLA host devices.

The environment is set here, before anything imports JAX, and that is all:
the tests never run on the chip (it belongs to one process at a time, and
`chip_smoke.py` is what runs there). They also opt out of the persistent
compile cache `tidb_tpu.ops.jax_env` would otherwise keep under the
checkout — the tree is copied for every check and must stay small.

Every test runs under a wall-clock bound (`_bounded`): a test that blocks
fails with every thread's stack instead of holding the whole run."""

import faulthandler
import os
import signal
import sys
import threading

_WANT_XLA = "--xla_force_host_platform_device_count=8"

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " " + _WANT_XLA).strip()
os.environ["JAX_ENABLE_X64"] = "1"
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

import pytest  # noqa: E402

# Several times the slowest tier-1 test (19 s alone, more beside five busy
# workers). A blocked test then costs two minutes, not the run's time limit.
TEST_BOUND_S = 120.0


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1")
    config.addinivalue_line(
        "markers", "chaos: failpoint/chaos-sweep tests")
    config.addinivalue_line(
        "markers", "perf_smoke: tier-1 perf guardrails (tiny scale, "
        "asserts zero retraces and streamed-overlap phase accounting)")


class TestBoundExceeded(Exception):
    """Raised in the worker's main thread when a test outlives its bound."""


@pytest.fixture(autouse=True)
def _bounded(request):
    """Arm SIGALRM for the test: past TEST_BOUND_S the handler dumps every
    thread's stack to stderr and raises in the main thread — which also
    breaks a blocked `lock.acquire()` — so the test fails and the run goes
    on. Disarmed (and the previous handler restored) on the way out.
    → the bound in seconds, or None where no alarm can be armed."""
    if threading.current_thread() is not threading.main_thread() \
            or not hasattr(signal, "setitimer"):
        yield None
        return

    def _expired(signum, frame):
        sys.stderr.write(f"\n=== {request.node.nodeid} exceeded "
                         f"{TEST_BOUND_S:.0f}s — all thread stacks ===\n")
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        raise TestBoundExceeded(
            f"{request.node.nodeid} still running after "
            f"{TEST_BOUND_S:.0f}s (stacks on stderr)")

    prev = signal.signal(signal.SIGALRM, _expired)
    signal.setitimer(signal.ITIMER_REAL, TEST_BOUND_S)
    try:
        yield TEST_BOUND_S
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, prev)


@pytest.fixture(scope="session")
def eight_devices():
    import jax
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs
