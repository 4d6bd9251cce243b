"""Pins OpenBLAS to one thread per test process: six xdist workers with host-wide pools made each
CPU QR/SVD 100-300x slower ((160, 80) QR 0.5 s vs 1.4 ms) and tier-1 overran 1470 s; pinned, 264 s."""

import contextlib
import os
import sys

os.environ.setdefault("OPENBLAS_NUM_THREADS", "1")

if "numpy" in sys.modules:
    # A pytest plugin (jaxtyping) loads numpy's OpenBLAS before this file, and
    # OpenBLAS reads the variable only when it loads; xdist workers inherit it.
    with contextlib.suppress(ImportError):
        from threadpoolctl import threadpool_limits
        threadpool_limits(int(os.environ["OPENBLAS_NUM_THREADS"]), user_api="blas")

import signal  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402

# About five times the slowest test under six xdist workers (~33 s).
TEST_LIMIT_S = 180


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    """Fail a test that runs past TEST_LIMIT_S instead of letting it stall the
    whole run.  POSIX main thread only; ``slow`` tests keep their own limits."""
    if (not hasattr(signal, "SIGALRM")
            or threading.current_thread() is not threading.main_thread()
            or item.get_closest_marker("slow")):
        return (yield)

    def expire(signum, frame):
        pytest.fail(f"ran past the per-test limit of {TEST_LIMIT_S} s "
                    "(conftest.TEST_LIMIT_S)")

    previous = signal.signal(signal.SIGALRM, expire)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)
