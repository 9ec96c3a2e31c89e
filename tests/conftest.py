import os
import signal
import threading

import pytest

# IMPORTANT: do NOT set XLA_FLAGS / device-count overrides here — smoke tests
# and benches must see the real single-device CPU.  Multi-device semantics are
# tested in subprocesses (tests/multidevice.py) and the production mesh only
# inside launch/dryrun.py.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

try:
    from hypothesis import settings
except ImportError:  # pragma: no cover — property tests skip themselves
    settings = None

if settings is not None:
    settings.register_profile("repro", deadline=None, max_examples=25,
                              derandomize=True)
    settings.load_profile("repro")

# Wall-clock limit for every test, in seconds.  A test that hangs fails on
# its own with a message naming the limit, instead of holding its worker
# until the whole run is cut.  Subprocess timeouts in the tests stay below
# it, so a stuck child is killed before the alarm fires.
TEST_TIME_LIMIT_S = 300


class TimeLimitExceeded(Exception):
    """Raised in the test's main thread when its time limit runs out."""


@pytest.fixture(autouse=True)
def _test_time_limit():
    if (not hasattr(signal, "SIGALRM")
            or threading.current_thread() is not threading.main_thread()):
        yield
        return
    limit = TEST_TIME_LIMIT_S

    def expired(signum, frame):
        raise TimeLimitExceeded(
            f"test exceeded the per-test time limit of {limit} s")

    previous = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)
