"""Test configuration: run JAX on a virtual 8-device CPU mesh.

The suite runs on the CPU: sharding correctness is validated on 8 virtual
host devices, and what only a chip can show is chip_smoke.py's job. The
platform is pinned both in the environment (for child processes) and in the
live jax config (in case jax was imported before this file).
"""

import contextlib
import faulthandler
import os
import signal
import tempfile

# keep grpc-core/absl INFO chatter (GOAWAY notices on server stop, etc.) off
# stderr: it interleaves with pytest's progress lines and corrupts them
os.environ.setdefault("GRPC_VERBOSITY", "ERROR")
os.environ.setdefault("ABSL_MIN_LOG_LEVEL", "2")

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax
import pytest

jax.config.update("jax_platforms", "cpu")

assert len(jax.devices()) == 8, "tests expect an 8-device virtual CPU mesh"

# Every test has a time limit of its own, from here and nowhere else. A test
# that waits for ever then fails by name, with every thread's stack, and the
# run goes on; without this one such wait costs the whole run its clock and
# says nothing of which test it was. 120 s is five times the slowest sound
# test. Only a test that runs a whole smoke in a child process asks for more,
# with @pytest.mark.time_limit(seconds).
TEST_LIMIT_S = 120.0
# after the first alarm: a `finally:` of the failed test that waits too
_REARM_S = 10.0


@contextlib.contextmanager
def _time_limit(item, phase):
    """Fail ``item`` from a SIGALRM handler once ``phase`` has run for its
    limit. pytest and xdist workers run tests on the main thread, where
    CPython's lock, condition and subprocess waits yield to a signal, so
    ``t.join()``, ``as_completed`` and ``subprocess.run`` all end."""
    marker = item.get_closest_marker("time_limit")
    limit = float(marker.args[0]) if marker else TEST_LIMIT_S

    def on_alarm(signum, frame):
        with tempfile.TemporaryFile("w+") as f:
            faulthandler.dump_traceback(file=f, all_threads=True)
            f.seek(0)
            stacks = f.read()
        pytest.fail(f"{item.nodeid} ({phase}) passed its time limit of "
                    f"{limit:g} s; every thread's stack:\n{stacks}",
                    pytrace=False)

    before = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, limit, _REARM_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    with _time_limit(item, "setup"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with _time_limit(item, "call"):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    with _time_limit(item, "teardown"):
        return (yield)
