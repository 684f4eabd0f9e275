"""tests/conftest.py's per-test time limit, driven the way the suite is: a
child pytest on a temporary test file, with conftest.py loaded as a plugin
(the file lives outside tests/, so directory discovery would not find it)."""

from __future__ import annotations

import os
import subprocess
import sys
import time

TESTS = os.path.dirname(os.path.abspath(__file__))

CASES = '''
import signal, threading, pytest

@pytest.mark.time_limit(2)
def test_waits_for_ever():
    threading.Event().wait()

def test_in_time_sees_the_timer_armed():
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < left <= 120

def test_teardown_of_the_last_one_disarmed_it(request):
    request.addfinalizer(lambda: None)

def teardown_module(module):
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
    assert signal.getsignal(signal.SIGALRM) is signal.SIG_DFL
'''


def test_a_test_that_blocks_for_ever_fails_by_name_and_the_run_goes_on(tmp_path):
    (tmp_path / "test_cases.py").write_text(CASES)
    env = dict(os.environ, PYTHONPATH=TESTS)
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "conftest", "-p", "no:cacheprovider",
         "-c", os.path.join(os.path.dirname(TESTS), "pytest.ini"),
         "--rootdir", str(tmp_path), "-v", "test_cases.py"],
        capture_output=True, text=True, cwd=tmp_path, env=env,
    )
    elapsed = time.monotonic() - t0
    out = proc.stdout
    assert proc.returncode == 1, out[-3000:] + proc.stderr[-3000:]
    assert elapsed < 60
    assert "test_cases.py::test_waits_for_ever FAILED" in out
    assert "test_cases.py::test_waits_for_ever (call) passed its time limit of 2 s" in out
    # the dump: the waiting frame of the main thread, by file and function
    assert "Current thread" in out and "in test_waits_for_ever" in out
    assert "test_cases.py::test_in_time_sees_the_timer_armed PASSED" in out
    assert "test_cases.py::test_teardown_of_the_last_one_disarmed_it PASSED" in out
    assert "1 failed, 2 passed" in out
