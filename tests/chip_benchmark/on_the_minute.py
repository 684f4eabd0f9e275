"""Not a test: ``python on_the_minute.py <run.py arguments...>`` drives a whole
CPU-rehearsal run of the benchmark on a clock set so that the newest scrape
time ``run.py`` derives from it falls on a WHOLE MINUTE: the one thing a run
takes from the wall clock, at the value that lines the cell's query grids up
with the grid a promoted standing query keeps (one run in six by chance;
PR 32's flake). The whole process — ``run.py``, the server, its retention —
reads the same shifted clock; the load waits, if it has to, for the ten
seconds of a minute in which ``run.py``'s rounding lands on the minute.
"""

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["JAX_PLATFORMS"] = "cpu"  # run.py pins it too; the patch imports first

real = time.time
SHIFT = 10.5 - real() % 60  # the process starts 10.5 s into a minute
time.time = lambda: real() + SHIFT

from benchmarks.chip import run  # noqa: E402 — after the clock is set

load = run.Run.load


def on_the_minute(self):
    # run.py: t_last = now // interval * interval - interval, interval 10 s
    while not 10.0 <= time.time() % 60 < 19.0:
        time.sleep(0.05)
    load(self)
    if self.t_last % 60_000:
        raise run.RunFailure(f"t_last_ms={self.t_last} is not on a whole minute")


run.Run.load = on_the_minute
code = 1
try:
    code = run.main(sys.argv[1:])
except BaseException:  # noqa: BLE001 — shown, then the hard exit run.py makes too
    import traceback

    traceback.print_exc()
finally:
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
