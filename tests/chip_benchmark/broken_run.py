"""Not a test: ``python broken_run.py <fault> <run.py arguments...>`` drives a
whole CPU-rehearsal run of the benchmark with the timed path broken
underneath, for test_rehearsal.py to see ``correct`` come out false.

Faults, planted where the served answer is produced (the matrix render):
``scaled`` — every value 0.1 % high; ``truncated`` — the last row of what
is rendered left out.
"""

import os
import runpy
import sys

fault, argv = sys.argv[1], sys.argv[2:]
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["JAX_PLATFORMS"] = "cpu"  # run.py pins it too; the patch imports first

from filodb_tpu.api import promjson  # noqa: E402

render_rows = promjson.render_rows


def broken(ts_s, vals):
    if fault == "scaled":
        return render_rows(ts_s, vals * 1.001)
    if fault == "truncated":
        return render_rows(ts_s[:-1], vals[:-1])
    raise SystemExit(f"unknown fault {fault!r}")


promjson.render_rows = broken
sys.argv = [os.path.join(ROOT, "benchmarks", "chip", "run.py"), *argv]
runpy.run_path(sys.argv[0], run_name="__main__")
