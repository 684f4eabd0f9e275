"""Not a test: ``python high_fourth_panel.py <run.py arguments...>`` drives a
whole CPU-rehearsal run of the benchmark with one fault planted where the
served answer is produced (the matrix render): every value of an
``avg(avg_over_time(...))`` answer 1e-5 high, the other panels untouched —
for test_counters_cell.py to see ``correct`` come out false by that panel's
limit alone.
"""

import os
import runpy
import sys
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["JAX_PLATFORMS"] = "cpu"  # run.py pins it too; the patch imports first

from filodb_tpu.api import promjson  # noqa: E402
from filodb_tpu.coordinator.planner import QueryEngine  # noqa: E402

asked = threading.local()  # the query the handler's thread renders next
query_range, render_rows = QueryEngine.query_range, promjson.render_rows


def noting(self, promql, *args, **kwargs):
    asked.high = promql.startswith("avg(avg_over_time(")
    return query_range(self, promql, *args, **kwargs)


def high(ts_s, vals):
    return render_rows(ts_s, vals * (1 + 1e-5) if getattr(asked, "high", False) else vals)


QueryEngine.query_range = noting
promjson.render_rows = high
sys.argv = [os.path.join(ROOT, "benchmarks", "chip", "run.py"), *sys.argv[1:]]
runpy.run_path(sys.argv[0], run_name="__main__")
