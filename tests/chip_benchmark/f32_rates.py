"""Not a test: ``python f32_rates.py <run.py arguments...>`` drives a whole
CPU-rehearsal run of the benchmark with the base-2 body handing the epilogue
each series' f32 rate of a delta column (its window sum divided by the
window, as the program did before the delta window sums came as whole
counts with one factor): the group sum then adds f32 rates, not whole
counts — for test_expo_delta_cell.py to see ``correct`` come out false, so
the limits hold the program to the exact sum.
"""

import os
import runpy
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["JAX_PLATFORMS"] = "cpu"  # run.py pins it too; the patch imports first

from filodb_tpu.ops import aggregations as AGG  # noqa: E402

body = AGG.FUSED_BODIES["hist_shared"]
grid = body.base2_grid


def f32_rates(func, rows, win, is_delta):
    if is_delta and func in ("rate", "increase"):
        return AGG._hist_range_shared(func, *rows, *win, is_delta)
    return grid(func, rows, win, is_delta)


AGG.FUSED_BODIES["hist_shared"] = body._replace(base2_grid=f32_rates)
sys.argv = [os.path.join(ROOT, "benchmarks", "chip", "run.py"), *sys.argv[1:]]
runpy.run_path(sys.argv[0], run_name="__main__")
