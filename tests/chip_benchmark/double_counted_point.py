"""Not a test: ``python double_counted_point.py <run.py arguments...>`` drives
a whole CPU-rehearsal run of the benchmark with the base-2 body's windows one
point too wide at their old end: each window sums the point at exactly
t - 5 m, which the window ending there has summed already — for
test_expo_delta_cell.py to see ``correct`` come out false: a delta point
counted in two windows is another answer.
"""

import os
import runpy
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["JAX_PLATFORMS"] = "cpu"  # run.py pins it too; the patch imports first

import jax.numpy as jnp  # noqa: E402

from filodb_tpu.ops import aggregations as AGG  # noqa: E402

body = AGG.FUSED_BODIES["hist_shared"]
grid = body.base2_grid


def one_point_wider(func, rows, win, is_delta):
    lo, *rest = win
    return grid(func, rows, (jnp.maximum(lo - 1, 0), *rest), is_delta)


AGG.FUSED_BODIES["hist_shared"] = body._replace(base2_grid=one_point_wider)
sys.argv = [os.path.join(ROOT, "benchmarks", "chip", "run.py"), *sys.argv[1:]]
runpy.run_path(sys.argv[0], run_name="__main__")
