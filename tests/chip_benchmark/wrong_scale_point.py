"""Not a test: ``python wrong_scale_point.py <run.py arguments...>`` drives a
whole CPU-rehearsal run of the benchmark with ONE point of one series merged
onto its series' scheme as if it sat one scale coarser than it does — its
counts under bounds twice as far apart as theirs — while the reference
merges it from its true scale: for test_expo_delta_cell.py to see
``correct`` come out false, so the limits are tight enough to see one point
of 46 080 put on the wrong scale at ingest.
"""

import os
import runpy
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["JAX_PLATFORMS"] = "cpu"  # run.py pins it too; the patch imports first

from filodb_tpu.core.histograms import Base2Rows  # noqa: E402

index = Base2Rows.index
done = []


def wrong_scale(self, dst):
    idx = index(self, dst)
    finer = np.nonzero(self.scale > dst.scale)[0]
    if done or not len(finer):
        return idx
    r = finer[-1]  # the newest such point: every range of the cell reads it
    one = Base2Rows(self.scale[r:r + 1] - 1, self.offset[r:r + 1] >> 1, self.n[r:r + 1])
    idx[r] = np.minimum(index(one, dst)[0], self.n[r] + 1)
    done.append(r)
    return idx


Base2Rows.index = wrong_scale
sys.argv = [os.path.join(ROOT, "benchmarks", "chip", "run.py"), *sys.argv[1:]]
runpy.run_path(sys.argv[0], run_name="__main__")
