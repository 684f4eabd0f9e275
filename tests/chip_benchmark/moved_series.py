"""Not a test: ``python moved_series.py <run.py arguments...>`` drives a whole
CPU-rehearsal run of the benchmark with one fault planted where the data
enters the program: every sample of ONE series is ingested 1 s later than it
was taken, the other series untouched, while the reference still reads the
true timestamps — for test_scraped_cell.py and test_counters_slide_cell.py to
see ``correct`` come out false: a store that keeps a series on another clock
than its own gives another answer.

A range function does not see a whole series moved unless a sample crosses a
window's edge, and step times and scrape times are both whole intervals
apart: on the shared grid every series has a sample ON each step, so any one
will do (the second); in a scraped fleet it is the series with the greatest
phase, the one scraped last before every step (within the last second of the
interval, among a few hundred targets), so that its newest sample leaves every
window.
"""

import os
import runpy
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["JAX_PLATFORMS"] = "cpu"  # run.py pins it too; the patch imports first

from filodb_tpu.memstore.memstore import TimeSeriesMemStore  # noqa: E402

from benchmarks.chip import regular_counters, scraped_counters  # noqa: E402

ingest_routed = TimeSeriesMemStore.ingest_routed
BY_MS = 1_000
which = {}  # "instance": the series that is moved, chosen when the data loads


def moved(self, dataset, batch, spread):
    rows = np.fromiter((t["instance"] == which["instance"] for t in batch.tags),
                       bool, len(batch.tags))
    if rows.any():
        batch.timestamps = np.where(rows, batch.timestamps + BY_MS, batch.timestamps)
    return ingest_routed(self, dataset, batch, spread)


def choosing(load):
    def chosen(self, memstore, spread):
        phase = getattr(self, "phase_ms", None)
        s = 1 if phase is None else int(np.argmax(phase))
        which["instance"] = self.tags[s]["instance"]
        return load(self, memstore, spread)
    return chosen


for cls in (regular_counters.CounterSet, scraped_counters.ScrapedSet):
    cls.load = choosing(cls.load)
TimeSeriesMemStore.ingest_routed = moved
sys.argv = [os.path.join(ROOT, "benchmarks", "chip", "run.py"), *sys.argv[1:]]
runpy.run_path(sys.argv[0], run_name="__main__")
