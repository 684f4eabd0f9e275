"""Reader kind ``since_start``: what the program had booked when the window
opened, since its process started. ``readers.py`` takes a counter's increase
over the window; the set-up is what the counter held before it.

``ctx["segments"][0][0]`` is the /metrics reading ``run.py`` takes as the
window opens, so a sum read from it covers start-up, the load, the first
queries, their compiles and whatever the background pre-warm had finished.
"""

from __future__ import annotations

from benchmarks.chip import readers


def read(ctx, counter, scale=1.0, **labels):
    """``scale`` x the counter's value at the window's start, summed over the
    series that carry ``labels``; 0.0 for a family the program does not have
    (a commit from before the clock was added), never None."""
    return scale * readers.total(ctx["segments"][0][0], counter, **labels)
