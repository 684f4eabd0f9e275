"""The one traffic generator: a cell's traffic file -> what each client sends.

A traffic file (``workloads/<cell>.json``) gives ``clients``, the ``panels``
(PromQL, the reference that answers it), the query grid (``steps`` of
``step_s``, ``window_ms`` of look-back) and how the ``range`` moves:

- ``{"mode": "newest"}``: every request asks for the same range, ending at
  the newest loaded scrape;
- ``{"mode": "slide", "advance_steps": n}``: the range starts at the oldest
  position whose first window lies inside the history, and each request
  moves it ``n`` steps on, staying strictly inside the history and
  wrapping at its end. Every seed walks the same positions from the oldest
  on: where a range lies in the history changes what staging has to decode
  (a range across a chunk boundary read 8 % slower on the chip), so a
  start drawn from the seed would make the seed change the work.

The cycle is every (position, panel) pair, positions outermost. Every
client walks the whole cycle; client ``c`` of ``C`` starts ``c * len(cycle)
// C`` on from the first. Clients that ask the same thing at the same time
are coalesced by the server into one execution: the walk of viewers of one
dashboard. No seed changes what is sent or in which order: the seed makes
the data.
"""

from __future__ import annotations

import urllib.parse

import numpy as np


def positions(traffic: dict, t_first_ms: int, t_last_ms: int) -> list[int]:
    """Start (ms) of each range the traffic can ask for."""
    step = int(traffic["step_s"]) * 1000
    span = (int(traffic["steps"]) - 1) * step
    mode = traffic["range"]["mode"]
    if mode == "newest":
        return [t_last_ms - span]
    if mode == "slide":
        first = t_first_ms + int(traffic["window_ms"])
        adv = int(traffic["range"]["advance_steps"]) * step
        out = list(range(first, t_last_ms - span + 1, adv))
        if not out:
            raise ValueError("the history is shorter than one range")
        return out
    raise ValueError(f"unknown range mode {mode!r}")


def out_t(traffic: dict, start_ms: int) -> np.ndarray:
    step = int(traffic["step_s"]) * 1000
    return start_ms + np.arange(int(traffic["steps"]), dtype=np.int64) * step


def cycles(traffic: dict, t_first_ms: int, t_last_ms: int):
    """(requests, walks): ``requests[i]`` is ``(panel index, start_ms,
    path)``; client ``c`` sends ``requests[i] for i in walks[c]``, again and
    again."""
    step_s = int(traffic["step_s"])
    span_ms = (int(traffic["steps"]) - 1) * step_s * 1000
    reqs = []
    for start in positions(traffic, t_first_ms, t_last_ms):
        for p, panel in enumerate(traffic["panels"]):
            q = urllib.parse.urlencode({
                "query": panel["query"], "start": start / 1000,
                "end": (start + span_ms) / 1000, "step": step_s})
            reqs.append((p, start, "/api/v1/query_range?" + q))
    C, n = int(traffic["clients"]), len(reqs)
    offs = [c * n // C for c in range(C)]
    return reqs, [list(range(off, n)) + list(range(off)) for off in offs]
