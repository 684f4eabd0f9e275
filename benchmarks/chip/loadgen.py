#!/usr/bin/env python3
"""The load generator: a child process that imports only the standard
library, so it needs no chip and does not share the server's interpreter
lock. ``loadgen.py <spec.json> <out.json>``.

The spec (written by ``run.py`` from the cell's traffic file) holds the
port, the seconds to measure and, per client, the cycle of request paths it
walks. Every request opens a connection of its own, as ``chip_smoke.py``'s
urllib client does. Closed loop: a client sends its next request when the
previous body has been read. The window opens when the first request is
sent and closes when every client has finished the request it had in flight
at ``seconds``; rates are taken over that whole length.

A traced run (``trace_requests`` > 0) holds whole requests inside the
profiler's sub-window: after ``trace_after_s`` every client finishes its
request and waits; the child prints ``PAUSED`` and reads a line from stdin
(the parent starts the trace), every client then does exactly
``trace_requests`` requests, the child prints ``TRACED`` and reads a line
again (the parent stops the trace), and the loop goes on to the deadline.

Handed back in ``out.json``: ``t0_wall`` (time.time() when the window
opened), ``window_s``, per request ``[client, position in its cycle, sent,
done, status, digest]`` (seconds from the opening, child's perf_counter),
the distinct bodies by digest, and the sub-window's edges.
"""

from __future__ import annotations

import hashlib
import http.client
import json
import sys
import threading
import time


def main(argv) -> int:
    spec_path, out_path = argv
    with open(spec_path) as f:
        spec = json.load(f)
    seconds = float(spec["seconds"])
    cycles = spec["cycles"]
    n_trace = int(spec.get("trace_requests") or 0)
    trace_after = float(spec.get("trace_after_s") or 0.0)
    records, bodies, lock = [], {}, threading.Lock()
    sub = {}

    def at_barrier(word: str, key: str):
        def action():
            sub[key] = time.perf_counter() - t0
            print(word, flush=True)
            sys.stdin.readline()
            sub[key + "_released"] = time.perf_counter() - t0
        return action

    paused = threading.Barrier(len(cycles), action=at_barrier("PAUSED", "start"))
    traced = threading.Barrier(len(cycles), action=at_barrier("TRACED", "stop"))
    errors = []

    def client(c: int, cycle: list) -> None:
        k = 0

        def one():
            nonlocal k
            pos = k % len(cycle)
            k += 1
            conn = http.client.HTTPConnection("127.0.0.1", spec["port"], timeout=120)
            sent = time.perf_counter() - t0
            try:
                conn.request("GET", cycle[pos], headers={"Connection": "close"})
                resp = conn.getresponse()
                body = resp.read()
                status = resp.status
            except (OSError, http.client.HTTPException) as e:
                body, status = repr(e).encode(), 0
            done = time.perf_counter() - t0
            conn.close()
            digest = hashlib.sha1(body).hexdigest()
            with lock:
                bodies.setdefault(digest, body.decode("utf-8", "replace"))
                records.append([c, pos, sent, done, status, digest])
            return done

        try:
            now = 0.0
            phase = 0 if n_trace else 2
            while now < seconds or phase < 2:
                if phase == 0 and now >= trace_after:
                    paused.wait()
                    for _ in range(n_trace):
                        one()
                    traced.wait()
                    phase = 2
                    now = time.perf_counter() - t0
                    continue
                now = one()
        except Exception as e:  # noqa: BLE001 — reported, and the run fails
            errors.append(f"client {c}: {e!r}")
            paused.abort()
            traced.abort()

    threads = [threading.Thread(target=client, args=(c, cyc), daemon=True)
               for c, cyc in enumerate(cycles)]
    t0_wall, t0 = time.time(), time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    window_s = max(r[3] for r in records) if records else 0.0
    with open(out_path, "w") as f:
        json.dump({"t0_wall": t0_wall, "window_s": window_s,
                   "records": records, "bodies": bodies, "sub": sub,
                   "errors": errors}, f)
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
