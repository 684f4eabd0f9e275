"""Crash-recovery end-to-end (reference standalone multi-jvm
IngestionAndRecoverySpec: ingest -> kill -9 -> restart -> query
correctness). A real server process starts on a persistent store, is fed
over HTTP, flushed via /admin/flush, killed with SIGKILL, restarted on the
same store, and must answer the same query with the same values."""

import json
import os
import signal
import subprocess
import sys
import textwrap
import time
import urllib.parse
import urllib.request

import numpy as np

BASE = 1_600_000_000_000

SERVER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    from filodb_tpu.server import FiloServer
    srv = FiloServer({
        "dataset": "prometheus", "shards": 4,
        "store_root": sys.argv[1],
        "query": {"timeout_s": 30},
    })
    port = srv.start(port=0)
    print(f"PORT={port}", flush=True)
    import threading
    threading.Event().wait()
""")


def _start(store):
    import selectors

    proc = subprocess.Popen(
        [sys.executable, "-c", SERVER, store],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    # readline with a real timeout: a hung child would otherwise block
    # the whole suite on readline forever
    sel = selectors.DefaultSelector()
    sel.register(proc.stdout, selectors.EVENT_READ)
    deadline = time.time() + 60
    buf = ""
    while time.time() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(f"server died: {proc.stdout.read()[-2000:]}")
        if not sel.select(timeout=1):
            continue
        line = proc.stdout.readline()
        buf += line
        if line.startswith("PORT="):
            sel.close()
            return proc, int(line.strip().split("=")[1])
    proc.kill()
    raise TimeoutError(f"server did not start within 60s: {buf[-2000:]}")


def _get(url):
    with urllib.request.urlopen(url) as r:
        return json.loads(r.read())


def _post(url, body=b""):
    req = urllib.request.Request(url, data=body, method="POST")
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def test_kill_dash_nine_then_recover(tmp_path):
    store = str(tmp_path / "store")
    q = urllib.parse.quote("sum(rate(rq_total[5m]))")
    qpath = (f"/api/v1/query_range?query={q}"
             f"&start={(BASE + 400_000) / 1000}&end={(BASE + 3_000_000) / 1000}&step=60")

    proc, port = _start(store)
    try:
        lines = ["# TYPE rq_total counter"]
        for s in range(3):
            for i in range(60):
                lines.append(f'rq_total{{inst="h{s}"}} {100 * s + 10 * i} {BASE + i * 60_000}')
        out = _post(f"http://127.0.0.1:{port}/ingest/prom", "\n".join(lines).encode())
        assert out["data"]["ingested"] == 180
        flushed = _post(f"http://127.0.0.1:{port}/admin/flush")
        assert flushed["data"]["chunks_written"] > 0
        before = _get(f"http://127.0.0.1:{port}{qpath}")
        assert before["data"]["result"], "query empty before kill"
        want = [(t, float(v)) for t, v in before["data"]["result"][0]["values"]]
    finally:
        os.kill(proc.pid, signal.SIGKILL)  # no warning, no cleanup
        proc.wait(timeout=30)

    proc2, port2 = _start(store)
    try:
        after = _get(f"http://127.0.0.1:{port2}{qpath}")
        assert after["data"]["result"], "query empty after recovery"
        got = [(t, float(v)) for t, v in after["data"]["result"][0]["values"]]
        assert [t for t, _ in got] == [t for t, _ in want]
        np.testing.assert_allclose(
            [v for _, v in got], [v for _, v in want], rtol=1e-5
        )
        # series-level metadata also recovered
        m = urllib.parse.quote("rq_total")
        series = _get(f"http://127.0.0.1:{port2}/api/v1/series?match[]={m}")["data"]
        assert len(series) == 3
    finally:
        proc2.kill()
        proc2.wait(timeout=30)
