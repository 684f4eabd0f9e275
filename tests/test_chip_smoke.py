"""chip_smoke.py's contract as far as a CPU can show it, plus the other
"no fallback that hides the device" rules of the bring-up PR:

- without a TPU (and without the rehearsal switch) the smoke exits non-zero
  and prints no timing and no result line — it pins the platform itself, so
  JAX_PLATFORMS=cpu in the environment does not turn it into a CPU run;
- the tiny ``--cpu-rehearsal`` passes every phase and labels every line cpu;
- the batch-downsample pool's workers never need the chip: the module they
  import pulls in no jax.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, env=None):
    return subprocess.run(
        [sys.executable, SMOKE, *args], capture_output=True, text=True,
        cwd=REPO, env=env,
    )


def test_no_tpu_exits_nonzero_and_prints_no_timing():
    assert os.environ.get("JAX_PLATFORMS") == "cpu"  # conftest's pin...
    proc = _run()  # ...which the smoke must NOT run under
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout
    assert '"ok"' not in proc.stderr
    assert "tpu" in proc.stderr.lower()


def test_outside_the_repo_it_fails(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_bytes(open(SMOKE, "rb").read())
    proc = subprocess.run(
        [sys.executable, str(alone), "--cpu-rehearsal"], capture_output=True,
        text=True, cwd=tmp_path,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.mark.time_limit(300)  # a whole smoke in a child process
@pytest.mark.parametrize("n_devices", [1, 8])
def test_cpu_rehearsal_passes_and_labels_itself_cpu(n_devices):
    """One virtual CPU device: the mesh phase prints its skip. Eight (the
    suite's own mesh): it runs — parity with the single-device answers, one
    dispatch, every device in each superblock's device_set."""
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}")
    proc = _run("--cpu-rehearsal", env=env)
    assert proc.returncode == 0, (
        proc.stdout[-3000:]
        + "".join(l for l in proc.stderr.splitlines(True)
                  if "cpu_aot_loader" not in l)[-3000:])
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == n_devices
    for line in lines[:-1]:
        assert line.startswith("[platform: cpu REHEARSAL] "), line
    text = proc.stdout
    if n_devices == 1:
        assert "mesh phase: skipped (1 device)" in text
    else:
        assert (f"every device holds a band: 2 sharded superblocks, each on "
                f"all {n_devices} devices") in text
    assert "input: t_end_ms=" in text
    # past pallas_kernels.MAX_T an irregular grid takes `general` by shape
    assert "grid=irregular key=family=fused_sum_rate|variant=general|epilogue=agg:sum|shapes=S32xT4864" in text
    assert "POST /ingest/prom acknowledged" in text
    for variant in ("variant=mxu", "variant=jitter", "variant=masked",
                    "variant=general", "variant=hist_shared"):
        assert variant in text, variant


def test_downsample_pool_workers_import_no_jax():
    """One process per chip: the spawn-pool workers of batch_downsample
    unpickle ``_downsample_shard_worker`` by importing its module, under a
    parent that may hold the chip — so that import must not bring in jax."""
    code = (
        "import sys\n"
        "from filodb_tpu.downsample.downsampler import _downsample_shard_worker\n"
        "from filodb_tpu.store.columnstore import LocalColumnStore\n"
        "assert 'jax' not in sys.modules, 'worker import pulled in jax'\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stderr
