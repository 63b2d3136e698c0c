"""Scale-bench smoke: the 1/50-scale envelope the full benchmark runs
(reference: release/benchmarks/README.md — distributed_test at 2,000
nodes / 40k actors / 1k PGs; here the one-host scaled envelope of
`python -m ray_tpu._private.scale_bench`).

Runs in-process (same entry points the bench uses) so a control-plane
regression that would stall the full envelope fails CI in minutes.
"""

import json
import os
import subprocess
import sys

import pytest


def test_scale_bench_quick_completes():
    """--quick finishes, emits every scenario line, and the envelope
    numbers are sane (all tasks done, all actors alive, all PGs
    placed)."""
    proc = subprocess.run(
        [sys.executable, "-m", "ray_tpu._private.scale_bench", "--quick"],
        capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    records = {}
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            records.update(json.loads(line))
    assert records["tasks"]["completed"] == records["tasks"]["n_tasks"]
    assert records["tasks"]["dispatch_per_s"] > 100
    assert records["actors"]["alive"] == records["actors"]["n_actors"]
    assert records["pgs_nodes"]["pgs_created"] == \
        records["pgs_nodes"]["n_pgs"]
    assert records["pgs_nodes"]["n_nodes"] >= 3


@pytest.mark.slow
def test_scale_bench_big_envelope_tasks():
    """The 1M-queued-task envelope (what `make bench-scale` prints):
    streamed submit, measured queue peak past 500k,
    sustained dispatch.  Excluded from tier-1 (`-m 'not slow'`) — this
    is minutes of wall clock."""
    script = (
        "import json\n"
        "from ray_tpu._private.scale_bench import bench_tasks\n"
        "r = bench_tasks(n_tasks=1_000_000)\n"
        "print('BIG-ENVELOPE', json.dumps(r))\n")
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("BIG-ENVELOPE"))
    r = json.loads(line.split(" ", 1)[1])
    assert r["completed"] == r["n_tasks"] == 1_000_000
    assert r["queue_peak"] >= 500_000, r
    assert r["dispatch_per_s"] > 10_000, r
