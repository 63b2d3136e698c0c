"""A cell of a routed model (``runners/serve_routed.py``) end to end in a
CPU rehearsal at a tiny size: the new generator, runner and loader are
found by name, and the result line carries both comparisons."""

import json

from benchmarks.tests import test_harness


def test_cpu_rehearsal_of_a_routed_cell(tmp_path):
    root, _ = test_harness._temp_copy(tmp_path)
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "tiny_block", "config": "tiny_sdar",
                           "traffic": "tiny_block", "chips": 1,
                           "why": "test-only"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "serve_block_diffusion" in m.get("workloads", []):
            m["workloads"].append("tiny_block")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    done = test_harness._run(
        root, "--workload", "tiny_block", "--seed", "2147489777",
        "--seconds", "4", "--trace", "1", env={"BENCH_REHEARSE": "1"})
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"  # never taken for a chip's
    assert line["correct"] is True and line["failed"] == 0
    assert 1.0 < line["metrics"]["tokens_per_slot_pass"]["value"] <= 4 / 3
    (note,) = [ln for ln in done.stdout.splitlines()
               if ln.startswith("# correct: ")]
    correct = json.loads(note[len("# correct: "):])
    assert correct["positions_compared"] == 13 * 16
    assert correct["tokens_missing"] == 0
    assert correct["within_margin_share"] >= 0.9
    assert 0.0 < correct["pinned"]["logit_rms_error"] < 0.013
    assert correct["repeat_equals_first"]
