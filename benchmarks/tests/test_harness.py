"""The harness is driven by data; the yardstick's pieces agree with the
program where they must; the contract's character rules hold."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks import common

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_follows_the_contract():
    b = common.benchmark_json()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    names = lambda xs: [x["name"] for x in xs]  # noqa: E731
    for group in ("configs", "workloads"):
        assert len(set(names(b[group]))) == len(b[group])
    metrics = b["end_to_end"] + b["per_layer"]
    assert len(set(names(metrics))) == len(metrics)
    e2e = set(names(b["end_to_end"]))
    assert "setup_s" in e2e
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    cells = {w["name"]: w for w in b["workloads"]}
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and "\n" not in m["layer"]
        moved = next(e for e in b["end_to_end"] if e["name"] == m["moves"])
        # reported only where the metric it moves is
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
    configs = {c["name"]: c for c in b["configs"]}
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    pairs = set()
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cell = common.load_cell(w["name"])  # the files are there, by name
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        kinds = [m for m in b["per_layer"]
                 if w["name"] in m.get("workloads", cells)]
        assert kinds and any(
            w["name"] in m.get("workloads", cells) and m["name"] != "setup_s"
            for m in b["end_to_end"])
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        with open(os.path.join(common.ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"] == ["num_hidden_layers"]
        # no width differs from the published config
        assert (body["hidden_size"], body["intermediate_size"],
                body["num_attention_heads"], body["num_key_value_heads"],
                body["head_dim"], body["vocab_size"]) == (
                    4096, 14336, 32, 8, 128, 32768)
        assert any(w["config"] == c["name"] for w in b["workloads"])
    assert len(json.dumps(b)) < 64 * 1024


def test_every_listed_metric_has_a_reader_and_every_mix_a_generator():
    b = common.benchmark_json()
    for m in b["end_to_end"]:
        assert callable(common.module("end_to_end", m["name"]).read)
    for m in b["per_layer"]:
        assert callable(common.module("layer_metrics", m["name"]).read)
    for w in b["workloads"]:
        cell = common.load_cell(w["name"])
        gen = common.module("generators", cell["mix"]["kind"])
        assert callable(common.module("runners", gen.RUNNER).run)
        family = cell["config_file"]["family"]
        assert common.module("families", family).n_params(
            cell["config_file"]) > 1e9
        assert callable(common.module("reference", family).logits)


def test_unknown_device_kind_is_an_error_not_a_default():
    assert common.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no default"):
        common.peaks("TPU v9 imaginary")


def test_family_counts_at_the_published_sizes():
    from benchmarks.families import llama_dense as f

    c = common.load_json("configs", "mistral7b_serve_1chip.json")
    assert f.params_per_layer(c) == 218_112_000
    assert f.params_outside_layers(c) == 2 * 32768 * 4096 + 4096
    full = {**c, "num_hidden_layers": 32}
    assert abs(f.n_params(full) - 7.248e9) < 5e6  # Mistral-7B-v0.3: 7.25B
    assert f.kv_bytes_per_token({**c, "num_hidden_layers": 1}) == 4096
    # decode: every matmul weight once, plus the K/V present
    assert f.decode_step_bytes(c, 0) == f.matmul_params(c) * 2
    assert f.decode_step_bytes(c, 1000) - f.decode_step_bytes(c, 0) \
        == 1000 * f.kv_bytes_per_token(c)
    # prefill: 2 FLOPs per matmul parameter per token dominate
    flops = f.prefill_flops(c, 768)
    assert 0.9 < flops / (2 * 768 * f.matmul_params(c)) < 1.15
    assert f.prefill_flops(c, 100, 900) > f.prefill_flops(c, 100, 0)
    assert 6 * f.matmul_params(c) < f.train_flops_per_token(c, 4096) \
        < 7 * f.matmul_params(c)


def test_reference_agrees_with_the_program_at_a_tiny_size():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.families import llama_dense as family
    from benchmarks.reference import llama_dense as reference
    from ray_tpu.models import llama

    with open(os.path.join(common.HERE, "tests", "data", "tiny", "configs",
                           "tiny_serve.json")) as f:
        c = json.load(f)
    c = {**c, "dtype": "float32"}
    cfg = family.model_config(c)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 3,
                                c["vocab_size"])
    ours = np.asarray(reference.logits(c, params, tokens))
    theirs = np.asarray(llama.apply(params, tokens, cfg, attn_impl="xla"))
    # both float32 on the CPU: only the order of summation differs
    np.testing.assert_allclose(ours, theirs, atol=2e-4, rtol=2e-4)
    ref_loss = float(reference.loss(c, params, tokens))
    their_loss = float(llama.loss_fn(params, tokens, cfg, attn_impl="xla"))
    assert abs(ref_loss - their_loss) < 1e-4
    cands, gaps = reference.greedy(c, params, [[5, 6, 7], [9, 8, 7, 6, 5]],
                                   steps=3, pad_to=16)
    full = np.asarray(llama.apply(params, jnp.asarray(
        [[5, 6, 7] + [x[0] for x in cands[0][:2]]]), cfg, attn_impl="xla"))
    assert int(full[0, -1].argmax()) == cands[0][2][0]
    assert all(g[0] == 0.0 and g[1] >= 0.0 for row in gaps for g in row)


def _temp_copy(tmp_path):
    """A copy of the benchmark with a cell, a configuration, a traffic mix
    and a per-layer metric dropped in as NEW files, and new entries in
    BENCHMARK.json; no file that was there is edited."""
    root = tmp_path / "copy"
    shutil.copytree(common.HERE, root / "benchmarks", ignore=shutil.
                    ignore_patterns("out", "__pycache__", ".jax_cache"))
    os.symlink(os.path.join(common.ROOT, "ray_tpu"), root / "ray_tpu")
    tiny = os.path.join(common.HERE, "tests", "data", "tiny")
    before = {os.path.join(dp, p): os.path.getmtime(os.path.join(dp, p))
              for dp, _, fs in os.walk(root / "benchmarks") for p in fs}
    for kind in ("configs", "cells", "traffic"):
        for name in os.listdir(os.path.join(tiny, kind)):
            shutil.copy(os.path.join(tiny, kind, name),
                        root / "benchmarks" / kind / name)
    (root / "benchmarks" / "layer_metrics" / "tokens_out_total.py").write_text(
        '"""A metric a later PR might add: tokens the engine generated."""\n'
        "\n\ndef read(ctx):\n"
        '    return (ctx.get("counters") or {}).get("tokens_generated")\n')
    b = common.benchmark_json()
    b["workloads"].append({"name": "tiny_closed", "config": "tiny_serve",
                           "traffic": "tiny_closed", "chips": 1,
                           "why": "test-only"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "serve_long_output" in m.get("workloads", []):
            m["workloads"].append("tiny_closed")
    b["per_layer"].append({
        "name": "tokens_out_total", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "engine",
        "moves": "output_tokens_per_s", "workloads": ["tiny_closed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root, before


def _run(root, *args, env=None):
    e = {k: v for k, v in os.environ.items()
         if k not in ("BENCH_REHEARSE", "XLA_FLAGS")}
    e.update({"JAX_PLATFORMS": "cpu", **(env or {})})
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", *args], cwd=root, env=e,
        capture_output=True, text=True, timeout=600)


def test_new_files_are_found_by_name_and_a_cpu_run_is_refused(tmp_path):
    root, before = _temp_copy(tmp_path)
    args = ("--workload", "tiny_closed", "--seed", "3", "--seconds", "4")
    # without a TPU a run fails and prints no result line
    refused = _run(root, *args, "--trace", "0")
    assert refused.returncode != 0
    assert '"metrics"' not in refused.stdout
    assert "TPU chip" in refused.stderr
    # the rehearsal on the CPU runs the new cell end to end, and says so
    done = _run(root, *args, "--trace", "1", env={"BENCH_REHEARSE": "1"})
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    assert line["device"]["platform"] == "cpu"  # never taken for a chip's
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["metrics"]["tokens_out_total"]["value"] > 0
    assert line["metrics"]["tokens_out_total"]["unit"] == "tokens"
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert len(line["breakdown"]["device_ops"]) <= 10
    assert len(line["breakdown"]["idle_gaps"]) <= 5
    for path, mtime in before.items():  # nothing that was there was edited
        assert os.path.getmtime(path) == mtime, path
