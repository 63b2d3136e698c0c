"""Device time by part (``trace/device_parts.py``): the path parser on the
forms a compiled program carries, the containment of operations in program
runs on synthetic lines, what a trace without parts gives, two cuts of
traces recorded on the chip WITH scopes, and a CPU rehearsal of a serving
shape through the child process."""

import json
import os

import pytest

from benchmarks import common
from benchmarks.tests import test_harness
from benchmarks.trace import device_parts as dp

DATA = os.path.join(os.path.dirname(__file__), "data")
PARTS = frozenset((
    "embed", "layers", "attn/norm", "attn/qkv", "attn/rope", "attn/kv_write",
    "attn/attend", "attn/attend/repeat_kv", "attn/out", "mlp/norm",
    "mlp/gate_up", "mlp/down", "moe/route", "moe/dispatch", "moe/experts",
    "moe/combine", "head", "sample", "loss", "optim"))
NEW = ("device_time_scoped_share", "decode_mlp_share",
       "decode_attn_proj_share", "prefill_attend_share",
       "block_pass_head_share", "block_pass_dispatch_share",
       "train_remat_share", "train_optimizer_share")


def test_the_reader_knows_the_programs_own_names():
    from ray_tpu.models import llama

    assert frozenset(llama.PARTS) == PARTS


@pytest.mark.parametrize("op_name, part, phase", [
    # forward, inside the layer scan (itself a part: the innermost wins)
    ("jit(f)/jvp(layers)/while/body/closed_call/attn/qkv/dot_general",
     "attn/qkv", "fwd"),
    # backward
    ("jit(f)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "attn/qkv/dot_general", "attn/qkv", "bwd"),
    # recomputation, although it runs in the backward pass
    ("jit(f)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/gate_up/dot_general", "mlp/gate_up",
     "recompute"),
    # a scope outside the scan is wrapped INTO the transform's name
    ("jit(f)/transpose(jvp(head))/add_any", "head", "bwd"),
    ("jit(step_fn)/transpose(jvp(loss))/while/body/closed_call/checkpoint/"
     "rematted_computation/dot_general:", "loss", "recompute"),
    # the scan's own slice of its stacked weights
    ("jit(decode_step_greedy)/layers/while/body/dynamic_slice:", "layers",
     "fwd"),
    # a Pallas custom call, as the chip's trace names it (``tf_op`` ends ':')
    ("jit(decode_step_greedy)/layers/while/body/closed_call/attn/attend/"
     "jit(_paged_decode)/paged_decode_attention/pallas_call:", "attn/attend",
     "fwd"),
    # a part nested in a part
    ("jit(step_fn)/jvp(layers)/while/body/closed_call/attn/attend/repeat_kv/"
     "broadcast_in_dim:", "attn/attend/repeat_kv", "fwd"),
    ("jit(step_fn)/optim/reduce_sum:", "optim", "fwd"),
    # no part: a jitted helper's name is not a scope, even a part's
    ("jit(decode_step_greedy)/jit(take_along_axis)/gather:", None, "fwd"),
    ("jit(head)/jit(loss)/mul", None, "fwd"),
    ("", None, "fwd"),
])
def test_path_parser(op_name, part, phase):
    assert dp.split_path(op_name, PARTS) == (part, phase)


def _lines(runs, ops):
    """Synthetic lines: ``runs`` [(program, start, end)], ``ops`` [(hlo
    name, op_name, start, end)]; times in ns."""
    metadata, ids = {}, {}

    def mid(name, op=""):
        if (name, op) not in ids:
            ids[(name, op)] = len(ids) + 1
            metadata[ids[(name, op)]] = (name, op)
        return ids[(name, op)]

    modules = [(mid(f"{p}(123)"), s, e) for p, s, e in runs]
    events = [(mid(f"%{n} = f32[8]{{0}} fusion(f32[8]{{0}} %x)", op), s, e)
              for n, op, s, e in ops]
    return modules, events, metadata


def test_operations_go_to_the_run_that_contains_them():
    q = "jit(decode_step_greedy)/layers/while/body/closed_call/attn/qkv/dot"
    runs = [("jit_decode_step_greedy", 50, 250),    # cut by the slice's edge
            ("jit_decode_step_greedy", 300, 400),
            ("jit_prefill", 450, 600),
            ("jit_decode_step_greedy", 650, 750),
            ("jit_decode_step_greedy", 900, 1100)]  # cut by the other edge
    ops = [("fusion.1", q, 60, 240),         # in the cut run: dropped
           ("fusion.1", q, 300, 340),
           ("while.3", "", 300, 400),        # a container: its body counts
           ("copy.2", "", 340, 360),         # no path at all: unscoped
           ("fusion.9", "jit(decode_step_greedy)/sample/argmax", 360, 400),
           ("fusion.5", "jit(prefill)/layers/while/body/closed_call/attn/"
            "attend/dot_general", 450, 550),
           ("fusion.1", q, 650, 700),
           ("fusion.7", "jit(f)/jit(_where)/select_n", 700, 710),
           ("fusion.2", q, 800, 850),        # between runs: dropped
           ("fusion.1", q, 950, 1000)]       # in the other cut run
    got = dp.table(*_lines(runs, ops), PARTS, 100, 1000)
    step, pre = got["jit_decode_step_greedy"], got["jit_prefill"]
    assert step["runs"] == 2 and pre["runs"] == 1
    assert step["module_s"] == pytest.approx(200e-9)
    assert step["ops_s"] == pytest.approx(160e-9)
    assert step["parts"]["attn/qkv"]["fwd"] == pytest.approx(90e-9)
    assert step["parts"]["sample"]["fwd"] == pytest.approx(40e-9)
    assert step["unscoped_s"] == pytest.approx(30e-9)
    assert step["unscoped"] == [["copy.2", pytest.approx(20e-9)],
                                ["fusion.7", pytest.approx(10e-9)]]
    assert pre["parts"] == {"attn/attend": {
        "fwd": pytest.approx(100e-9), "recompute": 0.0, "bwd": 0.0}}
    # the parts and the unscoped rest sum to the operation time
    for p in got.values():
        assert sum(sum(c.values()) for c in p["parts"].values()) \
            + p["unscoped_s"] == pytest.approx(p["ops_s"])
    ctx = {"_device_parts": got}
    assert dp.share(ctx, "jit_decode_step", "attn/qkv".__eq__) \
        == pytest.approx(100 * 90 / 160)
    assert dp.share(ctx, "", lambda part: True) \
        == pytest.approx(100 * 230 / 260)
    assert dp.share(ctx, "jit_block_step", lambda part: True) is None
    text = "\n".join(dp.note_lines({"programs": got, "events": 10,
                                    "parse_s": 0.01}))
    assert "jit_decode_step_greedy (2 runs" in text
    assert "attn/qkv 56.25" in text and "unscoped 18.75 [copy.2 12.50" in text


def test_fwd_recompute_bwd_are_told_apart():
    base = "jit(step_fn)/transpose(jvp(layers))/while/body/closed_call/"
    ops = [("fusion.1", "jit(step_fn)/jvp(layers)/while/body/closed_call/"
            "mlp/down/dot_general", 0, 10),
           ("fusion.2", base + "checkpoint/rematted_computation/mlp/down/"
            "dot_general", 10, 20),
           ("fusion.3", base + "checkpoint/mlp/down/dot_general", 20, 50),
           ("fusion.4", "jit(step_fn)/optim/mul", 50, 60)]
    got = dp.table(*_lines([("jit_step_fn", 0, 60)], ops), PARTS, 0, 60)
    assert got["jit_step_fn"]["parts"]["mlp/down"] == {
        "fwd": pytest.approx(10e-9), "recompute": pytest.approx(10e-9),
        "bwd": pytest.approx(30e-9)}
    ctx = {"_device_parts": got, "kind": "train"}
    remat = common.module("layer_metrics", "train_remat_share").read(ctx)
    optim = common.module("layer_metrics", "train_optimizer_share").read(ctx)
    assert remat == optim == pytest.approx(100 / 6)


def test_a_trace_without_any_part_says_nothing():
    ops = [("fusion.121", "", 10, 40),
           ("fusion.122", "jit(decode_step_greedy)/while/body/dot_general",
            40, 90)]
    lines = _lines([("jit_decode_step_greedy", 0, 100)], ops)
    assert dp.table(*lines, PARTS, 0, 100) is None
    # ... so that every reader leaves its metric out, and raises nothing
    for ctx in ({"_device_parts": None}, {}, {"device_trace": None},
                {"device_trace": {"xplane": "/nonexistent"}}):
        for name in NEW:
            assert common.module("layer_metrics", name).read(ctx) is None
    lines = dp.note_lines({"programs": None, "events": 2, "parse_s": 0.0})
    assert len(lines) == 1 and "none carries a part" in lines[0]


def _recorded(name):
    modules, ops, metadata = dp.read_device(os.path.join(DATA, name))
    lo = min(s for _, s, _ in modules)
    hi = max(e for _, _, e in modules)
    return dp.table(modules, ops, metadata, PARTS, lo, hi), ops, metadata


def test_the_walk_by_hand_reads_what_profile_data_reads():
    from jax.profiler import ProfileData

    path = os.path.join(DATA, "decode_parts.xplane.pb")
    (plane,) = [p for p in ProfileData.from_file(path).planes
                if p.name == "/device:TPU:0"]
    want = {line.name: [(ev.name, ev.start_ns, ev.duration_ns)
                        for ev in line.events] for line in plane.lines}
    modules, ops, metadata = dp.read_device(path)
    for got, line in ((modules, "XLA Modules"), (ops, "XLA Ops")):
        assert len(got) == len(want[line]) > 0
        for (mid, s, e), (name, start, dur) in zip(got, want[line]):
            assert metadata[mid][0] == name
            # ProfileData hands out whole nanoseconds
            assert 0.0 <= s - start < 1.0
            assert e - s == pytest.approx(dur, abs=1.0)
    assert sum(1 for _, op in metadata.values() if op) > 60


def test_recorded_decode_steps_with_scopes():
    """Three ``decode_step_greedy`` runs on a TPU v5e (PR 36; 4 layers of
    width 1024, 8 slots), cut to device 0's two lines, each operation's
    HLO line shortened and of its metadata's stats only ``tf_op`` kept."""
    got, ops, metadata = _recorded("decode_parts.xplane.pb")
    (name,) = got
    step = got[name]
    assert name == "jit_decode_step_greedy" and step["runs"] == 3
    share = {part: 100 * sum(c.values()) / step["ops_s"]
             for part, c in step["parts"].items()}
    assert set(share) == {"embed", "layers", "attn/norm", "attn/qkv",
                          "attn/rope", "attn/kv_write", "attn/attend",
                          "attn/out", "mlp/norm", "mlp/gate_up", "mlp/down",
                          "head"}  # ``sample`` is fused into the head's
    assert share["layers"] == pytest.approx(25.80, abs=0.01)
    assert share["attn/attend"] == pytest.approx(18.94, abs=0.01)
    assert 100 * step["unscoped_s"] / step["ops_s"] \
        == pytest.approx(0.69, abs=0.01)
    assert sum(share.values()) + 100 * step["unscoped_s"] / step["ops_s"] \
        == pytest.approx(100.0)
    assert 0.97 < step["ops_s"] / step["module_s"] < 0.98
    # every call of the kernel lies under the attention core
    kernel = [mid for mid, (n, _) in metadata.items()
              if n.startswith("%paged_decode_attention")]
    assert kernel and all(
        dp.split_path(metadata[m][1], PARTS)[0] == "attn/attend"
        for m in kernel)
    assert sum(1 for m, _, _ in ops if m in kernel) == 3 * 4
    # no step has anything but a forward pass
    assert all(c["recompute"] == c["bwd"] == 0.0
               for c in step["parts"].values())
    ctx = {"_device_parts": got}
    read = lambda n: common.module("layer_metrics", n).read(ctx)  # noqa: E731
    assert read("device_time_scoped_share") == pytest.approx(99.31, abs=0.01)
    assert read("decode_mlp_share") == pytest.approx(37.69, abs=0.01)
    assert read("decode_attn_proj_share") == pytest.approx(36.02, abs=0.01)
    assert read("prefill_attend_share") is None  # no prefill in the cut


def test_recorded_train_step_with_scopes():
    """One train step on one TPU v5e chip (PR 36; 3 layers of width 1024
    under remat, the flash kernels, chunked loss, AdamW), cut the same
    way."""
    got, ops, metadata = _recorded("train_parts.xplane.pb")
    step = got["jit_step_fn"]
    assert step["runs"] == 1
    total = {ph: 100 * sum(c[ph] for c in step["parts"].values())
             / step["ops_s"] for ph in dp.PHASES}
    assert total["recompute"] == pytest.approx(13.81, abs=0.02)
    assert total["bwd"] > total["fwd"] > total["recompute"]
    # remat recomputes the layers' parts, and XLA drops the one product
    # whose result the backward pass does not need
    assert step["parts"]["mlp/gate_up"]["recompute"] > 0
    assert step["parts"]["attn/attend"]["recompute"] > 0
    assert step["parts"]["mlp/down"]["recompute"] == 0.0
    assert step["parts"]["optim"]["bwd"] == 0.0
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dkv",
                   "flash_attention_bwd_dq"):
        mids = [m for m, (n, _) in metadata.items()
                if n.startswith("%" + kernel)]
        assert mids, kernel
        assert {dp.split_path(metadata[m][1], PARTS)[0] for m in mids} \
            == {"attn/attend"}
    ctx = {"_device_parts": got}
    read = lambda n: common.module("layer_metrics", n).read(ctx)  # noqa: E731
    assert read("train_remat_share") == pytest.approx(total["recompute"])
    assert read("train_optimizer_share") == pytest.approx(16.76, abs=0.01)
    assert read("device_time_scoped_share") == pytest.approx(88.57, abs=0.01)
    assert read("decode_mlp_share") is None


def test_cpu_rehearsal_reads_the_trace_and_says_what_it_found(tmp_path):
    """The whole path, child process included.  The CPU's trace carries no
    paths (its events have ``hlo_op`` and no ``tf_op``), so the note line
    says that none has a part, and the metrics are left out."""
    root, _ = test_harness._temp_copy(tmp_path)
    b = json.loads((root / "BENCHMARK.json").read_text())
    cell = "tiny_closed"
    if cell not in [w["name"] for w in b["workloads"]]:
        b["workloads"].append({"name": cell, "config": "tiny_serve",
                               "traffic": cell, "chips": 1,
                               "why": "test-only"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "serve_long_output" in m.get("workloads", []) \
                and cell not in m["workloads"]:
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    done = test_harness._run(
        root, "--workload", cell, "--seed", "2147483659", "--seconds", "4",
        "--trace", "1", env={"BENCH_REHEARSE": "1"})
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"
    assert not set(NEW) & set(line["metrics"])
    assert "decode_step_ms" in line["metrics"] or "device_idle_share" \
        in line["metrics"]
    (note,) = [ln for ln in done.stdout.splitlines()
               if ln.startswith("# device_parts:")]
    assert "operation events read in" in note
    assert "none carries a part" in note
