"""The trace reduction: interval arithmetic on synthetic intervals, and
the whole reduction on a small recorded trace (three decode steps of
``serve_long_output`` on a TPU v5e, cut from this benchmark's first traced
chip run; event texts truncated to 400 characters)."""

import os

import pytest

from benchmarks.trace import reduce

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "decode_steps.xplane.pb")


def test_union_total_subtract_gaps():
    u = reduce.union([[5, 7], [0, 2], [1, 3], [3, 4], [9, 9], [6, 6.5]])
    assert u == [[0, 4], [5, 7]]
    assert reduce.total(u) == 6
    assert reduce.subtract([[0, 10]], u) == [[4, 5], [7, 10]]
    assert reduce.subtract(u, [[1, 6]]) == [[0, 1], [6, 7]]
    assert reduce.subtract(u, []) == u
    assert reduce.subtract([], u) == []
    assert reduce.gaps(u, 0, 10) == [[7, 10], [4, 5]]  # longest first


def test_names():
    text = ("%fusion.12 = bf16[8,128]{1,0} fusion(bf16[8,128] %p), "
            "kind=kLoop, calls=%fused")
    assert reduce.op_name(text) == "fusion.12"
    assert reduce.op_name("%flash_attention_bwd_dq.7 = f32[8] custom-call("
                          "f32[8] %p)") == "flash_attention_bwd_dq"
    assert reduce.op_name('%custom-call.3 = f32[] custom-call(f32[] %p), '
                          'name="flash_attention_bwd_dkv"') \
        == "flash_attention_bwd_dkv"
    # a consumer of the kernel's result is not the kernel
    assert reduce.op_name("%fusion.9 = f32[8] fusion(f32[8] "
                          "%flash_attention_bwd_dq.7)") == "fusion.9"
    assert reduce.COLLECTIVE.search("%async-collective-done.5 = bf16[4]")
    assert reduce.module_name("jit_decode_step_greedy(1335859915643548347)") \
        == "jit_decode_step_greedy"
    assert reduce.CONTAINER.match("while.3") and not reduce.CONTAINER.match(
        "while_fusion.3")


def _planes(ops, modules=(), n_devices=1):
    return [(f"/device:TPU:{d}", [(reduce.OPS_LINE, list(ops)),
                                  (reduce.MODULES_LINE, list(modules))])
            for d in range(n_devices)] + [("/host:CPU", [("python3", [])])]


def test_synthetic_busy_idle_and_exposed_collectives():
    ms = 1e6  # ns
    ops = [("%fusion.1 = x", 0, 40 * ms), ("%fusion.2 = x", 30 * ms, 50 * ms),
           ("%all-gather.1 = x", 45 * ms, 70 * ms),
           ("%while.1 = x", 0, 50 * ms),
           ("%fusion.1 = x", 80 * ms, 100 * ms)]
    mods = [("jit_step(1)", 0, 70 * ms), ("jit_step(1)", 80 * ms, 100 * ms),
            ("jit_step(1)", 90 * ms, 130 * ms)]  # the last is cut: left out
    r = reduce.reduce_planes(_planes(ops, mods, n_devices=2))
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.090)
    assert r["collective_s"] == pytest.approx(0.025)
    assert r["collective_exposed_s"] == pytest.approx(0.020)  # 50..70 ms
    assert r["modules"] == {"jit_step": {"count": 2,
                                         "seconds": pytest.approx(0.090)}}
    assert r["ops"]["fusion.1"] == {"count": 2,
                                    "seconds": pytest.approx(0.060)}
    assert "while.1" not in r["ops"]  # a container of listed operations
    assert r["gaps"][0] == [pytest.approx(0.070), pytest.approx(0.080)]


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device plane"):
        reduce.reduce_planes([("/host:CPU", [("python3", [("x", 0, 1)])])])


def test_recorded_trace():
    r = reduce.reduce_file(RECORDED)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.4796, abs=1e-3)
    # decode steps run back to back inside a burst: the device never idles
    assert 0.99 < r["busy_s"] / r["window_s"] <= 1.0
    decode = r["modules"]["jit_decode_step_greedy"]
    assert decode["count"] == 3  # whole runs only
    assert decode["seconds"] / decode["count"] == pytest.approx(0.1125,
                                                                abs=2e-3)
    top = sorted(r["ops"], key=lambda k: -r["ops"][k]["seconds"])[:4]
    assert top[:2] == ["broadcast_in_dim.98", "broadcast_in_dim.97"]
    assert set(top[2:]) == {"multiply_reduce_fusion.4",
                            "multiply_reduce_fusion.5"}
    assert not any(reduce.CONTAINER.match(k) for k in r["ops"])
    assert r["collective_s"] == 0.0
    gaps = r["gaps"]
    assert gaps and all(b > a for a, b in gaps)
    assert all(gaps[i][1] - gaps[i][0] >= gaps[i + 1][1] - gaps[i + 1][0]
               for i in range(len(gaps) - 1))
    assert sum(b - a for a, b in gaps) <= r["window_s"] - r["busy_s"] + 1e-9
    assert r["lines"]["/device:TPU:0"]["XLA Ops"] == 4970
