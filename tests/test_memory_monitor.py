"""Memory monitor + worker-killing policy.

Reference: src/ray/common/memory_monitor.h:52 + raylet
worker_killing_policy_retriable_fifo.cc.  Memory pressure kills ONE
policy-chosen worker — a retriable task retries transparently, a
non-retriable one surfaces OutOfMemoryError with provenance — and the node
(scheduler + store daemon) survives.  Pressure is injected by driving the
scheduler's handler directly, the same way the reference unit-tests its
killing policies without real OOM.
"""

import threading
import time

import pytest

import ray_tpu
from ray_tpu._private.memory_monitor import (
    MemoryMonitor,
    choose_victim,
    node_memory_usage,
    process_rss,
)
from ray_tpu.exceptions import OutOfMemoryError


class _W:
    def __init__(self, alive=True, in_flight=(), actor=None, proc=object()):
        self.alive = alive
        self.in_flight = {i: s for i, s in enumerate(in_flight)}
        self.actor_id = actor
        self.proc = proc


class _Spec:
    def __init__(self, retries_left=0, kind="TASK"):
        self.retries_left = retries_left
        self.kind = kind


def test_choose_victim_prefers_retriable_plain_workers():
    retriable = _W(in_flight=[_Spec(retries_left=3)])
    plain = _W(in_flight=[_Spec(retries_left=0)])
    actor = _W(in_flight=[_Spec(retries_left=3)], actor=b"a1")
    idle = _W(in_flight=[])
    dead = _W(alive=False, in_flight=[_Spec(retries_left=3)])
    assert choose_victim([actor, plain, retriable, idle, dead]) is retriable
    # no retriable plain worker: non-retriable plain beats actors
    assert choose_victim([actor, plain, idle]) is plain
    # actors are last resort
    assert choose_victim([actor, idle]) is actor
    # nothing killable
    assert choose_victim([idle, dead]) is None


def test_node_memory_and_rss_sane():
    used, total = node_memory_usage()
    assert 0 < used <= total
    import os

    assert process_rss(os.getpid()) > 1 << 20  # this interpreter > 1MB


def test_monitor_fires_above_threshold_with_cooldown():
    calls = []
    usage = {"v": (50, 100)}
    mon = MemoryMonitor(0.9, lambda u, t, th: calls.append((u, t)) or True,
                        cooldown_s=10.0, usage_fn=lambda: usage["v"])
    assert not mon.check_once()  # below threshold
    usage["v"] = (95, 100)
    assert mon.check_once()
    assert not mon.check_once()  # cooldown suppresses the second kill
    assert calls == [(95, 100)]


def _node_busy(sched):
    # native-lane tasks are tracked in C++, not WorkerState.in_flight
    if any(w.in_flight for w in sched._workers.values()):
        return True
    if getattr(sched, "_raylet_native", False):
        return sched._node_srv.raylet_stats()["inflight"] > 0
    return False


def test_oom_kill_retries_task_and_preserves_node(ray_cluster):
    """Pressure kills the worker mid-task; the task (retriable) re-runs to
    completion and the cluster stays healthy — a targeted kill, not node
    death."""
    import ray_tpu.api as api

    sched = api._global_node.scheduler
    release = threading.Event()

    @ray_tpu.remote
    def slow(x):
        import time as _t

        _t.sleep(1.0)  # long enough for the pressure injection to land
        return x * 3

    ref = slow.options(max_retries=2).remote(14)
    # wait until the task is actually running on a worker
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        with sched._lock:
            if _node_busy(sched):
                break
        time.sleep(0.02)
    killed = sched._handle_memory_pressure(95 << 20, 100 << 20, 0.95)
    assert killed, "no victim found while a task was in flight"
    assert ray_tpu.get(ref, timeout=120) == 42  # retried transparently

    @ray_tpu.remote
    def quick():
        return "alive"

    assert ray_tpu.get(quick.remote(), timeout=60) == "alive"
    release.set()


def test_oom_error_carries_provenance(ray_cluster):
    """A NON-retriable task killed under pressure fails with
    OutOfMemoryError naming rss/node usage/threshold."""
    import ray_tpu.api as api

    sched = api._global_node.scheduler

    @ray_tpu.remote
    def hog():
        import time as _t

        _t.sleep(20.0)  # wide window: the kill must land mid-execution
        return 1

    ref = hog.options(max_retries=0).remote()
    deadline = time.monotonic() + 60
    killed = False
    while time.monotonic() < deadline and not killed:
        with sched._lock:
            busy = _node_busy(sched)
        if busy:
            killed = sched._handle_memory_pressure(97 << 20, 100 << 20,
                                                   0.95)
        if not killed:
            time.sleep(0.05)
    assert killed, "pressure injection never found an in-flight victim"
    with pytest.raises(OutOfMemoryError, match="memory monitor"):
        ray_tpu.get(ref, timeout=60)


def test_native_monitor_emits_pressure_markers(ray_cluster):
    """The C++ epoll-loop monitor (core_worker.cc memory_check): enabling
    it with a floor threshold produces 0x7e crossings that reach the
    Python pressure handler with real usage numbers — sampling and
    rate-limiting native, policy Python."""
    import time

    import ray_tpu.api as api

    sched = api._global_node.scheduler
    if sched._node_srv is None:
        pytest.skip("native node server unavailable")
    fired = []
    orig = sched._on_native_memory_pressure
    sched._on_native_memory_pressure = \
        lambda used, total: fired.append((used, total))
    try:
        # threshold far below any real usage: first sample crosses
        sched._set_native_memory_monitor(1e-6, 0.05, 0.2)
        deadline = time.monotonic() + 10
        while len(fired) < 2 and time.monotonic() < deadline:
            time.sleep(0.05)
    finally:
        sched._set_native_memory_monitor(0.0, 1.0, 5.0)  # disable
        time.sleep(0.3)  # let any straggler marker drain (flag drops it)
        sched._on_native_memory_pressure = orig
    assert len(fired) >= 2, "native monitor never fired"
    used, total = fired[0]
    assert 0 < used <= total
    # cooldown gating is native: crossings are spaced, not per-sample
    assert len(fired) <= 60
