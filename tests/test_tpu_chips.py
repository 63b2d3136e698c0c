"""Who may open a TPU chip: detection, grant, and release.

No chip is needed.  The node is told it has two (``resources={"TPU": 2}``),
JAX stays on the CPU, and what is checked is the part that is the runtime's
own: which process a chip grant lands in, what environment that process was
born with, and that the process is gone before the chip is granted again.
"""

import os
import sys
import time

import pytest

import ray_tpu
from ray_tpu._private import node as node_mod
from ray_tpu.cluster_utils import Cluster
from ray_tpu.util.accelerators.tpu import chip_env


@pytest.fixture
def tpu_cluster():
    """Fresh one-node cluster that believes it has two chips."""
    import ray_tpu.api as api
    from ray_tpu._private import worker as worker_mod

    prev_ctx = worker_mod._global_worker
    prev_node = api._global_node
    worker_mod.set_global_worker(None)
    api._global_node = None
    c = Cluster(head_node_args={
        "resources": {"CPU": 4.0, "TPU": 2.0}, "min_workers": 1,
        "object_store_memory": 1 << 27})
    ray_tpu.init(_existing_node=c.head_node)
    try:
        yield c
    finally:
        api._global_node = None
        worker_mod.set_global_worker(None)
        c.shutdown()
        worker_mod.set_global_worker(prev_ctx)
        api._global_node = prev_node


def _whoami_fn():
    """Nested, so that it travels to the worker by value."""
    def whoami():
        import os

        keys = ("JAX_PLATFORMS", "TPU_VISIBLE_CHIPS",
                "TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS")
        return {"pid": os.getpid(), **{k: os.environ.get(k) for k in keys}}

    return whoami


def _gone(pid: int, timeout_s: float = 10.0) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            return True
        time.sleep(0.05)
    return False


def _born_with(pid: int) -> dict:
    """The environment a process was started with.  Just after exec the
    kernel may not have published it yet: wait for it."""
    deadline = time.monotonic() + 10.0
    while True:
        with open(f"/proc/{pid}/environ", "rb") as f:
            env = dict(kv.split(b"=", 1) for kv in f.read().split(b"\0")
                       if b"=" in kv)
        if env or time.monotonic() > deadline:
            return env
        time.sleep(0.02)


@pytest.mark.parametrize("chips,on_node,expect", [
    ([0], 4, {"TPU_VISIBLE_CHIPS": "0", "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
              "TPU_HOST_BOUNDS": "1,1,1"}),
    ([2, 3], 4, {"TPU_VISIBLE_CHIPS": "2,3",
                 "TPU_CHIPS_PER_HOST_BOUNDS": "1,2,1",
                 "TPU_HOST_BOUNDS": "1,1,1"}),
    ([0, 1, 2, 3], 4, {}),  # the whole host: the machine's own defaults
    ([0], 1, {}),
])
def test_chip_env(chips, on_node, expect):
    assert chip_env(chips, on_node) == expect


def test_detection_counts_device_files_and_never_asks_jax(monkeypatch):
    import jax  # noqa: F401 - imported on purpose: detection must ignore it

    files = {"/dev/accel*": [],
             "/dev/vfio/*": ["/dev/vfio/0", "/dev/vfio/3", "/dev/vfio/vfio"]}
    monkeypatch.delenv("RAY_TPU_NUM_CHIPS", raising=False)
    monkeypatch.setattr(node_mod.glob, "glob", lambda pat: files[pat])
    monkeypatch.setattr(
        sys.modules["jax"], "devices",
        lambda *a, **k: pytest.fail("detection started the jax backend"))
    assert node_mod.detect_num_tpu_chips() == 2
    files["/dev/vfio/*"] = ["/dev/vfio/vfio"]
    assert node_mod.detect_num_tpu_chips() == 0
    assert "TPU" not in node_mod.default_resources()
    monkeypatch.setenv("RAY_TPU_NUM_CHIPS", "4")
    assert node_mod.default_resources()["TPU"] == 4.0


def test_worker_without_a_grant_is_held_to_the_cpu(tpu_cluster, monkeypatch):
    me = ray_tpu.get(ray_tpu.remote(_whoami_fn()).remote(), timeout=60)
    assert me["JAX_PLATFORMS"] == "cpu"
    assert me["TPU_VISIBLE_CHIPS"] is None
    # even where the driver's own environment would have allowed the chip
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    pool = tpu_cluster.head_node.scheduler._pool
    w = pool.spawn_worker()
    chip = pool.spawn_worker([1], 2)
    try:
        assert _born_with(w.proc.pid)[b"JAX_PLATFORMS"] == b"cpu"
        env = _born_with(chip.proc.pid)
        assert env[b"JAX_PLATFORMS"] == b"tpu,cpu"
        assert env[b"TPU_VISIBLE_CHIPS"] == b"1"
    finally:
        for proc in (w.proc, chip.proc):
            proc.kill()
            proc.wait(timeout=10)


def test_chip_task_gets_a_process_of_its_own_that_ends_with_it(tpu_cluster):
    task = ray_tpu.remote(num_tpus=1)(_whoami_fn())
    first = ray_tpu.get(task.remote(), timeout=60)
    assert first["TPU_VISIBLE_CHIPS"] == "0"
    assert first["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,1"
    assert _gone(first["pid"]), "the process outlived its chip grant"
    second = ray_tpu.get(task.remote(), timeout=60)
    assert second["pid"] != first["pid"]
    assert second["TPU_VISIBLE_CHIPS"] == "0"  # the same chip, handed on
    plain = ray_tpu.get(ray_tpu.remote(_whoami_fn()).remote(), timeout=60)
    assert plain["pid"] not in (first["pid"], second["pid"])
    sched = tpu_cluster.head_node.scheduler
    assert _gone(second["pid"])
    deadline = time.monotonic() + 10
    while sched._free_chips != [0, 1] and time.monotonic() < deadline:
        time.sleep(0.05)
    assert sched._free_chips == [0, 1]
    assert ray_tpu.available_resources().get("TPU") == 2.0


def test_two_one_chip_actors_hold_different_chips(tpu_cluster):
    whoami = _whoami_fn()

    @ray_tpu.remote(num_tpus=1)
    class Holder:
        def who(self):
            return whoami()

    a, b = Holder.remote(), Holder.remote()
    wa, wb = ray_tpu.get([a.who.remote(), b.who.remote()], timeout=60)
    assert {wa["TPU_VISIBLE_CHIPS"], wb["TPU_VISIBLE_CHIPS"]} == {"0", "1"}
    assert wa["pid"] != wb["pid"]
    # later calls stay in the process that holds the chip
    assert ray_tpu.get(a.who.remote(), timeout=60)["pid"] == wa["pid"]
    ray_tpu.kill(a)
    ray_tpu.kill(b)


def test_killed_actor_hands_its_chips_to_the_next(tpu_cluster):
    whoami = _whoami_fn()

    @ray_tpu.remote(num_tpus=2)
    class WholeHost:
        def who(self):
            return whoami()

    first = WholeHost.remote()
    w1 = ray_tpu.get(first.who.remote(), timeout=60)
    # every chip of the host: nothing is narrowed
    assert w1["TPU_VISIBLE_CHIPS"] is None
    assert w1["TPU_CHIPS_PER_HOST_BOUNDS"] is None
    second = WholeHost.remote()  # must wait: no chip is free
    ready, _ = ray_tpu.wait([second.who.remote()], timeout=1.0)
    assert not ready
    ray_tpu.kill(first)
    w2 = ray_tpu.get(second.who.remote(), timeout=60)
    assert w2["pid"] != w1["pid"]
    assert _gone(w1["pid"])
    ray_tpu.kill(second)


def test_failed_creation_of_a_chip_actor_frees_the_chips(tpu_cluster):
    @ray_tpu.remote(num_tpus=2)
    class Broken:
        def __init__(self):
            raise RuntimeError("no")

        def ping(self):
            return 1

    with pytest.raises(Exception):
        ray_tpu.get(Broken.remote().ping.remote(), timeout=60)
    task = ray_tpu.remote(num_tpus=2)(_whoami_fn())
    assert ray_tpu.get(task.remote(), timeout=60)["pid"] > 0
