"""Autoscaler v2: instance FSM reconciliation + TPU slice atomicity.

Declarative desired/actual reconciliation and a provider whose unit is an
atomic multi-host TPU slice.  These tests drive the reconciler
deterministically (tick by tick) against fake GCS/provider shims — the same
strategy the reference uses for autoscaler v2 unit tests
(python/ray/autoscaler/v2/tests/).
"""

import time

from ray_tpu.autoscaler.v2 import (
    ALLOCATED,
    ALLOCATION_FAILED,
    QUEUED,
    RUNNING,
    TERMINATED,
    AutoscalerV2,
    Instance,
    SliceType,
    TPUSliceProvider,
)


class FakeGcs:
    """Just enough GCS: the reconciler reads alive nodes + marks dead."""

    def __init__(self):
        self.alive = set()
        self.dead = set()

    def list_nodes(self):
        class N:  # noqa: D401 - tiny view object
            def __init__(self, nid):
                self.node_id = nid
                self.alive = True
                self.sched_socket = ""
        return [N(n) for n in self.alive]

    def mark_node_dead(self, nid):
        self.alive.discard(nid)
        self.dead.add(nid)


class VirtualHosts:
    """In-process host launcher: launched hosts 'join' the fake GCS after
    being marked up; individual hosts can be rigged to fail."""

    def __init__(self, gcs, fail_hosts=0):
        self.gcs = gcs
        self.fail_hosts = fail_hosts  # fail the Nth launch call(s)
        self.launches = 0
        self.terminated = []

    def launch(self, node_id, slice_type, instance):
        self.launches += 1
        if self.fail_hosts and self.launches % slice_type.hosts == 0 \
                and self.fail_hosts > 0:
            self.fail_hosts -= 1
            raise RuntimeError("host provision failed")
        self.gcs.alive.add(node_id)

    def terminate(self, node_id):
        self.terminated.append(node_id)
        self.gcs.alive.discard(node_id)


def make_scaler(gcs, hosts, slice_types, demand, **kw):
    provider = TPUSliceProvider("unused", host_launcher=hosts.launch,
                                host_terminator=hosts.terminate)
    return AutoscalerV2(gcs, provider, slice_types,
                        demand_fn=lambda: demand, **kw)


def test_two_host_slice_scales_up_atomically():
    gcs = FakeGcs()
    hosts = VirtualHosts(gcs)
    # two 4-chip asks fill ONE 2-host slice; the third forces a second
    # slice — launches are packed, not one-slice-per-ask
    demand = [{"TPU": 4.0}, {"TPU": 4.0}, {"TPU": 4.0}]
    scaler = make_scaler(
        gcs, hosts,
        {"v5e-8": SliceType(resources={"TPU": 4.0, "CPU": 8.0}, hosts=2,
                            topology="2x4")},
        demand)
    stats = scaler.reconcile()
    assert stats["launched"] == 2
    insts = scaler.im.all(ALLOCATED)
    assert {len(i.node_ids) for i in insts} == {2}  # 2 hosts per instance
    assert len(gcs.alive) == 4
    # next tick: every host joined -> RUNNING
    scaler._demand_fn = lambda: []
    scaler.reconcile()
    assert len(scaler.im.all(RUNNING)) == 2


def test_partial_host_failure_unwinds_whole_slice():
    gcs = FakeGcs()
    hosts = VirtualHosts(gcs, fail_hosts=1)  # second host of slice 1 fails
    scaler = make_scaler(
        gcs, hosts,
        {"v5e-8": SliceType(resources={"TPU": 4.0}, hosts=2)},
        [{"TPU": 4.0}])
    stats = scaler.reconcile()
    assert stats["failed"] == 1
    # the surviving host of the failed slice was torn down: atomicity
    assert len(gcs.alive) == 0 and len(hosts.terminated) == 1
    # the instance re-queued; next tick retries and succeeds
    queued = scaler.im.all(QUEUED)
    assert len(queued) == 1 and queued[0].retries == 1
    stats = scaler.reconcile()
    assert stats["launched"] == 1
    assert len(gcs.alive) == 2


def test_allocation_gives_up_after_bounded_retries():
    gcs = FakeGcs()
    hosts = VirtualHosts(gcs, fail_hosts=99)
    scaler = make_scaler(
        gcs, hosts, {"v5e-8": SliceType(resources={"TPU": 4.0}, hosts=2)},
        [{"TPU": 4.0}])
    for _ in range(AutoscalerV2.MAX_ALLOC_RETRIES + 2):
        scaler.reconcile()
    dead = scaler.im.all(ALLOCATION_FAILED)
    assert len(dead) == 1 and "allocation failed" in dead[0].error


def test_idle_slice_scales_down_as_one_unit():
    gcs = FakeGcs()
    hosts = VirtualHosts(gcs)
    scaler = make_scaler(
        gcs, hosts, {"v5e-8": SliceType(resources={"TPU": 4.0}, hosts=2)},
        [{"TPU": 4.0}], idle_timeout_s=0.05)
    scaler.reconcile()
    # demand satisfied once capacity exists (a live demand_fn would see
    # the new availability in scheduler snapshots; the static fake cannot)
    scaler._demand_fn = lambda: []
    scaler.reconcile()
    assert len(scaler.im.all(RUNNING)) == 1
    # both hosts idle (empty snapshots -> use explicit idle view)
    scaler._snapshots = {
        nid: {"pending_tasks": 0, "available_resources": {"TPU": 4.0},
              "total_resources": {"TPU": 4.0}}
        for nid in gcs.alive}
    scaler._demand_fn = lambda: []
    scaler.reconcile()          # arms idle_since
    time.sleep(0.08)
    scaler._snapshots = {
        nid: {"pending_tasks": 0, "available_resources": {"TPU": 4.0},
              "total_resources": {"TPU": 4.0}}
        for nid in gcs.alive}
    stats = scaler.reconcile()  # past timeout -> terminate whole slice
    assert stats["terminated"] == 1
    assert len(gcs.alive) == 0 and len(hosts.terminated) == 2
    assert len(scaler.im.all(TERMINATED)) == 1


def test_min_instances_floor_and_host_death_reaps_slice():
    gcs = FakeGcs()
    hosts = VirtualHosts(gcs)
    scaler = make_scaler(
        gcs, hosts,
        {"v5e-8": SliceType(resources={"TPU": 4.0}, hosts=2,
                            min_instances=1)},
        [])
    scaler.reconcile()
    scaler.reconcile()
    running = scaler.im.all(RUNNING)
    assert len(running) == 1
    # one host of the slice dies -> remnant terminated atomically; the
    # min_instances floor re-queues a replacement in the same tick
    victim = running[0].node_ids[0]
    gcs.alive.discard(victim)
    stats = scaler.reconcile()
    assert stats["terminated"] == 1
    assert len(scaler.im.all(QUEUED, ALLOCATED)) == 1
