"""Actor/task-group collectives over the shared-memory object store.

Counterpart of the reference's collective library
(/root/reference/python/ray/util/collective/collective.py:145 init_collective_group,
:290 allreduce, plus allgather/reducescatter/broadcast/send/recv) — but where the
reference wraps NCCL/Gloo communicators, the TPU-native design has two planes:

1. **In-program (ICI) collectives** are *not here*: inside a jitted SPMD
   program they are ``jax.lax.psum/all_gather/ppermute`` over mesh axes —
   XLA emits ICI collectives directly (see ray_tpu.parallel.mesh).
2. **Host-plane collectives** (this module) coordinate *between actors or
   tasks* — different processes, possibly different hosts — the role NCCL
   groups play for the reference's `ray.util.collective`.  The data plane is
   the native shm object store (zero-copy numpy intra-node, chunked pulls
   across nodes); the rendezvous plane is the GCS KV, so there is no extra
   coordinator process or actor to place and no communicator state to leak.

Every participant calls ``init_collective_group(world_size, rank, group_name)``
once, then the verbs.  Each verb bumps a per-group sequence number that all
ranks advance in lockstep (same total order of collectives per group — the
same contract NCCL imposes), so keys never collide across rounds.
"""

from __future__ import annotations

import time
from typing import List, Optional, Sequence

import numpy as np

from ray_tpu._private import worker as worker_mod
from ray_tpu.core.object_ref import ObjectRef

_KV_NS = "collective"
_POLL_S = 0.002


class ReduceOp:
    SUM = "sum"
    PRODUCT = "product"
    MIN = "min"
    MAX = "max"
    MEAN = "mean"


_REDUCERS = {
    ReduceOp.SUM: lambda xs: sum(xs[1:], xs[0]),
    ReduceOp.PRODUCT: lambda xs: _fold(np.multiply, xs),
    ReduceOp.MIN: lambda xs: _fold(np.minimum, xs),
    ReduceOp.MAX: lambda xs: _fold(np.maximum, xs),
    ReduceOp.MEAN: lambda xs: sum(xs[1:], xs[0]) / len(xs),
}


def _fold(op, xs):
    acc = xs[0]
    for x in xs[1:]:
        acc = op(acc, x)
    return acc


class _GroupState:
    def __init__(self, world_size: int, rank: int, name: str, incarnation: int):
        if not (0 <= rank < world_size):
            raise ValueError(f"rank {rank} out of range for world {world_size}")
        self.world_size = world_size
        self.rank = rank
        self.name = name
        # Key prefix includes the incarnation so a destroy + re-init with the
        # same group name never reads the previous incarnation's stale keys.
        # All ranks perform the same init/destroy sequence (the same lockstep
        # contract the per-round seq already relies on), so per-process
        # incarnation counters agree across ranks.
        self.incarnation = incarnation
        self.seq = 0
        # p2p ordering is per (src, dst) pair, independent of the collective
        # seq: a rank that sends to two peers (or mixes p2p with collectives)
        # must not skew rendezvous counters for anyone else.
        self.p2p_send_seq: dict[int, int] = {}  # dst_rank -> next seq
        self.p2p_recv_seq: dict[int, int] = {}  # src_rank -> next seq
        # Keys/objects this rank published, per collective round, reclaimed
        # once every rank has stamped that round's done marker.
        self.round_pending: dict[int, list[tuple[str, bytes]]] = {}
        # Outstanding p2p sends: (key, oid) per dst, reclaimed once the
        # receiver has deleted the rendezvous key (absence == consumed).
        self.p2p_pending: dict[int, list[tuple[str, bytes]]] = {}

    def prefix(self) -> str:
        return f"{self.name}/i{self.incarnation}"


# group_name -> _GroupState, per process (each actor is its own process).
_groups: dict[str, _GroupState] = {}
# group_name -> number of times this process has initialized it.
_incarnations: dict[str, int] = {}


def _ctx():
    w = worker_mod.global_worker()
    if w is None:
        raise RuntimeError("ray_tpu is not initialized in this process")
    return w


def _kv_put(key: str, value: bytes):
    _ctx().rpc("kv_put", {"namespace": _KV_NS, "key": key.encode(),
                          "value": value})


def _kv_get(key: str) -> Optional[bytes]:
    return _ctx().rpc("kv_get", {"namespace": _KV_NS, "key": key.encode()})


def _kv_del(key: str):
    _ctx().rpc("kv_del", {"namespace": _KV_NS, "key": key.encode()})


def _wait_kv(key: str, timeout: float) -> bytes:
    deadline = time.monotonic() + timeout
    w = _ctx()
    if w.gcs_address:
        # Event-driven wait: subscribe to the collective KV channel and
        # sleep until the key's write event arrives (a 2ms rendezvous
        # spin burned the very core the control plane runs on).  Register
        # BEFORE checking so a write between check and wait cannot be
        # lost; periodic re-checks guard against a dropped event ring (gap
        # wakes handle the common case).
        from ray_tpu._private import kv_watch

        watcher = kv_watch.get_watcher(w.gcs_address, _KV_NS)
        ev = watcher.register(key.encode())
        try:
            while True:
                v = _kv_get(key)
                if v is not None:
                    return v
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"collective rendezvous timed out on {key!r}")
                ev.wait(min(remaining, 2.0))
                ev.clear()
        finally:
            watcher.unregister(key.encode(), ev)
    # no GCS endpoint in this process (minimal embedded contexts): poll
    while True:
        v = _kv_get(key)
        if v is not None:
            return v
        if time.monotonic() > deadline:
            raise TimeoutError(f"collective rendezvous timed out on {key!r}")
        time.sleep(_POLL_S)


def init_collective_group(world_size: int, rank: int,
                          backend: str = "shm",
                          group_name: str = "default") -> None:
    """Join a collective group. Call once in every participating process.

    ``backend`` accepts "shm" (native) — "nccl"/"gloo" names from reference
    code are mapped to it so ported call-sites run unchanged.
    """
    if backend not in ("shm", "nccl", "gloo", "xla"):
        raise ValueError(f"unknown collective backend {backend!r}")
    if group_name in _groups:
        raise RuntimeError(f"collective group {group_name!r} already "
                           f"initialized in this process")
    inc = _incarnations.get(group_name, 0) + 1
    _incarnations[group_name] = inc
    _groups[group_name] = _GroupState(world_size, rank, group_name, inc)


def is_group_initialized(group_name: str = "default") -> bool:
    return group_name in _groups


def get_rank(group_name: str = "default") -> int:
    return _group(group_name).rank


def get_collective_group_size(group_name: str = "default") -> int:
    return _group(group_name).world_size


def destroy_collective_group(group_name: str = "default",
                             grace_s: float = 5.0) -> None:
    g = _groups.pop(group_name, None)
    if g is None:
        return
    # Best-effort farewell barrier: if every rank reaches destroy within the
    # grace period, all earlier rounds are provably finished cluster-wide
    # and this rank's leftovers can be reclaimed.  On timeout nothing is
    # deleted — yanking keys from under a straggler mid-collect is worse
    # than leaking a round of tiny keys (which the incarnation prefix keeps
    # from ever being misread).  The barrier round's own token is the one
    # thing knowingly left behind (~bytes per rank per incarnation).
    barrier_ok = False
    try:
        _publish(g, f"ag/{g.rank}", np.zeros((), np.int8))
        _collect(g, lambda r: f"ag/{r}", grace_s)
        _gc_rounds_before(g, g.seq)
        barrier_ok = True
    except Exception:
        pass
    # p2p: receiver deletes the rendezvous key on recv, so key-absence means
    # consumed (free our object).  A key still present after a SUCCESSFUL
    # farewell barrier is an unmatched send — a program error per the
    # lockstep contract — reclaim it outright.  If the barrier timed out a
    # straggler may still be about to recv, so only confirmed-consumed sends
    # are freed (same leave-it-in-place policy as the collective rounds).
    for entries in g.p2p_pending.values():
        for key, oid in entries:
            if barrier_ok or _kv_get(key) is None:
                _reclaim(key, oid)


def _reclaim(key: Optional[str], oid: Optional[bytes]) -> None:
    """Best-effort delete of a rendezvous key and its published object."""
    w = _ctx()
    if key is not None:
        try:
            _kv_del(key)
        except Exception:
            pass
    if oid is not None:
        try:
            w.store.delete(oid)
        except Exception:
            pass
        node = getattr(w, "node", None)
        nid = getattr(node, "node_id", None) if node is not None else None
        if nid:
            try:
                w.rpc("remove_object_location", {"oid": oid, "node_id": nid})
            except Exception:
                pass


def _gc_rounds_before(g: _GroupState, seq: int) -> None:
    """Reclaim this rank's published keys/objects for all rounds < seq.

    Only called once the caller has PROOF every rank finished those rounds:
    completing an all-publish collect at round ``seq`` means every rank
    published at ``seq``, which it does strictly after finishing every
    earlier round (including broadcast rounds where only the src published).
    A broadcast src that races ahead therefore never reclaims anything on
    its own authority — its pending rounds wait for the next all-publish
    round to confirm the stragglers caught up.
    """
    for s in [s for s in g.round_pending if s < seq]:
        for key, oid in g.round_pending.pop(s):
            _reclaim(key, oid)


def _group(group_name: str) -> _GroupState:
    g = _groups.get(group_name)
    if g is None:
        raise RuntimeError(
            f"collective group {group_name!r} is not initialized; call "
            f"init_collective_group(world_size, rank, group_name=...) first")
    return g


def _to_host(tensor) -> np.ndarray:
    # jax.Array / torch.Tensor / numpy all round-trip through the host for
    # the host-plane; in-program collectives never leave HBM (see module doc).
    if hasattr(tensor, "__array__"):
        return np.asarray(tensor)
    return np.asarray(tensor)


def _publish(g: _GroupState, tag: str, arr: np.ndarray) -> None:
    ref = _ctx().put_object(arr)
    key = f"{g.prefix()}/{g.seq}/{tag}"
    _kv_put(key, ref.binary())
    g.round_pending.setdefault(g.seq, []).append((key, ref.binary()))


def _collect(g: _GroupState, tag_of, timeout: float) -> List[np.ndarray]:
    from ray_tpu import api
    out = []
    for r in range(g.world_size):
        oid = _wait_kv(f"{g.prefix()}/{g.seq}/{tag_of(r)}", timeout)
        value = api.get(ObjectRef(oid), timeout=timeout)
        if isinstance(value, np.ndarray):
            # Own the bytes: the publisher reclaims the backing shm object
            # once a later round proves everyone has moved past this one.
            value = np.array(value)
        out.append(value)
    return out


def allgather(tensor, group_name: str = "default",
              timeout: float = 60.0) -> List[np.ndarray]:
    """Gather every rank's tensor; returns list indexed by rank."""
    g = _group(group_name)
    _publish(g, f"ag/{g.rank}", _to_host(tensor))
    vals = _collect(g, lambda r: f"ag/{r}", timeout)
    # Every rank published this round, so every earlier round is finished
    # cluster-wide: reclaim our stale keys/objects (bounds per-step growth).
    _gc_rounds_before(g, g.seq)
    g.seq += 1
    return vals


def allreduce(tensor, op: str = ReduceOp.SUM, group_name: str = "default",
              timeout: float = 60.0) -> np.ndarray:
    """Reduce across ranks; every rank returns the full reduced tensor."""
    if op not in _REDUCERS:
        raise ValueError(f"unknown reduce op {op!r}")
    vals = allgather(tensor, group_name=group_name, timeout=timeout)
    return _REDUCERS[op](vals)


def reducescatter(tensor, op: str = ReduceOp.SUM,
                  group_name: str = "default",
                  timeout: float = 60.0) -> np.ndarray:
    """Reduce across ranks, then return this rank's 1/world_size shard
    (along axis 0, which must divide evenly)."""
    g = _group(group_name)
    reduced = allreduce(tensor, op=op, group_name=group_name, timeout=timeout)
    n = g.world_size
    if reduced.shape[0] % n:
        raise ValueError(
            f"reducescatter dim0 {reduced.shape[0]} not divisible by "
            f"world_size {n}")
    shard = reduced.shape[0] // n
    return reduced[g.rank * shard:(g.rank + 1) * shard]


def broadcast(tensor, src_rank: int = 0, group_name: str = "default",
              timeout: float = 60.0) -> np.ndarray:
    """Every rank returns src_rank's tensor."""
    from ray_tpu import api
    g = _group(group_name)
    if g.rank == src_rank:
        _publish(g, f"bc/{src_rank}", _to_host(tensor))
    oid = _wait_kv(f"{g.prefix()}/{g.seq}/bc/{src_rank}", timeout)
    g.seq += 1
    value = api.get(ObjectRef(oid), timeout=timeout)
    if isinstance(value, np.ndarray):
        value = np.array(value)  # own the bytes (src reclaims later)
    return value


def send(tensor, dst_rank: int, group_name: str = "default") -> None:
    """Point-to-point send (pairs with recv on dst_rank).

    Ordered per (src, dst) pair — matching sends/recvs advance a dedicated
    counter, so interleaving sends to several peers or mixing p2p with
    collectives never skews anyone's rendezvous sequence.
    """
    g = _group(group_name)
    # Reclaim earlier sends to this peer the receiver has consumed: recv
    # deletes the rendezvous key after reading, so key-absence is the ack.
    still = []
    for key, oid in g.p2p_pending.get(dst_rank, []):
        if _kv_get(key) is None:
            _reclaim(None, oid)
        else:
            still.append((key, oid))
    if still:
        g.p2p_pending[dst_rank] = still
    else:
        g.p2p_pending.pop(dst_rank, None)
    n = g.p2p_send_seq.get(dst_rank, 0)
    ref = _ctx().put_object(_to_host(tensor))
    key = f"{g.prefix()}/p2p/{g.rank}->{dst_rank}/{n}"
    _kv_put(key, ref.binary())
    # Advance only after the publish succeeded, so a failed send can be
    # retried at the same sequence number.
    g.p2p_send_seq[dst_rank] = n + 1
    g.p2p_pending.setdefault(dst_rank, []).append((key, ref.binary()))


def recv(src_rank: int, group_name: str = "default",
         timeout: float = 60.0) -> np.ndarray:
    """Point-to-point receive from src_rank.

    Unlike the reference (which writes into a caller tensor), returns the
    received array — idiomatic for a functional JAX host program.
    """
    from ray_tpu import api
    g = _group(group_name)
    n = g.p2p_recv_seq.get(src_rank, 0)
    key = f"{g.prefix()}/p2p/{src_rank}->{g.rank}/{n}"
    oid = _wait_kv(key, timeout)
    value = api.get(ObjectRef(oid), timeout=timeout)
    if isinstance(value, np.ndarray):
        # Own the bytes before acking — the sender may free the backing shm
        # object the moment it observes the ack.
        value = np.array(value)
    # Advance only once the value is in hand: a timed-out recv may be
    # retried and must wait on the same sequence number.
    g.p2p_recv_seq[src_rank] = n + 1
    # Deleting the rendezvous key doubles as the consumption ack: the sender
    # frees the published object once it observes the key gone.
    _kv_del(key)
    return value


def barrier(group_name: str = "default", timeout: float = 60.0) -> None:
    """Block until every rank reaches the same barrier."""
    allgather(np.zeros((), np.int8), group_name=group_name, timeout=timeout)


def declare_collective_group(actors: Sequence, world_size: Optional[int] = None,
                             ranks: Optional[Sequence[int]] = None,
                             backend: str = "shm",
                             group_name: str = "default") -> None:
    """Driver-side convenience: initialize the group inside each actor.

    Uses the hidden ``__rtpu_apply__`` actor method (counterpart of the
    reference's ``__ray_call__``), so any actor class participates without
    declaring anything.
    """
    n = world_size if world_size is not None else len(actors)
    rks = list(ranks) if ranks is not None else list(range(len(actors)))
    from ray_tpu import api

    def _join(_self, world, rank, be, gname):
        init_collective_group(world, rank, backend=be, group_name=gname)

    refs = [
        a.__rtpu_apply__.remote(_join, n, r, backend, group_name)
        for a, r in zip(actors, rks)
    ]
    api.get(refs)
