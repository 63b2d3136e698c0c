"""Wire codec: round trips, struct tolerance, and the malformed-frame fuzz.

Control frames must be schema'd, versioned, and — the security property —
a malformed frame must not be able to execute code.
The fuzz here feeds random bytes, truncations, bit flips, and actual pickle
payloads to the decoder and asserts the only outcomes are a decoded value or
``WireError``.
"""

import os
import pickle
import random

import pytest

from ray_tpu._private import wire
from ray_tpu._private.gcs import ActorInfo, NodeInfo


ROUND_TRIPS = [
    None, True, False, 0, -1, 2**62, -(2**62), 0.0, 3.5, float("inf"),
    "", "hello", "ünïcode", b"", b"\x00\xff" * 100,
    [], [1, 2, 3], (1, "two", b"three", None),
    {"a": 1, b"b": [2.5, {"c": (True,)}]},
    {("ns", b"key"): b"value"},  # GCS KV table shape: tuple keys
    [[[[[1]]]]],
]


@pytest.mark.parametrize("value", ROUND_TRIPS, ids=repr)
def test_round_trip(value):
    assert wire.decode(wire.encode(value)) == value


def test_round_trip_structs():
    a = ActorInfo(actor_id=b"x" * 16, name="n", state="ALIVE",
                  worker_id=b"w", node_id=b"nd", num_restarts=2,
                  max_restarts=-1, class_name="C", addr="1.2.3.4:5")
    assert wire.decode(wire.encode(a)) == a
    n = NodeInfo(node_id=b"y" * 16, resources={"CPU": 4.0, "TPU": 8.0},
                 alive=True, sched_socket="/tmp/s.sock", is_head=True,
                 available={"CPU": 3.0}, queued=7)
    assert wire.decode(wire.encode(n)) == n


def test_struct_field_tolerance():
    """Unknown fields from a newer peer are dropped, not fatal."""
    enc = bytearray(wire.encode(ActorInfo(actor_id=b"a")))
    # splice an extra field into the struct's field dict by re-encoding
    fields = ActorInfo(actor_id=b"a").__dict__ | {"future_field": 42}
    raw = bytearray(wire.encode(fields))
    spliced = bytes(enc[:2]) + bytes(raw)  # 0x0A + struct id + dict
    decoded = wire.decode(spliced)
    assert isinstance(decoded, ActorInfo) and decoded.actor_id == b"a"


def test_errors_reconstruct():
    err = wire.decode(wire.encode(ValueError("bad thing")))
    assert isinstance(err, ValueError) and str(err) == "bad thing"
    # framework exceptions round trip by type
    from ray_tpu.exceptions import ActorDiedError

    err = wire.decode(wire.encode(ActorDiedError("gone")))
    assert isinstance(err, ActorDiedError)


def test_unknown_error_type_degrades_safely():
    class Sneaky(Exception):
        pass

    decoded = wire.decode(wire.encode(Sneaky("boom")))
    assert isinstance(decoded, wire.RemoteError)
    assert "Sneaky" in str(decoded) and "boom" in str(decoded)


def test_unencodable_types_rejected():
    with pytest.raises(wire.WireError):
        wire.encode(object())
    with pytest.raises(wire.WireError):
        wire.encode(lambda: None)


def test_request_response_envelopes():
    method, args, kwargs = wire.decode_request(
        wire.encode_request("kv_put", ("ns", b"k", b"v"), {}))
    assert method == "kv_put" and args == ("ns", b"k", b"v") and kwargs == {}
    ok, payload = wire.decode_response(wire.encode_response(True, [1, 2]))
    assert ok and payload == [1, 2]


def test_length_bomb_rejected_without_allocation():
    # a list claiming 2^31 elements in a 10-byte frame
    frame = b"\x07" + (2**31 - 1).to_bytes(4, "little") + b"\x00" * 5
    with pytest.raises(wire.WireError):
        wire.decode(frame)


def test_pickle_payload_cannot_execute():
    """The RCE the codec exists to prevent: a pickle that would run
    os.system on load must be inert here."""
    evil = pickle.dumps((os.system, ("echo pwned",)))
    with pytest.raises(wire.WireError):
        wire.decode(evil)
    # ...and wrapped as a bytes VALUE it stays bytes, never unpickled
    assert wire.decode(wire.encode(evil)) == evil


def test_fuzz_random_and_mutated_frames():
    rng = random.Random(1234)
    seeds = [wire.encode(v) for v in ROUND_TRIPS]
    seeds.append(wire.encode(ActorInfo(actor_id=b"a")))
    for _ in range(2000):
        choice = rng.random()
        if choice < 0.4:  # pure random bytes
            frame = rng.randbytes(rng.randrange(0, 64))
        elif choice < 0.7:  # truncation of a valid frame
            base = rng.choice(seeds)
            frame = base[:rng.randrange(0, len(base) + 1)]
        else:  # bit flips in a valid frame
            base = bytearray(rng.choice(seeds))
            for _ in range(rng.randrange(1, 4)):
                if base:
                    base[rng.randrange(len(base))] ^= 1 << rng.randrange(8)
            frame = bytes(base)
        try:
            wire.decode(frame)  # decoding garbage to a value is fine
        except wire.WireError:
            pass  # rejecting it is fine
        # anything else (segfault, exec, unexpected exception type) fails


def test_gcs_protocol_over_wire(tmp_path):
    """GcsServer/GcsClient speak the codec end to end, including error
    reconstruction and the version handshake."""
    from ray_tpu._private.gcs import Gcs, GcsClient, GcsServer

    gcs = Gcs()
    server = GcsServer(gcs, str(tmp_path / "gcs.sock"))
    try:
        client = GcsClient(server.socket_path)
        client.kv_put("ns", b"k", b"v")
        assert client.kv_get("ns", b"k") == b"v"
        client.register_actor(ActorInfo(actor_id=b"a1", name="dup"))
        got = client.get_actor_by_name("dup")
        assert isinstance(got, ActorInfo) and got.actor_id == b"a1"
        with pytest.raises(ValueError, match="already taken"):
            client.register_actor(ActorInfo(actor_id=b"a2", name="dup"))
    finally:
        server.shutdown()


def test_gcs_rejects_version_mismatch(tmp_path):
    from ray_tpu._private import protocol
    from ray_tpu._private.gcs import Gcs, GcsServer

    gcs = Gcs()
    server = GcsServer(gcs, str(tmp_path / "gcs.sock"))
    try:
        conn = protocol.connect_addr(server.socket_path)
        conn.send_bytes(b"RTPUWIRE" + bytes([99]))  # future version
        assert conn.recv_bytes() is None  # server hangs up, no reply
    finally:
        server.shutdown()
