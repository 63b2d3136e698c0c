#!/usr/bin/env python3
"""``ops/lightning.py``'s decode update APART, on the chip, at the shapes of
the two cells whose decode step runs it (PERF.md section 6, PR 57: the
numbers it gave):

    python3 benchmarks/time_state_update.py [<calls>]

One JSON line a shape: the median device time of a call (the ``XLA Modules``
runs of the jitted update in a profile, and the ``XLA Ops`` events of the
kernel ``lightning_update`` alone), the bytes the update has to move (each
live slot's float32 state of the layer read once and written once), and
what that is in GB/s and as a share of a v5e's 819 GB/s.  The state is the
cell's whole buffer, every layer's rows, donated as the step has it; a call
updates one layer.  A tree whose update cannot take a shape (before PR 57:
d_k unequal to d_v, keys a group) says so on that shape's line.  It takes
the chip itself: no cluster, nothing else running.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_GBPS = 819.0  # a v5e's
# name -> (layers, slots, live slots, heads, key groups, d_k, d_v): what the
# configurations' engines hold (benchmarks/configs/*.json) and about what
# their cells keep live
SHAPES = {
    "falcon_h1_34b_serve_1chip": (6, 64, 50, 32, 2, 256, 128),
    "minicpm_sala_serve_1chip": (6, 32, 24, 32, 32, 128, 128),
}


def _device_times(trace_dir: str) -> tuple:
    """(the jitted update's runs, the kernel's events), seconds each."""
    from benchmarks.trace import reduce

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    runs, kernel = [], []
    for plane, lines in reduce.read_planes(path):
        if not plane.startswith("/device:TPU:0"):
            continue
        for line, events in lines:
            for name, start, end in events:
                if line == "XLA Modules" and "update_one_layer" in name:
                    runs.append((end - start) * 1e-9)
                elif line == "XLA Ops" and "lightning_update" in name:
                    kernel.append((end - start) * 1e-9)
    return runs, kernel


def time_shape(name: str, calls: int) -> dict:
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import lightning

    layers, slots, live, H, G, dk, dv = SHAPES[name]
    need = 2.0 * live * H * dk * dv * 4
    out = {"shape": name, "state": [layers, slots, H, dk, dv], "live": live,
           "bytes_required": need}
    keys = jax.random.split(jax.random.key(0), 5)
    state = jax.random.normal(keys[0], (layers, slots, H, dk, dv),
                              jnp.float32)
    q, k = (jax.random.normal(kk, (slots, G, dk), jnp.float32)
            for kk in keys[1:3])
    v = jax.random.normal(keys[3], (slots, H, dv), jnp.float32)
    g = -jax.random.uniform(keys[4], (slots, H), jnp.float32, 1e-3, 0.2)
    active = jnp.arange(slots) < live

    def update_one_layer(state, layer):
        return lightning.decode_update(state, layer, q, k, v, g, active)

    update = jax.jit(update_one_layer, donate_argnums=0)
    try:
        o, state = update(state, jnp.int32(0))  # compiles
    except (ValueError, TypeError) as e:
        return {**out, "refused": f"{type(e).__name__}: {e}"[:300]}
    jax.block_until_ready(state)
    trace_dir = tempfile.mkdtemp(prefix="state_update_")
    jax.profiler.start_trace(trace_dir)
    for i in range(calls):
        o, state = update(state, jnp.int32(i % layers))
    jax.block_until_ready((o, state))
    jax.profiler.stop_trace()
    runs, kernel = _device_times(trace_dir)
    for key, times in (("call", runs), ("kernel", kernel)):
        if times:
            t = statistics.median(times)
            out.update({f"{key}_us": t * 1e6, f"{key}_events": len(times),
                        f"{key}_gbps": need / t * 1e-9,
                        f"{key}_share_of_hbm": need / t * 1e-9 / HBM_GBPS})
    return out


if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 24
    for shape in SHAPES:
        print(json.dumps(time_shape(shape, n)), flush=True)
