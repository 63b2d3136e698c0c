#!/usr/bin/env python3
"""``ops/lightning.py``'s chunked scan APART, on the chip, at the shapes of
the three cells whose prefills run it (PERF.md section 6, PR 63: the numbers
it gave):

    python3 time_lightning_scan.py [<calls>] [forms|blocks|all] [<shape>,..]

``forms``: one JSON line a shape and form: the plain form
(``chunked_plain``: float32 [n, H, ...] arrays in HBM and a ``lax.scan``)
against the kernel (``chunked``), each at chunks of 64 and of 128 tokens.
``module_us`` is the median device time of the jitted call (its ``XLA
Modules`` runs in a profile: the kernel WITH the decays' running sums and
whatever XLA lays out again around it), ``kernel_us`` the ``XLA Ops`` events
of ``lightning_scan`` alone, ``ms_a_layer_a_ktoken`` the module's time a
thousand tokens, ``mxu_share`` the recurrence's arithmetic (``flops``: a
chunk's scores a key, and a head's three products, at six bf16 passes a
``HIGHEST`` product) over the module's time at a v5e's 197 TFLOP/s.  Every
kernel line compares its outputs with the plain form's at chunks of 64 on
the same seeded inputs (``o_err``, ``S_err``: the largest difference over
the yardstick's root mean square).

``blocks``: the kernel with the state rows a grid step forced
(``_scan(rows=)``), every count the shape allows.

The inputs are the cells' own shapes and dtypes (benchmarks/configs/*.json):
Nemotron-3-Super's Mamba-2 layer (1,024 tokens, 128 heads of 64 PACKED two a
row, 8 groups, state 128, float32), MiniCPM-SALA's lightning layer (a chunk
of 2,048 tokens, 32 heads [128, 128] each its own key, bfloat16),
Falcon-H1's mixer (256 and 1,024 tokens, 32 heads [256, 128], 2 groups,
float32); a nonzero S0, decays drawn as the families make them.  It takes
the chip itself: no cluster, nothing else running; ``forms`` about two
minutes.  Copy it into a parent's tree to read the parent (no kernel there:
``chunked`` IS the plain form, and the kernel's lines say so).
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

MXU_TFLOPS = 197.0  # a v5e's, bf16
PASSES = 6  # bf16 passes a float32 product at HIGHEST
# name -> (tokens, heads, d_v, key groups, d_k, heads a state row, dtype)
SHAPES = {
    "nemotron3_super_1024": (1024, 128, 64, 8, 128, 2, "float32"),
    "minicpm_sala_2048": (2048, 32, 128, 32, 128, 1, "bfloat16"),
    "falcon_h1_256": (256, 32, 128, 2, 256, 1, "float32"),
    "falcon_h1_1024": (1024, 32, 128, 2, 256, 1, "float32"),
}
KERNEL = "lightning_scan"


def flops(L: int, H: int, dv: int, G: int, dk: int, chunk: int) -> float:
    """What the recurrence needs by chunks of ``chunk``: a key's scores
    [C, C] over d_k, and a head's ``inside`` [C, C] x [C, d_v], read-out and
    write [C, d_k] x [d_k, d_v] each, two operations a multiply-add."""
    n = -(-L // chunk)
    return 2.0 * n * chunk * (G * chunk * dk + H * dv * (chunk + 2 * dk))


def draw(name: str, seed: int = 0):
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import lightning

    L, H, dv, G, dk, pack, dtype = SHAPES[name]
    r = np.random.default_rng(seed)
    q, k = (jnp.asarray(r.standard_normal((L, G, dk)) * dk ** -0.25, dtype)
            for _ in range(2))
    v = jnp.asarray(r.standard_normal((L, H, dv)), dtype)
    if G == H:  # a fixed decay a head
        g = jnp.broadcast_to(lightning.log_decays(H), (L, H))
    else:  # dt A: a token's own, a head decaying fast among them
        g = -jnp.asarray(r.uniform(1e-3, 0.2, (L, H)), jnp.float32)
        g = g.at[:, 0].set(-1.6)
    S0 = lightning.pack_state(jnp.asarray(
        r.standard_normal((H, dk, dv)), jnp.float32), pack)
    return q, k, v, g, S0


def _device_times(trace_dir: str, module: str) -> tuple:
    """(the jitted call's runs, the kernel's events), seconds each."""
    from benchmarks.trace import reduce

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    runs, kernel = [], []
    for plane, lines in reduce.read_planes(path):
        if not plane.startswith("/device:TPU:0"):
            continue
        for line, events in lines:
            for name, start, end in events:
                if line == "XLA Modules" and module in name:
                    runs.append((end - start) * 1e-9)
                elif line == reduce.OPS_LINE and KERNEL in name:
                    kernel.append((end - start) * 1e-9)
    return runs, kernel


def _timed(fn, name: str, args, calls: int):
    """(outputs, median module seconds, median kernel seconds or None)."""
    import jax

    fn.__name__ = fn.__qualname__ = name
    call = jax.jit(fn)
    out = jax.block_until_ready(call(*args))  # compiles
    trace_dir = tempfile.mkdtemp(prefix="lightning_scan_")
    jax.profiler.start_trace(trace_dir)
    jax.block_until_ready([call(*args) for _ in range(calls)])
    jax.profiler.stop_trace()
    runs, kernel = _device_times(trace_dir, name)
    med = statistics.median
    return out, med(runs), (med(kernel) if kernel else None)


def _err(got, want) -> float:
    import jax.numpy as jnp

    return float(jnp.max(jnp.abs(got - want))
                 / jnp.sqrt(jnp.mean(jnp.square(want))))


def _line(name, form, chunk, module_s, kernel_s, **more) -> dict:
    L, H, dv, G, dk, _, _ = SHAPES[name]
    need = flops(L, H, dv, G, dk, chunk)
    return {"shape": name, "form": form, "chunk": chunk,
            "module_us": module_s * 1e6,
            "kernel_us": None if kernel_s is None else kernel_s * 1e6,
            "ms_a_layer_a_ktoken": module_s * 1e3 * 1000 / L,
            "flops": need,
            "mxu_share": PASSES * need / (module_s * MXU_TFLOPS * 1e12),
            **more}


def forms(name: str, calls: int) -> None:
    from ray_tpu.ops import lightning

    args = draw(name)
    want = None
    for form in ("chunked_plain", "chunked"):
        for chunk in (64, 128):
            fn = getattr(lightning, form)
            try:
                (o, S), module_s, kernel_s = _timed(
                    lambda *a: fn(*a, chunk=chunk),  # noqa: B023
                    f"{form}_{chunk}", args, calls)
            except ValueError as e:  # narrow heads: whole tiles of tokens
                print(json.dumps({"shape": name, "form": form,
                                  "chunk": chunk, "refused": str(e)[:200]}),
                      flush=True)
                continue
            if want is None:
                want = (o, S)
            print(json.dumps(_line(
                name, form, chunk, module_s, kernel_s,
                o_err=_err(o, want[0]), S_err=_err(S, want[1]))), flush=True)


def blocks(name: str, calls: int, chunk: int = 64) -> None:
    from ray_tpu.ops import lightning

    L, H, dv, G, dk, pack, _ = SHAPES[name]
    n_rows = H // pack
    args = draw(name)
    for rows in (d for d in lightning._whole_keys(n_rows, G)
                 if d % 8 == 0 or d == n_rows):
        try:
            _, module_s, kernel_s = _timed(
                lambda *a: lightning._scan(  # noqa: B023
                    *a, chunk=chunk, rows=rows, interpret=False),
                f"scan_rows_{rows}", args, calls)
        except Exception as e:  # more VMEM than a kernel gets unasked
            print(json.dumps({"shape": name, "rows": rows,
                              "refused": str(e)[-300:]}), flush=True)
            continue
        print(json.dumps(_line(name, "chunked", chunk, module_s, kernel_s,
                               rows=rows)), flush=True)


def main() -> None:
    import jax

    calls = int(sys.argv[1]) if len(sys.argv) > 1 else 20
    what = sys.argv[2] if len(sys.argv) > 2 else "forms"
    names = sys.argv[3].split(",") if len(sys.argv) > 3 else list(SHAPES)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"this times a TPU's kernels; found {dev.platform}")
    print(json.dumps({"device": dev.device_kind, "calls": calls}), flush=True)
    for name in names:
        if what in ("forms", "all"):
            forms(name, calls)
        if what in ("blocks", "all"):
            for chunk in (64, 128):
                blocks(name, calls, chunk)


if __name__ == "__main__":
    main()
