#!/usr/bin/env python3
"""``ops/paged_attention.py``'s two walks APART, on the chip, at the pools
of the cells whose decode step runs them (PERF.md section 6, PR 60: the
numbers it gave):

    python3 time_paged_walk.py [<calls>] [kernels|bodies|blocks|all] [<shape>,..]

``kernels``: one JSON line a shape: the kernel's median device time (its
``XLA Ops`` events in a profile), the bytes the walk must move (every page
a live slot's length, window or list reaches, K and V, once), GB/s, the
share of a v5e's 819 GB/s, and what that is a block and a page copy.  The
pools are the cells' own (benchmarks/configs/*.json, the engine's pools)
with about what the cells keep live; the tables are drawn twice, the pages
of a slot in a row (a young free list) and shuffled (no run anywhere).

``bodies``: the same walk with its body REPLACED, a probe kernel of this
file (the tree's kernels are not edited for it): the parent's page-by-page
form whole, (a) its copies and waits with no products, (b) its products over
buffers filled once, (c) every copy of a block issued in a straight line
and waited for page by page / ONCE a buffer, and the copies of 2 or 4
adjacent pages merged.  That says where a copy's ~40 ns lie: the issue, the
wait, the DMA engine's turn a descriptor, or the body.

``blocks``: the one-wait and four-a-copy forms at 16 / 32 / 64 pages a block.

It takes the chip itself: no cluster, nothing else running; ``kernels``
about three minutes, ``bodies`` eight.  Copy it into a parent's tree to read
the parent's kernels; run each step under ``timeout`` (a wait that asks a
semaphore for bytes that never come hangs the chip, and the interpreter
cannot see it).
"""

from __future__ import annotations

import functools
import glob
import json
import math
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

HBM_GBPS = 819.0  # a v5e's
# name -> the pool a layer kind holds ([layers, pages, page_size, KV heads,
# head_dim], or [layers, pages, page_size, width] of latent rows), the
# engine's slots and table width, the query heads the kernel is handed, and
# about what the cell keeps live (slots x tokens)
SHAPES = {
    "trinity_full": dict(pool=(1, 32768, 16, 4, 128), slots=64, table=512,
                         heads=32, live=9, tokens=4400),
    "trinity_window": dict(pool=(4, 8448, 16, 4, 128), slots=64, table=512,
                           heads=32, live=9, tokens=4400, window=2048),
    # lists a KV head of a slot (heads_apart): the window's 128 pages, the
    # first block's and 64 chosen blocks of 4 pages
    "minicpm_sala_lists": dict(pool=(2, 51201, 16, 2, 128), slots=32,
                               table=392, heads=32, live=7, tokens=6100,
                               heads_apart=True),
    "falcon_h1": dict(pool=(6, 6144, 16, 4, 128), slots=64, table=128,
                      heads=20, live=38, tokens=430),
    # the block pass: 4 rows a slot x 32 heads, each KV head's together
    "sdar_block_pass": dict(pool=(8, 2048, 16, 4, 128), slots=32, table=64,
                            heads=128, live=28, tokens=420),
    "glm_latent": dict(pool=(8, 12288, 16, 640), slots=64, table=256,
                       heads=32, live=28, tokens=1300, value_dim=512),
    "longcat_latent": dict(pool=(8, 12288, 16, 640), slots=64, table=256,
                           heads=64, live=38, tokens=1360, value_dim=512),
    "mistral_control": dict(pool=(16, 3072, 16, 8, 128), slots=32,
                            table=128, heads=32, live=32, tokens=700),
}


def _kernel_times(trace_dir: str, needle: str) -> list:
    """Device seconds of each ``XLA Ops`` event whose name holds ``needle``."""
    from benchmarks.trace import reduce

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    times = []
    for plane, lines in reduce.read_planes(path):
        if not plane.startswith("/device:TPU:0"):
            continue
        for line, events in lines:
            if line == reduce.OPS_LINE:
                times += [(end - start) * 1e-9 for name, start, end in events
                          if needle in name]
    return times


def _profiled(call, calls: int, needle: str) -> list:
    import jax

    jax.block_until_ready(call(0))  # compiles
    trace_dir = tempfile.mkdtemp(prefix="paged_walk_")
    jax.profiler.start_trace(trace_dir)
    out = [call(i) for i in range(calls)]
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    return _kernel_times(trace_dir, needle)


def draw(name: str, order: str, seed: int = 0):
    """The shape's pools, query, tables and lengths; ``order`` is ``rows``
    (a slot's pages consecutive ids) or ``shuffled``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    s = SHAPES[name]
    pool_shape, B, P = s["pool"], s["slots"], s["table"]
    ps = pool_shape[2]
    rng = np.random.default_rng(seed)
    apart = s.get("heads_apart", False)
    G = pool_shape[3] if apart else 1
    lengths = np.zeros((B, G), np.int32)
    lengths[:s["live"]] = np.minimum(
        rng.integers(int(s["tokens"] * 0.8), int(s["tokens"] * 1.2),
                     (s["live"], G)), P * ps)
    used = -(-lengths // ps)
    ids = np.arange(1, 1 + B * G * P)
    if B * G * P >= pool_shape[1]:  # pages enough for the live slots only
        ids = np.arange(1, pool_shape[1])
    if order == "shuffled":
        ids = rng.permutation(ids)
    tables = np.zeros((B, G, P), np.int32)
    at = 0
    for b in range(B):
        for g in range(G):
            n = used[b, g]
            tables[b, g, :n] = ids[at:at + n]
            at += n
    keys = jax.random.split(jax.random.key(seed), 3)
    pools = [jax.random.normal(k, pool_shape, jnp.bfloat16)
             for k in keys[:1 if len(pool_shape) == 4 else 2]]
    q = jax.random.normal(keys[2], (B, s["heads"], pool_shape[-1]),
                          jnp.bfloat16)
    if not apart:
        tables, lengths = tables[:, 0], lengths[:, 0]
    return pools, q, jnp.asarray(tables), jnp.asarray(lengths)


def pages_walked(name: str, lengths) -> int:
    import numpy as np

    s = SHAPES[name]
    ps = s["pool"][2]
    lengths = np.asarray(lengths)
    first = np.maximum(lengths - s.get("window", 1 << 30), 0) // ps
    return int((-(-lengths // ps) - first).sum())


def walk_bytes(name: str, lengths) -> float:
    """Bytes the walk must move: the pages reached, from every pool."""
    pool = SHAPES[name]["pool"]
    return (pages_walked(name, lengths) * math.prod(pool[2:]) * 2.0
            * (1 if len(pool) == 4 else 2))


def dense_diff(name: str, got, pools, q, tables, lengths) -> float:
    """The kernel's answer at layer 1 against the gathered, masked float32
    softmax over the live slots' tables (what the walk replaced)."""
    import jax
    import jax.numpy as jnp

    s = SHAPES[name]
    live, ps = s["live"], s["pool"][2]
    apart = s.get("heads_apart", False)
    layer = 1 % s["pool"][0]
    q, tables, lengths = q[:live], tables[:live], lengths[:live]
    got = got[:live].astype(jnp.float32)
    if apart:  # a list a KV head: its query heads, its own head's rows
        G = s["pool"][3]
        B, H, d = q.shape
        q = q.reshape(B * G, H // G, d)
        tables, lengths = tables.reshape(B * G, -1), lengths.reshape(-1)
        got = got.reshape(B * G, H // G, -1)
    T = tables.shape[1] * ps
    keys = pools[0][layer][tables].astype(jnp.float32)
    vals = (pools[1][layer][tables].astype(jnp.float32) if len(pools) == 2
            else keys[..., :s["value_dim"]])
    if len(pools) == 2:  # [B, P, ps, n_kv, d] -> a query head's own KV head
        n_kv = keys.shape[3]
        head = (jnp.arange(q.shape[0]) % n_kv)[:, None] if apart else (
            jnp.arange(q.shape[1]) // (q.shape[1] // n_kv))[None]
        head = jnp.broadcast_to(head, q.shape[:2])
        pick = lambda x: jnp.take_along_axis(  # noqa: E731
            x.reshape(x.shape[0], T, n_kv, -1).transpose(0, 2, 1, 3),
            head[:, :, None, None], axis=1)  # [B, H, T, d]
        keys, vals = pick(keys), pick(vals)
        scores = jnp.einsum("bhd,bhtd->bht", q.astype(jnp.float32), keys,
                            precision="highest") / math.sqrt(q.shape[-1])
    else:
        keys, vals = keys.reshape(-1, T, keys.shape[-1]), vals.reshape(
            -1, T, vals.shape[-1])
        scores = 0.05 * jnp.einsum("bhd,btd->bht", q.astype(jnp.float32),
                                   keys, precision="highest")
    at = jnp.arange(T)[None]
    mask = (at < lengths[:, None]) & (
        at >= (lengths - s.get("window", 1 << 30))[:, None])
    p = jax.nn.softmax(jnp.where(mask[:, None], scores, -1e30), axis=-1)
    want = (jnp.einsum("bht,bhtd->bhd", p, vals, precision="highest")
            if len(pools) == 2
            else jnp.einsum("bht,btd->bhd", p, vals, precision="highest"))
    return float(jnp.abs(got - want).max())


def kernel_line(name: str, order: str, calls: int) -> dict:
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    s = SHAPES[name]
    pools, q, tables, lengths = draw(name, order)
    layers = s["pool"][0]
    if len(pools) == 1:
        needle = "paged_latent_decode_attention"

        def call(i):
            return pa.paged_latent_decode_attention(
                q, pools[0], tables, lengths, jnp.int32(i % layers),
                value_dim=s["value_dim"], sm_scale=0.05)
    else:
        needle = "paged_decode_attention"
        kw = {}
        if "window" in s:
            kw["window"] = s["window"]
        if s.get("heads_apart"):
            kw["heads_apart"] = True

        def call(i):
            return pa.paged_decode_attention(
                q, *pools, tables, lengths, jnp.int32(i % layers), **kw)

    times = _profiled(call, calls, needle)
    need = walk_bytes(name, lengths)
    pages = pages_walked(name, lengths)
    out = {"shape": name, "tables": order, "pool": list(s["pool"]),
           "live": s["live"], "pages_walked": pages, "bytes_required": need,
           "page_bytes": math.prod(s["pool"][2:]) * 2,
           "max_abs_diff_from_dense": dense_diff(name, call(1), pools, q,
                                                 tables, lengths)}
    if times:
        t = statistics.median(times)
        copies = pages * len(pools)
        out.update(kernel_us=t * 1e6, events=len(times),
                   gbps=need / t * 1e-9,
                   share_of_hbm=need / t * 1e-9 / HBM_GBPS,
                   us_a_32_copies=t * 1e6 / copies * 32,
                   ns_a_page_copy=t * 1e9 / copies)
    return out


# -- the walk with its body replaced -----------------------------------------

FORMS = ("paged", "straight", "one_wait", "merged2", "merged4")


def _probe_kernel(lengths_ref, tables_ref, layer_ref, starts_ref, q_ref,
                  *refs, n_pools: int, n_kv: int, value_dim: int, form: str,
                  copies: bool, products: bool):
    """The tree's walk (``_paged_decode_kernel``; one pool: the latent one)
    with what a block does chosen by hand.  ``form``: ``paged`` is the
    parent's (a predicate, a copy and a wait a page); the others copy EVERY
    page of a block (entries past the length are the null page) with no
    predicate: ``straight`` waits page by page, ``one_wait`` once a
    buffer, ``merged<R>`` also moves R adjacent table entries in one copy
    (the tables are rows: entry g * R + r is entry g * R's page + r)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    import jax
    import jax.numpy as jnp

    pools, o_ref = refs[:n_pools], refs[n_pools]
    bufs, sems = refs[n_pools + 1:2 * n_pools + 1], refs[-1]
    B, H, d = q_ref.shape
    ppb, ps = bufs[0].shape[1:3]
    P = tables_ref.shape[0] // B
    block_tokens = ppb * ps
    cols = block_tokens * n_kv
    layer = layer_ref[0]
    R = int(form[6:]) if form.startswith("merged") else 1

    def first_block(b):
        return starts_ref[b] // block_tokens

    def block_copies(b, blk, buf, wait: bool):
        n_pages = (lengths_ref[b] + ps - 1) // ps
        if form == "paged":
            for i in range(ppb):
                pg = blk * ppb + i

                @pl.when((pg < n_pages) & (pg >= starts_ref[b] // ps))
                def _():
                    page = 0 if wait else tables_ref[b * P + pg]
                    for s in range(n_pools):
                        copy = pltpu.make_async_copy(
                            pools[s].at[layer, page], bufs[s].at[buf, i],
                            sems.at[s, buf])
                        copy.wait() if wait else copy.start()
            return
        if wait and form != "straight":
            for s in range(n_pools):
                pltpu.make_async_copy(
                    pools[s].at[layer, pl.ds(0, ppb)], bufs[s].at[buf],
                    sems.at[s, buf]).wait()
            return
        for i in range(0, ppb, R):
            page = 0 if wait else tables_ref[b * P + blk * ppb + i]
            for s in range(n_pools):
                copy = pltpu.make_async_copy(
                    pools[s].at[layer, pl.ds(page, R)],
                    bufs[s].at[buf, pl.ds(i, R)], sems.at[s, buf])
                copy.wait() if wait else copy.start()

    def next_active(b):
        return jax.lax.while_loop(
            lambda n: (n < B) & (lengths_ref[jnp.minimum(n, B - 1)] == 0),
            lambda n: n + 1, b + 1)

    row = jax.lax.broadcasted_iota(jnp.int32, (H, cols), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (H, cols), 1)
    own = (col % n_kv) == (row // (H // n_kv))
    o_ref[...] = jnp.zeros_like(o_ref)
    for buf_ref in bufs:
        buf_ref[...] = jnp.zeros_like(buf_ref)
    first = next_active(-1)
    if copies:
        @pl.when(first < B)
        def _():
            block_copies(first, first_block(first), 0, wait=False)

    def slot(carry):
        b, buf = carry
        length = lengths_ref[b]
        n_blocks = (length + block_tokens - 1) // block_tokens
        nxt = next_active(b)
        q = q_ref[b]

        def block(i, carry):
            m, l, acc, buf = carry
            if copies:
                more = i + 1 < n_blocks

                @pl.when(more | (nxt < B))
                def _():
                    after = jnp.minimum(nxt, B - 1)
                    block_copies(jnp.where(more, b, after),
                                 jnp.where(more, i + 1, first_block(after)),
                                 1 - buf, wait=False)

                block_copies(b, i, buf, wait=True)
            if not products:
                return m, l, acc, 1 - buf
            k = bufs[0][buf].reshape(cols, d)
            v = (bufs[1][buf].reshape(cols, d) if n_pools == 2
                 else k[:, :value_dim])
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            keep = col < (length - i * block_tokens) * n_kv
            if n_kv > 1:
                keep &= own
            keep &= col >= (starts_ref[b] - i * block_tokens) * n_kv
            s = jnp.where(keep, s * 0.05, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l, acc, 1 - buf

        _, l, acc, buf = jax.lax.fori_loop(
            first_block(b), n_blocks, block,
            (jnp.full((H, 1), -1e30, jnp.float32),
             jnp.ones((H, 1), jnp.float32),
             jnp.zeros((H, value_dim), jnp.float32), buf))
        o_ref[b] = (acc / l).astype(o_ref.dtype)
        return nxt, buf

    jax.lax.while_loop(lambda c: c[0] < B, slot, (first, 0))


def probe(name: str, form: str, copies: bool, products: bool,
          pages_per_block: int | None = None):
    """(call, copies a call issues) of the probe at a shape, rows tables."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = SHAPES[name]
    if s.get("heads_apart"):
        raise ValueError("the probe walks tables, not lists")
    pools, q, tables, lengths = draw(name, "rows")
    n_pools = len(pools)
    ps = s["pool"][2]
    n_kv = s["pool"][3] if n_pools == 2 else 1
    value_dim = s.get("value_dim", s["pool"][-1])
    B, P = tables.shape
    ppb = pages_per_block or (16 if n_pools == 2 else 32)
    ppb = min(ppb, P)
    starts = jnp.maximum(lengths - s.get("window", 1 << 30), 0)
    on_tpu = jax.default_backend() == "tpu"
    if n_pools == 1:
        q = jnp.pad(q, ((0, 0), (0, -q.shape[1] % 16), (0, 0)))
    kernel = functools.partial(
        _probe_kernel, n_pools=n_pools, n_kv=n_kv, value_dim=value_dim,
        form=form, copies=copies, products=products)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    run = jax.jit(pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(),
            in_specs=[vmem] + [any_space] * n_pools, out_specs=vmem,
            scratch_shapes=[pltpu.VMEM((2, ppb) + tuple(s["pool"][2:]),
                                       jnp.bfloat16)] * n_pools
            + [pltpu.SemaphoreType.DMA((n_pools, 2))]),
        out_shape=jax.ShapeDtypeStruct(q.shape[:2] + (value_dim,), q.dtype),
        interpret=not on_tpu, name="paged_walk_probe"))
    flat = tables.reshape(-1).astype(jnp.int32)
    layers = s["pool"][0]

    def call(i):
        return run(lengths, flat, jnp.asarray([i % layers], jnp.int32),
                   starts.astype(jnp.int32), q, *pools)

    ln, st = np.asarray(lengths), np.asarray(starts)
    live = ln > 0
    blocks = int((-(-ln // (ppb * ps)) - st // (ppb * ps))[live].sum())
    if form == "paged":
        issued = int((-(-ln // ps) - st // ps)[live].sum()) * n_pools
    else:
        R = int(form[6:]) if form.startswith("merged") else 1
        issued = blocks * (ppb // R) * n_pools
    return call, issued, blocks, ppb


def body_lines(name: str, calls: int):
    import numpy as np

    _, _, _, lengths = draw(name, "rows")
    need = walk_bytes(name, lengths)
    variants = [("paged", True, True), ("paged", True, False),
                ("paged", False, True)]
    for form in FORMS[1:]:
        variants += [(form, True, True), (form, True, False)]
    reference = None
    for form, copies, products in variants:
        call, issued, blocks, ppb = probe(name, form, copies, products)
        out = {"shape": name, "form": form, "copies": copies,
               "products": products, "pages_per_block": ppb,
               "blocks": blocks, "copies_issued": issued if copies else 0}
        if copies and products:  # every form computes the same attention
            got = np.asarray(call(0)).astype(np.float32)
            if reference is None:
                reference = got
            out["max_abs_diff_from_paged"] = float(
                np.abs(got - reference).max())
        times = _profiled(call, calls, "paged_walk_probe")
        if times:
            t = statistics.median(times)
            out.update(kernel_us=t * 1e6, events=len(times),
                       us_a_block=t * 1e6 / blocks,
                       share_of_hbm=need / t * 1e-9 / HBM_GBPS)
            if copies:
                out["ns_a_copy"] = t * 1e9 / issued
        yield out


def block_lines(name: str, calls: int):
    """The block's size apart: ``one_wait`` and ``merged4`` at 16 / 32 / 64
    pages a block (the ~0.3 us a block is the term a larger one amortises)."""
    _, _, _, lengths = draw(name, "rows")
    need = walk_bytes(name, lengths)
    for form in ("one_wait", "merged4"):
        for ppb in (16, 32, 64):
            call, issued, blocks, ppb = probe(name, form, True, True, ppb)
            times = _profiled(call, calls, "paged_walk_probe")
            t = statistics.median(times)
            yield {"shape": name, "form": form, "pages_per_block": ppb,
                   "blocks": blocks, "copies_issued": issued,
                   "kernel_us": t * 1e6, "us_a_block": t * 1e6 / blocks,
                   "share_of_hbm": need / t * 1e-9 / HBM_GBPS}


if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 20
    what = sys.argv[2] if len(sys.argv) > 2 else "all"
    only = sys.argv[3].split(",") if len(sys.argv) > 3 else list(SHAPES)
    if what in ("kernels", "all"):
        for shape in only:
            for order in ("rows", "shuffled"):
                print(json.dumps(kernel_line(shape, order, n)), flush=True)
    if what in ("bodies", "all"):
        for shape in only:
            if not SHAPES[shape].get("heads_apart"):
                for line in body_lines(shape, n):
                    print(json.dumps(line), flush=True)
    if what in ("blocks", "all"):
        for shape in ("trinity_window", "glm_latent"):
            if shape in only:
                for line in block_lines(shape, n):
                    print(json.dumps(line), flush=True)
