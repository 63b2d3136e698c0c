"""The replica's side of a serving cell whose model caches LATENT rows and
routes tokens to experts (``runners/serve_latent.py``): the loader
``in_worker.make_loader`` would be, with the three comparisons such a model
can be held to.

- (a) before the engine exists, LOGITS of the program's layers with the
  reference's expert sets handed to them (``families/glm4_moe_lite.py``
  ``pinned_logits`` says why they are pinned) against the reference's own,
  through BOTH attention forms: K and V rebuilt from the latent rows, as
  the prefills run it, and absorbed, as the decode step runs it;
- (b) after the ENGINE has answered the check's prompts, the latent rows ITS
  programs left in its pool for those sequences (``jit_prefill*``'s rows for
  the prompt, then the rows ``jit_decode_step*`` wrote a token at a time),
  found through the engine's own prefix index, against the reference's
  ``c_kv | k_rope`` of the same tokens: a page fault, a row written to the
  wrong slot or a page kept in fewer bits shows in layer 0's rows, and a
  fault of the decode kernel's walk in layer 1's, which lie behind layer
  0's attention and before any router (``verify_and_rows``);
- (c) ITS greedy tokens held against the reference on its own history
  (``reference.verify``): sees the kernel over the pages, the sampler and
  the engine.

The prompts are long enough to cross the latent kernel's compute blocks
(512 tokens): a walk that dropped a block would pass on short ones.
"""

from __future__ import annotations

import os
import threading
import time

from benchmarks import common, in_worker
from benchmarks.in_worker_recurrent import _engine  # the replica's engine

PINNED_ROWS = 16  # the last 16 positions of each prompt
CHUNK = 2  # sequences a reference pass takes at once (memory: the float32
# reference runs beside bf16 weights and the pool, 12.5 of 16.9 GB)


def _chunks(n: int):
    return [slice(i, min(i + CHUNK, n)) for i in range(0, n, CHUNK)]


def pinned_check(c: dict, params, family, reference, prompts: list,
                 pad_to: int) -> dict:
    """Logit error of the program's layers under the reference's routing,
    in both attention forms, over the last ``PINNED_ROWS`` positions of
    every check prompt (rms over positions and the whole vocabulary)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n, r = len(prompts), PINNED_ROWS
    tokens = np.zeros((n, pad_to), np.int32)
    rows = np.zeros((n, r), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
        rows[i] = np.arange(len(p) - r, len(p))
    ref = jax.jit(lambda p, t, at: reference.logits_and_routing(c, p, t, at))
    got = {form: jax.jit(lambda p, t, at, w, e, form=form:
                         family.pinned_logits(c, p, t, at, w, e,
                                              form == "absorbed"))
           for form in ("rebuilt", "absorbed")}
    sq = {form: 0.0 for form in got}
    worst = {form: 0.0 for form in got}
    ref_sq = 0.0
    for part in _chunks(n):
        t, at = jnp.asarray(tokens[part]), jnp.asarray(rows[part])
        want, weights, chosen = ref(params, t, at)
        ref_sq += float(jnp.sum(want * want))
        for form, f in got.items():
            err = f(params, t, at, weights, chosen) - want
            sq[form] += float(jnp.sum(err * err))
            worst[form] = max(worst[form], float(jnp.max(jnp.abs(err))))
    count = n * r * c["vocab_size"]
    return {"logit_rms_error": {f: (v / count) ** 0.5 for f, v in sq.items()},
            "logit_max_error": worst, "logit_rms": (ref_sq / count) ** 0.5,
            "positions": n * r}


def verify_and_rows(c: dict, params, engine, reference, family, ask: dict,
                    steps: int, pad_to: int) -> dict:
    """(c) and (b) from ONE reference pass a chunk of sequences (each a check
    prompt and the tokens the engine continued it with).

    ``gaps``: ``reference.verify``'s, the engine's tokens on their own
    history.  ``rows``: the latent rows in the engine's pool against that
    pass's, over every sequence's full pages, found through the engine's
    prefix index (a finished sequence's full pages stay resident and
    indexed); relative rms, the prompt's rows (a prefill's) and the
    generated tokens' (decode steps') apart, for:

    - ``first``: layer 0's rows, whose input is the embedding's rows on both
      sides (one bf16 product deep): a row kept in fewer bits, or written to
      another place, shows beside it;
    - ``second``: layer 1's rows, which lie behind layer 0's ATTENTION (the
      paged kernel over the pool where a decode step wrote them, K and V
      rebuilt where a prefill did) and its dense MLP, and before any router:
      the deepest rows that a router's near-ties cannot move;
    - ``all``: every layer's.  Behind a router a bf16 stream takes another
      4th expert than float32 in a share of tokens and the layers after it
      then route otherwise too, so these carry 0.13-0.16 where nothing is
      wrong (PERF.md section 6, PR 41); rows of another page or slot read
      1.4."""
    import jax.numpy as jnp

    ps = engine.cfg.page_size
    prompts, outputs = ask["prompts"], ask["outputs"]
    layers = {"first": slice(0, 1), "second": slice(1, 2),
              "all": slice(None)}
    acc = {f"{k}_{part}": [0.0, 0.0] for k in layers
           for part in ("prefill", "decode")}
    gaps, found = [], []
    for part in _chunks(len(prompts)):
        g, want = reference.verify(c, params, prompts[part], outputs[part],
                                   steps, pad_to, rows=True)
        gaps += g
        for j, (p, o) in enumerate(zip(prompts[part], outputs[part])):
            pages = engine.prefix_cache.match(p + o)
            got = family.engine_rows(engine, pages).astype(jnp.float32)
            T, n = len(pages) * ps, min(len(p), len(pages) * ps)
            found.append({"tokens": len(p + o), "resident": T,
                          "decode_rows": T - n})
            d = got - want[:, j, :T]
            for k, which in layers.items():
                for name, lo, hi in (("prefill", 0, n), ("decode", n, T)):
                    acc[f"{k}_{name}"][0] += float(
                        jnp.sum(d[which, lo:hi] ** 2))
                    acc[f"{k}_{name}"][1] += float(
                        jnp.sum(want[which, j, lo:hi] ** 2))
    rows = {k: (e / w) ** 0.5 if w else None for k, (e, w) in acc.items()}
    rows["sequences"] = found
    rows["decode_rows"] = sum(f["decode_rows"] for f in found)
    return {"gaps": gaps, "rows": rows}


def make_loader(spec: dict):
    """``spec`` as ``in_worker.make_loader``'s.  The note it leaves has
    ``pinned`` where that one has ``reference``."""

    def load():
        import jax  # noqa: F401 - first use of the chip in this process

        notes, pid = spec["notes_dir"], os.getpid()
        t0 = time.time()
        clock = in_worker.CompileClock(
            os.path.join(notes, f"compile-{pid}.json"))
        c, chk = spec["config"], spec["check"]
        family = common.module("families", c["family"])
        reference = common.module("reference", c["family"])
        params = family.make_params(c, spec["seed"], c["dtype"])
        jax.block_until_ready(params)
        t1 = time.time()
        pinned = pinned_check(c, params, family, reference, chk["prompts"],
                              chk["pad_to"])
        common.write_json(os.path.join(notes, f"replica-{pid}.json"), {
            **in_worker.devices_note(), "weights_s": t1 - t0,
            "reference_s": time.time() - t1,
            "weight_bytes": sum(x.nbytes for x in jax.tree.leaves(params)),
            "pinned": pinned})
        threading.Thread(target=in_worker._side_channel, args=(spec, clock),
                         name="bench-side", daemon=True).start()
        threading.Thread(target=_verify_channel,
                         args=(spec, params, family, reference),
                         name="bench-verify", daemon=True).start()
        return params, family.model_config(c)

    return load


def _verify_channel(spec: dict, params, family, reference):
    """Serves the driver's one ``cmd-verify.json`` ({prompts, outputs}: what
    the engine answered): the reference's verdict on those tokens and on
    the rows they left in the pool, computed here because the weights and
    the engine are here.  The engine is idle meanwhile."""
    notes, pid, chk = spec["notes_dir"], os.getpid(), spec["check"]
    cmd = os.path.join(notes, "cmd-verify.json")
    while not os.path.exists(cmd):
        time.sleep(0.05)
        if os.path.exists(os.path.join(notes, "cmd-finish")):
            return
    ask, t = common.load_json(cmd), time.time()
    try:
        out = verify_and_rows(spec["config"], params, _engine(), reference,
                              family, ask, chk["steps"], chk["pad_to"])
    except Exception as e:  # noqa: BLE001 - the driver reports it
        out = {"error": f"{type(e).__name__}: {e}"}
    out["verify_s"] = time.time() - t
    common.write_json(os.path.join(notes, f"verify-{pid}.json"), out)
