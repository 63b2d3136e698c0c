"""The replica's side of a serving cell whose model ROUTES tokens to experts
(``runners/serve_routed.py``): the loader ``in_worker.make_loader`` would
be, with the two comparisons such a model can be held to.

A served bf16 model takes another expert than the float32 reference
wherever its router is near a tie (``families/sdar_moe.py``
``pinned_logits`` has the numbers), so its greedy tokens leave the
reference's in most sequences within a few blocks although nothing is
wrong, and ``in_worker.make_loader``'s reference, computed before the
engine exists and compared token by token until the first difference,
cannot hold it.  Here:

- before the engine exists, LOGITS of the program's layers with the
  reference's expert sets handed to them against the reference's own
  (``pinned``): sees precision, expert identity and dropped assignments;
- after the engine has answered the check's prompts, ITS tokens held
  against the reference on its own history (``cmd-verify`` ->
  ``reference.verify``): sees the pages, the sampler and the engine.
"""

from __future__ import annotations

import os
import threading
import time

from benchmarks import common, in_worker

PINNED = {"pad_to": 64, "rows": 16}  # the last 16 positions of each prompt


def pinned_check(c: dict, params, family, reference, prompts: list) -> dict:
    """Logit error of the program's layers under the reference's routing,
    over the last ``rows`` positions of every check prompt."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n, s, r = len(prompts), PINNED["pad_to"], PINNED["rows"]
    tokens = np.full((n, s), c["sampler"]["mask_token_id"], np.int32)
    rows = np.zeros((n, r), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
        rows[i] = np.arange(len(p) - r, len(p))
    tokens, rows = jnp.asarray(tokens), jnp.asarray(rows)
    ref, weights, chosen = jax.jit(
        lambda p, t, at: reference.logits_and_routing(c, p, t, at))(
            params, tokens, rows)
    got = jax.jit(lambda p, t, at, w, e: family.pinned_logits(
        c, p, t, at, w, e))(params, tokens, rows, weights, chosen)
    err = got - ref
    return {"logit_rms_error": float(jnp.sqrt(jnp.mean(err * err))),
            "logit_max_error": float(jnp.max(jnp.abs(err))),
            "logit_rms": float(jnp.sqrt(jnp.mean(ref * ref))),
            "positions": n * r}


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
        pinned = pinned_check(c, params, family, reference, chk["prompts"])
        common.write_json(os.path.join(notes, f"replica-{pid}.json"), {
            **in_worker.devices_note(), "weights_s": t1 - t0,
            "reference_s": time.time() - t1,
            "weight_bytes": sum(x.nbytes for x in jax.tree.leaves(params)),
            "pinned": pinned})
        threading.Thread(target=in_worker._side_channel, args=(spec, clock),
                         name="bench-side", daemon=True).start()
        threading.Thread(target=_verify_channel,
                         args=(spec, params, reference),
                         name="bench-verify", daemon=True).start()
        return params, family.model_config(c)

    return load


def _verify_channel(spec: dict, params, reference):
    """Serves the driver's one ``cmd-verify.json`` ({prompts, outputs}: what
    the engine answered): the reference's verdict on those tokens, computed
    here because the weights are here.  The engine is idle meanwhile."""
    notes, pid, chk = spec["notes_dir"], os.getpid(), spec["check"]
    cmd = os.path.join(notes, "cmd-verify.json")
    while not os.path.exists(cmd):
        time.sleep(0.05)
        if os.path.exists(os.path.join(notes, "cmd-finish")):
            return
    ask, t = common.load_json(cmd), time.time()
    try:
        out = {"gaps": reference.verify(
            spec["config"], params, ask["prompts"], ask["outputs"],
            chk["steps"], chk["pad_to"])}
    except Exception as e:  # noqa: BLE001 - the driver reports it
        out = {"error": f"{type(e).__name__}: {e}"}
    out["verify_s"] = time.time() - t
    common.write_json(os.path.join(notes, f"verify-{pid}.json"), out)
