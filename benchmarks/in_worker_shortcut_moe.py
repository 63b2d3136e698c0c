"""The replica's side of a serving cell whose model is built of
shortcut-connected double layers and holds ONE CHIP'S SHARE of its routed
experts (``runners/serve_shortcut_moe.py``): ``in_worker_latent``'s loader
and verify channel, with that family's comparison (a) widened by what a
share needs held apart.

- (a) before the engine exists, LOGITS of the program's layers with the
  reference's columns handed to them, through BOTH attention forms
  (``in_worker_latent`` says why they are pinned), over the share's slice
  of the vocabulary; and (a') the program's ``dispatch_share`` (its sort,
  the grouped kernel in column blocks, the combine) of every layer ON THE
  REFERENCE'S ROWS against the reference's held experts' part.  The held
  experts add about 0.03 of the stream's rms (a pick in 48 lands on them),
  so their weights kept in fewer bits would not show in the logits at all:
  (a') holds them on their own;
- (b) the latent rows the engine's programs left in its pool, a pool layer
  an ATTENTION SUBLAYER (``in_worker_latent.verify_and_rows``, as it
  stands: its ``first`` is layer 0's first sublayer, its ``second`` layer
  0's SECOND sublayer, which lies behind one attention and one dense FFN
  and before any router's output rejoins the stream, its ``all`` the
  eight);
- (c) the engine's greedy tokens on its own history
  (``reference.verify``).

A CONTROL (``runners/serve_shortcut_moe.py`` ``control``, never a run)
hands the loader a ``fault``, planted HERE in the replica's process before
anything compiles (``plant``), so that (a), (a'), the engine's programs and
with them (b) and (c) all run it; ``experts_3bit`` alone is planted into
(a) and (a') only (``pinned_check``: the cut weights are made inside those
two programs, and the engine's programs have no room for a second copy of
the experts).  ``control_pinned`` reads (a) and (a') under every fault in
one process, with no engine.
"""

from __future__ import annotations

import os
import threading
import time

from benchmarks import common, in_worker, in_worker_latent

PINNED_ROWS = 64  # the last 64 positions of each prompt
FAULTS = ("identity_left_out", "q_scale_left_out", "kv_scale_left_out",
          "branch_late", "experts_3bit", "rows_3bit")


def _cut(x, bits: int = 3):
    """x with its mantissa cut to ``bits`` (``reduce_precision``: XLA
    removes a cast pair as excess precision)."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=bits)


def plant(fault: str):
    """``fault`` into the program, in this process, before it compiles.
    Returns the function that takes it out again (``control_pinned`` plants
    one fault after another in one process)."""
    from ray_tpu.llm import model as lm
    from ray_tpu.models import glm_moe_lite as glm
    from ray_tpu.models import longcat_flash as lc
    from ray_tpu.models import moe
    from ray_tpu.models.llama import gated_mlp, rms_norm

    if fault == "identity_left_out":  # the identity picks add nothing
        whole = moe.dispatch_share
        swap = (moe, "dispatch_share", lambda *a, identity, **kw: whole(
            *a, identity=0, **kw))
    elif fault in ("q_scale_left_out", "kv_scale_left_out"):
        swap = (lc.LongCatFlashConfig,
                fault[:-len("_scale_left_out")] + "_lora_scale", 1.0)
    elif fault == "branch_late":  # the experts fed from norm_f1(h3)

        def late(cfg, p, experts, i, x, positions, attend, pool,
                 pinned=None):
            a, b = p["first"], p["second"]
            h1, (pool, *_) = glm.latent_attention_block(
                cfg, a, x, positions, attend, (pool, None, 2 * i))
            m = rms_norm(h1, a["mlp_norm"], cfg.norm_eps)
            h2 = h1 + gated_mlp(a, m)
            h3, (pool, *_) = glm.latent_attention_block(
                cfg, b, h2, positions, attend, (pool, None, 2 * i + 1))
            n = rms_norm(h3, b["mlp_norm"], cfg.norm_eps)
            s, counted = lc.routed_branch(cfg, p, experts, i, n, pinned)
            return h3 + gated_mlp(b, n) + s, pool, counted

        swap = (lc, "double_layer", late)
    elif fault == "rows_3bit":  # what the pages hold, in 3 bits
        write = lm._write_rows
        swap = (lm, "_write_rows", lambda pool, li, pages, slots, row: write(
            pool, li, pages, slots, _cut(row)))
    elif fault == "experts_3bit":  # (``pinned_check``: its ``served``)
        return lambda: None
    else:
        raise ValueError(f"fault {fault!r} is none of {FAULTS}")
    holder, name, new = swap
    old = holder.__dict__[name]  # (a property stays a property)
    setattr(holder, name, new)
    return lambda: setattr(holder, name, old)


def pinned_check(c: dict, params, family, reference, prompts: list,
                 pad_to: int, fault: str = None) -> dict:
    """(a) and (a') over the last ``PINNED_ROWS`` positions of every check
    prompt: the logit error (rms over positions and the vocabulary slice)
    of the program's layers under the reference's routing in both attention
    forms; the relative rms error of the program's held experts' part on
    the reference's rows, over the rows some held expert was picked for;
    and what the seeded router did there (the top 12's mass, the share of
    identity picks, the routed branch's rms beside the stream's)."""
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
    rows_fault = _cut if fault == "rows_3bit" else None
    served = lambda p: p  # noqa: E731
    if fault == "experts_3bit":  # the held experts in 3 bits, (a) and (a')

        def served(p):
            return {**p, "layers": {**p["layers"], "experts": jax.tree.map(
                _cut, p["layers"]["experts"])}}

    got = {form: jax.jit(lambda p, t, at, w, e, form=form:
                         family.pinned_logits(c, served(p), t, at, w, e,
                                              form == "absorbed",
                                              rows_fault))
           for form in ("rebuilt", "absorbed")}
    held_of = jax.jit(lambda p, m, w, e: family.held_part(
        c, served(p), m, w, e))
    sq = {form: 0.0 for form in got}
    worst = {form: 0.0 for form in got}
    ref_sq = held_err = held_sq = mass = ident = picks = 0.0
    branch_sq = m_sq = 0.0
    held_rows = 0
    real = family.router_columns(c)[0]
    for part in in_worker_latent._chunks(n):
        t, at = jnp.asarray(tokens[part]), jnp.asarray(rows[part])
        want, weights, chosen, m, held = ref(params, t, at)
        ref_sq += float(jnp.sum(want * want))
        for form, f in got.items():
            err = f(params, t, at, weights, chosen) - want
            sq[form] += float(jnp.sum(err * err))
            worst[form] = max(worst[form], float(jnp.max(jnp.abs(err))))
        # the routing AT the compared rows, [layers, b * r, k]
        layers, b = weights.shape[0], t.shape[0]
        pick = lambda y: jnp.take_along_axis(  # noqa: E731
            y.reshape(layers, b, pad_to, -1), at[None, :, :, None],
            axis=2).reshape(layers, b * r, -1)
        w_at, e_at = pick(weights), pick(chosen)
        m_at, held = (y.reshape(layers, b * r, -1) for y in (m, held))
        err = held_of(params, m_at, w_at, e_at) - held
        held_err += float(jnp.sum(err * err))
        held_sq += float(jnp.sum(held * held))
        held_rows += int(jnp.sum(jnp.any(held != 0, axis=-1)))
        zero_w = jnp.sum(jnp.where(e_at >= real, w_at, 0.0), -1,
                         keepdims=True)
        branch = held + zero_w * m_at
        branch_sq += float(jnp.sum(branch * branch))
        m_sq += float(jnp.sum(m_at * m_at))
        mass += float(jnp.sum(w_at)) / c["routed_scaling_factor"]
        ident += float(jnp.sum(e_at >= real))
        picks += float(e_at.size)
    count = n * r * c["vocab_size"]
    return {"logit_rms_error": {f: (v / count) ** 0.5 for f, v in sq.items()},
            "logit_max_error": worst, "logit_rms": (ref_sq / count) ** 0.5,
            "held_rel_rms_error": (held_err / held_sq) ** 0.5
            if held_sq else None,
            "held_rows": held_rows, "positions": n * r,
            "router": {"top_k_mass": mass * c["moe_topk"] / picks,
                       "identity_pick_share": ident / picks,
                       "branch_rms_over_normed_input":
                           (branch_sq / m_sq) ** 0.5}}


def make_loader(spec: dict):
    """``spec`` as ``in_worker.make_loader``'s, and ``fault`` (a control's,
    never a run's).  The note it leaves has ``pinned`` where that one has
    ``reference``."""

    def load():
        import jax  # noqa: F401 - first use of the chip in this process

        notes, pid = spec["notes_dir"], os.getpid()
        t0 = time.time()
        clock = in_worker.CompileClock(
            os.path.join(notes, f"compile-{pid}.json"))
        c, chk, fault = spec["config"], spec["check"], spec.get("fault")
        family = common.module("families", c["family"])
        reference = common.module("reference", c["family"])
        if fault:
            plant(fault)
        params = family.make_params(c, spec["seed"], c["dtype"])
        jax.block_until_ready(params)
        t1 = time.time()
        pinned = pinned_check(c, params, family, reference, chk["prompts"],
                              chk["pad_to"], fault)
        common.write_json(os.path.join(notes, f"replica-{pid}.json"), {
            **in_worker.devices_note(), "weights_s": t1 - t0,
            "reference_s": time.time() - t1,
            "weight_bytes": sum(x.nbytes for x in jax.tree.leaves(params)),
            "pinned": pinned})
        threading.Thread(target=in_worker._side_channel, args=(spec, clock),
                         name="bench-side", daemon=True).start()
        threading.Thread(target=in_worker_latent._verify_channel,
                         args=(spec, params, family, reference),
                         name="bench-verify", daemon=True).start()
        return params, family.model_config(c)

    return load
