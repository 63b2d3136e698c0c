"""The replica's side of a serving cell whose layers are ONE thing each, a
state-space mixer OR attention OR a routed feed-forward whose experts (in a
latent, a chip's share of them held) sit beside a shared expert
(``runners/serve_latent_moe_ssm.py``): ``in_worker_parallel_ssm``'s verify
channel as it stands (its (a) logits and (b) rows of the engine's own
programs through its pages and PACKED state rows, (c) the engine's tokens on
its own history, (d) the sequences finished inside the window), behind a
loader that first, BEFORE the engine exists, holds the routed layer apart
(``pinned_check``), because a bf16 stream swaps the last of 22 chosen
experts in most rows of most layers (the 22nd and 23rd of 512 sigmoid scores
lie ~0.003 apart), which is no fault and buries what is one:

- (p) LOGITS of the program's own layers (``nemotron_h.trunk``: its
  convolution, chunked scan, attention without positions, ``dispatch_share``
  and grouped kernel in the two-matrix form, both latent projections, the
  shared expert, its head) with the REFERENCE's routing handed to them,
  over the last ``PINNED_ROWS`` positions of every check prompt and the
  share's slice of the vocabulary;
- (h) the program's held experts' part ``r W_lout`` of every routed layer ON
  THE REFERENCE'S ROWS under the reference's routing, against the
  reference's (a share's part is a fraction of the stream: held on its
  own);
- (r) the program's ROUTER on the reference's rows: the share of rows whose
  chosen SET is the reference's, and the weights' error where it is.

A CONTROL (``runners/serve_latent_moe_ssm.py`` ``control``, never a run)
hands the loader a ``fault``, planted HERE in the replica's process before
anything compiles (``families/nemotron_h.py`` ``plant``), so that (p), (h),
(r), the engine's programs and with them (a) to (d) all run it.
"""

from __future__ import annotations

import os
import threading
import time

from benchmarks import common, in_worker, in_worker_parallel_ssm

PINNED_ROWS = 64  # the last 64 positions of each prompt


def pinned_check(c: dict, params, family, reference, prompts: list,
                 pad_to: int) -> dict:
    """(p), (h) and (r) over the last ``PINNED_ROWS`` positions of every
    check prompt, and what the seeded weights gave there (each kind's
    ``Mix(u)`` beside the stream it enters a layer, the held experts' part,
    a head's scores, the logits)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg, nh = family.model_config(c), family.model_module()
    k = c["num_experts_per_tok"]

    @jax.jit
    def program(params, tokens, at, weights, chosen):
        x = nh.trunk(params, tokens, cfg, (weights, chosen))
        return nh.head(params, x[at], cfg)

    held_of = jax.jit(lambda p, u, w, e: family.routed_part(
        c, p, u, (w, e))[0])
    routed = jax.jit(lambda p, u: family.routed_part(c, p, u)[1:])
    sq = ref_sq = held_err = held_sq = w_err = w_sq = 0.0
    worst, same, rows, read = 0.0, 0, 0, []
    for p in prompts:
        at = np.arange(len(p) - PINNED_ROWS, len(p))
        ref = reference.forward(c, params, p, at, len(p), pad_to)
        tokens = np.zeros(pad_to, np.int32)
        tokens[:len(p)] = p
        want = ref["logits"]
        err = program(params, jnp.asarray(tokens), jnp.asarray(at),
                      ref["weights"], ref["chosen"]) - want
        sq += float(jnp.sum(err * err))
        ref_sq += float(jnp.sum(want * want))
        worst = max(worst, float(jnp.max(jnp.abs(err))))
        w_at, e_at = ref["weights"][:, at], ref["chosen"][:, at]
        err = held_of(params, ref["u"], w_at, e_at) - ref["held"]
        held_err += float(jnp.sum(err * err))
        held_sq += float(jnp.sum(ref["held"] ** 2))
        # the router: sets compared as sets, weights column by column
        w_got, e_got = routed(params, ref["u"])
        agree = jnp.all(jnp.sort(e_got, -1) == jnp.sort(e_at, -1), -1)
        by_col = lambda w, e: jnp.zeros(  # noqa: E731
            (*e.shape[:-1], family.router_columns(c)), jnp.float32).at[
                tuple(jnp.indices(e.shape)[:-1]) + (e,)].set(w)
        d = jnp.where(agree[..., None],
                      by_col(w_got, e_got) - by_col(w_at, e_at), 0.0)
        w_err += float(jnp.sum(d * d))
        w_sq += float(jnp.sum(jnp.where(agree[..., None], w_at, 0.0) ** 2))
        same += int(jnp.sum(agree))
        rows += int(agree.size)
        read.append({name: [float(x) for x in np.asarray(ref[name])]
                     for name in ("mixer_rms", "mixer_stream_rms",
                                  "attention_rms", "attention_stream_rms",
                                  "routed_rms", "routed_stream_rms",
                                  "held_rms", "score_std")})
    count = len(prompts) * PINNED_ROWS * c["vocab_size"]
    return {"logit_rms_error": (sq / count) ** 0.5, "logit_max_error": worst,
            "logit_rms": (ref_sq / count) ** 0.5,
            "held_rel_rms_error": (held_err / held_sq) ** 0.5,
            "router_same_set_share": same / rows,
            "router_weight_rel_rms_error": (w_err / w_sq) ** 0.5
            if w_sq else None,
            "positions": len(prompts) * PINNED_ROWS, "picks": k,
            "seeded_weights": read}


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
        overrides = family.plant(fault)[0] if fault else {}
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
        threading.Thread(target=in_worker_parallel_ssm._verify_channel,
                         args=(spec, params, family, reference),
                         name="bench-verify", daemon=True).start()
        return params, family.model_config(c, **overrides)

    return load
