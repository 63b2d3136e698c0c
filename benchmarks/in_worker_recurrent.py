"""The loader a replica of a model with recurrent layers runs: everything
is ``in_worker.make_loader``'s (seeded weights, the reference's greedy
candidates, the note, the side channel) and two more comparisons that decide
``correct``:

- before the engine exists, the program's recurrence, both forms, against
  the reference's token-by-token scan on the SAME float32 inputs
  (``state_check``; ``families/<family>.recurrence_outputs`` says why they
  are pinned);
- after the ENGINE has answered the check's prompts, the rows ITS programs
  left in ``engine.state`` (``jit_prefill*`` then ``jit_decode_step*`` at the
  cell's layers and slots, many of them live) against the reference's state
  of the same tokens (``rows_check``, asked for through ``cmd-rows.json``).

The pinned inputs are drawn from the seed with the statistics the seeded
model gives its linear layers (unit keys, queries scaled by d_k^-0.5, values
of unit variance, a decay's rate log-uniform a head and modulated by a
softplus, beta over the whole of (0, 2)): a prompt of 300 tokens (more than
four chunks of 64, so a state is carried from chunk to chunk and then into
the decode steps) and 64 decode steps, at the published head counts and
widths.
"""

from __future__ import annotations

import os
import threading
import time

from benchmarks import common, in_worker

PROMPT, STEPS = 300, 64
_BASE_LOADER = in_worker.make_loader  # the runner swaps that name for ours


def state_check(c: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    family = common.module("families", c["family"])
    reference = common.module("reference", c["family"])
    H, dk, dv = (c["linear_num_value_heads"], c["linear_key_head_dim"],
                 c["linear_value_head_dim"])
    T = PROMPT + STEPS
    ks = jax.random.split(jax.random.key(seed, impl="rbg"), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (T, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (T, H, dk)))
    v = jax.random.normal(ks[2], (T, H, dv))
    rate = jnp.exp(jax.random.uniform(ks[3], (H,), minval=jnp.log(1e-3),
                                      maxval=jnp.log(0.105)))
    g = -rate * jax.nn.softplus(2.0 * jax.random.normal(ks[4], (T, H)) + 0.54)
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[5], (T, H)))
    t0 = time.time()
    with jax.default_matmul_precision("highest"):
        want = jax.jit(reference.delta_rule)(q, k, v, g, beta)
    got = jax.jit(lambda *a: family.recurrence_outputs(c, *a, PROMPT))(
        q, k, v, g, beta)
    scale = float(jnp.sqrt(jnp.mean(want * want)))

    def rel(lo, hi):
        d = got[lo:hi] - want[lo:hi]
        return float(jnp.sqrt(jnp.mean(d * d))) / scale

    return {"prefill_rel_rms": rel(0, PROMPT), "decode_rel_rms": rel(PROMPT, T),
            "output_rms": scale, "prompt": PROMPT, "steps": STEPS,
            "seconds": time.time() - t0}


def rows_check(c: dict, first, engine, ask: dict) -> dict:
    """``engine.state`` against the reference, the FIRST linear layer, every
    slot.  ``ask``: sequences (each a check prompt and the tokens the engine
    continued it with, more of them than ``steps``), prompt_lens, steps,
    copies: every sequence was just served ``copies`` times at once and
    ``steps`` tokens long, so as many slots hold its state, each after the
    prompt and ``steps - 1`` of its tokens or up to ``DECODE_OVERSHOOT``
    more (the engine chains decode steps and lets a slot go afterwards).
    Why the first layer: its mixer reads the embedding's rows, the same on
    both sides, so the served path's bf16 products are one layer deep there
    and a state kept in fewer bits shows beside them; by the last layer
    they have grown to several per cent.  Relative rms of a slot's row
    against a sequence's state, the least over the lengths it may have; a
    sequence's ``copies`` best slots are its own."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    family = common.module("families", c["family"])
    reference = common.module("reference", c["family"])
    steps, copies = ask["steps"], ask["copies"]
    over = np.arange(steps - 1, steps + family.DECODE_OVERSHOOT)
    width = max(len(s) for s in ask["sequences"])
    rows = family.engine_states(engine)

    @jax.jit
    def off(first, rows, tokens, lengths):  # (the weights an ARGUMENT: a
        # closure would bake the embedding into the program as a constant)
        want = reference.first_layer_states(c, first, tokens, lengths)
        scale = jnp.sqrt(jnp.mean(want * want, axis=(1, 2, 3)))
        return jax.lax.map(lambda w: jnp.sqrt(jnp.mean(
            (rows - w) ** 2, axis=(1, 2, 3))), want) / scale[:, None]

    found, taken = [], set()
    for seq, n in zip(ask["sequences"], ask["prompt_lens"]):
        if n + int(over[-1]) > len(seq):
            raise ValueError("a sequence is shorter than its slot may hold")
        tokens = np.zeros(width, np.int32)
        tokens[:len(seq)] = seq
        rel = np.asarray(off(first, rows, jnp.asarray(tokens),
                             jnp.asarray(n + over)))  # [lengths, slots]
        best = rel.min(axis=0)
        order = [int(i) for i in np.argsort(best) if int(i) not in taken]
        taken.update(order[:copies])
        found.append({
            "slots": order[:copies],
            "tokens_past_the_prompt": [int(over[rel[:, i].argmin()])
                                       for i in order[:copies]],
            "rel_rms": [float(best[i]) for i in order[:copies]],
            "next_slot_rel_rms": float(best[order[copies]])})
    return {"rows": found, "layer": 0, "slots": int(rows.shape[0]),
            "worst_rel_rms": max(max(f["rel_rms"]) for f in found),
            "nearest_other_rel_rms": min(f["next_slot_rel_rms"]
                                         for f in found)}


def _engine():
    """The replica's engine (the loader runs before it exists and is handed
    nothing of it)."""
    import gc

    from ray_tpu.llm.engine import LLMEngine

    (engine,) = [o for o in gc.get_objects() if isinstance(o, LLMEngine)]
    return engine


def _rows_channel(spec: dict, first):
    """Serves the driver's one ``cmd-rows.json``; the engine is idle
    meanwhile (its answers are in, the load has not begun)."""
    notes, pid = spec["notes_dir"], os.getpid()
    cmd = os.path.join(notes, "cmd-rows.json")
    while not os.path.exists(cmd):
        time.sleep(0.05)
        if os.path.exists(os.path.join(notes, "cmd-finish")):
            return
    ask, t = common.load_json(cmd), time.time()
    try:
        out = rows_check(spec["config"], first, _engine(), ask)
    except Exception as e:  # noqa: BLE001 - the driver reports it
        out = {"error": f"{type(e).__name__}: {e}"}
    out["seconds"] = time.time() - t
    common.write_json(os.path.join(notes, f"rows-{pid}.json"), out)


def make_loader(spec: dict):
    base = _BASE_LOADER(spec)

    def load():
        import jax

        params, model_cfg = base()
        c = spec["config"]
        common.write_json(
            os.path.join(spec["notes_dir"], f"state-{os.getpid()}.json"),
            state_check(c, spec["seed"]))
        # what ``reference.first_layer_states`` reads, kept for the rows'
        # check: the embedding (the engine's own array) and one layer's
        # mixer weights as the loader made them (89 MB at published widths)
        first = {"embed": params["embed"], "layers": {"lin": {
            "mix": jax.tree.map(lambda w: w[:1],
                                params["layers"]["lin"]["mix"])}}}
        threading.Thread(target=_rows_channel, args=(spec, first),
                         name="bench-rows", daemon=True).start()
        family = common.module("families", c["family"])
        return family.serving_params(params), model_cfg

    return load
