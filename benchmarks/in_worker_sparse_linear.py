"""The replica's side of a serving cell whose model has BLOCK-SPARSE
attention layers and fixed-decay LINEAR-attention layers, prompts computed
in chunks that carry the state (``runners/serve_sparse_linear.py``): the
loader ``in_worker.make_loader`` would be, with the comparisons such a
model can be held to.

- (a) before the engine exists, LOGITS of the program's layers with the
  reference's choice of blocks handed to them (``families/minicpm_sala.py``
  ``pinned_logits`` says why it is pinned; the sparse layers attend through
  the prefills' own ``block_sparse.attend_under``) against the reference's
  own, at positions under ``dense_len``, just past it and deep past it;
- (b) in the same pass the program's OWN choice of blocks against the
  reference's at every query past ``dense_len``: the share of selected
  blocks the two have in common;
- (e) the program's recurrence as the engine runs it (``chunked`` in calls
  that hand the state on, then ``decode_update`` a token at a time) against
  the reference's closed form on the SAME float32 inputs;
- (c) after the ENGINE has answered the check's prompts (each computed in
  3-6 chunks, then greedy steps through the kernel's lists, half of them
  crossing ``dense_len``), the rows ITS programs left: K and V pages, the
  rows of pooled keys, and the float32 state rows, against the reference's
  of the same tokens, after chunks (a sequence that ended on its prompt)
  and after steps;
- (f) over the same pools, what the decode step's SPARSE ATTENTION makes
  of the reference's query at each check sequence's last cached position:
  the step's own ``lists_from`` and the paged kernel with ``heads_apart``
  over the engine's K/V pages under the reference's choice, against the
  reference's attention output there; and the pages the step's own
  ``page_lists`` lists there against the pages of the reference's choice;
- (d) ITS greedy tokens held against the reference on its own history, one
  of the sequences PREEMPTED after a few steps and recomputed through the
  same chunks.

How the rows are found.  ``arm`` wraps three private methods of
``ray_tpu.llm.engine`` (a program that renames them fails here by name):
``_release_slot`` and ``_seat`` note which slot and pages a sequence held
as it ended; ``_accept_burst`` notes, for a slot that ends inside a burst,
the tokens the burst's later steps fed (a burst runs its eight steps
whatever ends inside it, so the slot's STATE has taken those tokens too:
the reference's state is computed over the same); ``_ensure_capacity``
preempts ONE sequence once.  This model is served without a prefix index,
so a finished sequence's pages go back to the allocator's free list and are
not written again before the list comes round (first in, first out, and
the check uses a fraction of the pool); a slot's state rows are copied as
the sequence ends (the next one admitted to the slot begins them anew).
All wrappers come off (``disarm``) before the load.
"""

from __future__ import annotations

import os
import threading
import time

from benchmarks import common, in_worker
from benchmarks.in_worker_recurrent import _engine  # the replica's engine
from benchmarks.in_worker_windowed import _wait_for

# (a), (b): two sequences, three groups of positions each
PINNED = {"sequences": 2, "length": 9600, "pad_to": 9728, "rows": 16,
          "at": {"under_dense_len": 3000, "past_dense_len": 8300,
                 "deep_past": 9560}}
# (e): a prompt in chunks of 2,048 and decode steps, at the published heads
RECURRENCE = {"prompt": 4500, "chunk": 2048, "steps": 64}
PREEMPT_AFTER = 8  # tokens the preempted sequence has generated by then
_WRAPPED = ("_release_slot", "_seat", "_accept_burst", "_ensure_capacity")


def pinned_check(c: dict, params, family, reference, seed: int,
                 overrides: dict) -> dict:
    """(a) and (b): logit error under the reference's choice, rms over the
    positions of each group and the whole vocabulary; and the share of
    selected blocks the program's own choice has in common with it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    r = np.random.default_rng([int(seed), 77])
    n, s, k = PINNED["sequences"], PINNED["pad_to"], PINNED["rows"]
    at = np.concatenate([p + np.arange(k) for p in PINNED["at"].values()])
    ref = jax.jit(lambda p, t, rows: reference.logits_and_selection(
        c, p, t, rows))
    got = jax.jit(lambda p, t, rows, sel: family.pinned_logits(
        c, p, t, rows, sel, **overrides))
    sq = np.zeros(len(PINNED["at"]))
    ref_sq, common_blocks, chosen = 0.0, 0, 0
    dense = c["sparse_config"]["dense_len"]
    for _ in range(n):
        tokens = np.zeros(s, np.int32)
        tokens[:PINNED["length"]] = r.integers(3, c["vocab_size"],
                                               PINNED["length"])
        t, rows = jnp.asarray(tokens), jnp.asarray(at, jnp.int32)
        want, sel = ref(params, t, rows)
        have, own = got(params, t, rows, sel)
        err = np.asarray(have - want)
        sq += (err.reshape(len(sq), k, -1) ** 2).sum(axis=(1, 2))
        ref_sq += float(jnp.sum(want * want))
        past = slice(dense, PINNED["length"])  # queries under the rule
        common_blocks += int(jnp.sum(sel[:, past] & own[:, past]))
        chosen += int(jnp.sum(sel[:, past]))
    count = n * k * c["vocab_size"]
    by_group = dict(zip(PINNED["at"], (sq / count) ** 0.5))
    return {"logit_rms_error": {g: float(v) for g, v in by_group.items()},
            "logit_rms": (ref_sq / (count * len(sq))) ** 0.5,
            "selection_in_common": common_blocks / max(1, chosen),
            "blocks_compared": chosen, "positions": n * k * len(sq)}


def recurrence_check(c: dict, family, reference, seed: int) -> dict:
    """(e): relative rms error of the program's outputs and final state on
    pinned float32 inputs with the statistics the model gives them (q and k
    of unit rms a head, v of unit variance)."""
    import jax
    import jax.numpy as jnp

    H, d = c["lightning_nh"], c["lightning_head_dim"]
    T = RECURRENCE["prompt"] + RECURRENCE["steps"]
    q, k, v = (jax.random.normal(key, (T, H, d), jnp.float32)
               for key in jax.random.split(jax.random.key(seed, impl="rbg"), 3))
    with jax.default_matmul_precision("highest"):
        want_o = jax.jit(lambda *a: reference.linear_attention(c, *a))(
            q, k, v) * d ** 0.5  # (the reference scales o, the layer q S)
        want_S = jax.jit(lambda k, v: reference.state_at(c, k, v, T))(k, v)
    got_o, got_S = jax.jit(lambda *a: family.recurrence_outputs(
        c, *a, RECURRENCE["prompt"], RECURRENCE["chunk"]))(q, k, v)

    def rel(got, want):
        return float(jnp.sqrt(jnp.sum((got - want) ** 2)
                              / jnp.sum(want ** 2)))

    return {"outputs_rel_rms": rel(got_o, want_o),
            "state_rel_rms": rel(got_S, want_S), "tokens": T}


def arm(engine, preempt_prompt: list, engine_state) -> list:
    """Note what every sequence holds as it ends (``engine_state(engine,
    slot)``: its state rows), and preempt the sequence whose prompt is
    ``preempt_prompt`` once it has generated ``PREEMPT_AFTER`` tokens.
    Returns the list the notes go to."""
    notes = []
    release, seat = engine._release_slot, engine._seat
    accept, ensure = engine._accept_burst, engine._ensure_capacity
    fed = {}  # slot -> the tokens its state has taken, as a burst ends

    def note(i, s, tokens, state_tokens):
        # (the slot's state rows are copied HERE, on the scheduler's
        # thread between two programs: the next sequence admitted to the
        # slot begins them anew)
        notes.append({"tokens": tokens, "held": s.num_tokens, "slot": i,
                      "pages": list(s.pages), "state_tokens": state_tokens,
                      "S": engine_state(engine, i),
                      "preempts": s.request.preempts})

    def releasing(i, s):
        seq = list(s.request.prompt_tokens + s.generated)
        note(i, s, seq, fed.pop(i, None))
        return release(i, s)

    def seating(i, slot, last):
        seat(i, slot, last)
        if engine._slots[i] is not slot:  # it ended on its prompt
            note(i, slot, list(slot.request.prompt_tokens) + [last],
                 list(slot.request.prompt_tokens))

    def accepting(active_slots, rows):
        for i, s in active_slots:
            # step j fed the token step j - 1 made (the first the slot's
            # last token), whatever ended meanwhile
            seq = s.request.prompt_tokens + s.generated
            fed[i] = list(seq[:s.num_tokens + 1]) + [
                int(t) for t in rows[:-1, i]]
        return accept(active_slots, rows)

    def preempting(steps):
        for i, s in enumerate(engine._slots):
            if (s is not None and s.prefill_at is None
                    and not s.request.preempts
                    and len(s.generated) >= PREEMPT_AFTER
                    and s.request.prompt_tokens == preempt_prompt):
                engine._preempt(i, s)
        return ensure(steps)

    engine._release_slot, engine._seat = releasing, seating
    engine._accept_burst, engine._ensure_capacity = accepting, preempting
    return notes


def disarm(engine) -> None:
    """Take ``arm``'s wrappers off: the engine's own methods again."""
    for name in _WRAPPED:
        engine.__dict__.pop(name, None)


def verify_and_rows(c: dict, params, engine, reference, family, ask: dict,
                    notes: list, steps: int, pad_to: int) -> dict:
    """(d) and (c) from ONE reference pass a sequence.  ``gaps``:
    ``reference.verify``'s, the engine's tokens on their own history.
    ``rows``: relative rms of what the engine's pools and state rows hold
    against that pass's: ``kv_prefill`` / ``kv_decode`` (K and V, the
    prompt's positions and the generated tokens'), ``first_kv`` (layer 0's
    alone: one product deep), ``pooled`` (the complete rows of pooled
    keys), ``state_chunks`` (the state rows of the sequences that ended on
    their prompt: chunks alone) and ``state_steps`` (of those that decoded,
    over the tokens their state had taken).  ``attend_rel_rms``: (f), the
    served sparse attention at each sequence's last cached position (under
    ``dense_len`` and past it apart); ``engine_selection_in_common``: of
    the pages of the reference's choice there, past ``dense_len``, the
    share the step's own lists hold."""
    import jax.numpy as jnp
    import numpy as np

    ps = engine.cfg.page_size
    w = c["sparse_config"]["kernel_size"]
    acc = {k: [0.0, 0.0] for k in ("kv_prefill", "kv_decode", "first_kv",
                                   "pooled", "state_chunks", "state_steps")}
    dense, same, chosen = c["sparse_config"]["dense_len"], 0, 0
    attend = {"under_dense_len": [0.0, 0.0], "past_dense_len": [0.0, 0.0]}

    def add(name, got, want):
        acc[name][0] += float(jnp.sum((got.astype(jnp.float32) - want) ** 2))
        acc[name][1] += float(jnp.sum(want ** 2))

    gaps, found = [], []
    for p, o in zip(ask["prompts"], ask["outputs"]):
        seq = p + o
        note = next((n for n in reversed(notes)
                     if n["tokens"][:len(seq)] == seq[:len(n["tokens"])]
                     and len(n["tokens"]) >= len(seq) - 1), None)
        if note is None or note["state_tokens"] is None:
            raise RuntimeError("a check sequence was never released")
        # the reference over what the STATE took: the sequence and, past
        # it, the tokens the last burst's later steps fed
        fed = note["state_tokens"]
        held, n = note["held"], min(len(p), note["held"])
        g, want = reference.verify(c, params, p, o, steps, pad_to, fed=fed,
                                   q_at=held - 1)
        gaps.append(g)
        got = family.engine_rows(engine, note["pages"])
        for name in ("k", "v"):
            add("kv_prefill", got[name][:, :n], want[name][:, :n])
            if held > n:
                add("kv_decode", got[name][:, n:held], want[name][:, n:held])
            add("first_kv", got[name][0, :held], want[name][0, :held])
        rows = max(0, (held - w) // ps + 1)  # the complete rows
        add("pooled", got["pooled"][:, :rows], want["pooled"][:, :rows])
        add("state_steps" if len(o) > 1 else "state_chunks", note["S"],
            want["S"])
        # (f) the decode step's sparse attention over the engine's pools
        # at the sequence's last cached position, under the reference's
        # choice there, against the reference's
        ref_sel = np.asarray(want["selection"][:, held - 1])
        served = family.served_attention(engine, note["pages"], want["q"],
                                         held, ref_sel)
        e = attend["past_dense_len" if held > dense else "under_dense_len"]
        e[0] += float(jnp.sum((served - want["o"]) ** 2))
        e[1] += float(jnp.sum(want["o"] ** 2))
        if held > dense:
            # the pages the ENGINE's step lists there (its own
            # configuration, the pooled rows its programs left, the
            # reference's query) against the pages of the reference's choice
            for li in range(ref_sel.shape[0]):
                own = family.engine_selection(engine, note["pages"],
                                              want["q"][li], held, li)
                ref = family.selected_pages(engine, note["pages"],
                                            ref_sel[li], held)
                same += sum(len(a & b) for a, b in zip(own, ref))
                chosen += sum(len(b) for b in ref)
        found.append({"tokens": len(seq), "held": held, "state_over": len(fed),
                      "pooled_rows": rows, "preempts": note["preempts"]})
    rows = {k: (e / r) ** 0.5 if r else None for k, (e, r) in acc.items()}
    rows["sequences"] = found
    return {"gaps": gaps, "rows": rows,
            "attend_rel_rms": {k: (e / r) ** 0.5 if r else None
                               for k, (e, r) in attend.items()},
            "state_dtype": str(engine.state["S"].dtype),
            "engine_selection_in_common": same / chosen if chosen else None,
            "preempted": int(np.sum([f["preempts"] for f in found])),
            "all_free_after":
                engine.allocator.num_free() == engine.allocator.num_pages - 1}


def make_loader(spec: dict):
    """``spec`` as ``in_worker.make_loader``'s.  The note it leaves has
    ``pinned`` and ``recurrence`` where that one has ``reference``."""

    def load():
        import jax  # noqa: F401 - first use of the chip in this process

        notes, pid = spec["notes_dir"], os.getpid()
        t0 = time.time()
        clock = in_worker.CompileClock(
            os.path.join(notes, f"compile-{pid}.json"))
        c = spec["config"]
        family = common.module("families", c["family"])
        reference = common.module("reference", c["family"])
        fault = spec.get("fault")  # a control's, never a run's
        plant = {"state_bf16": family.plant_state_bf16,
                 "list_page_shifted": family.plant_list_page_shifted,
                 "other_heads_columns": family.plant_other_heads_columns
                 }.get(fault)
        if plant:
            plant()
        params = family.make_params(c, spec["seed"], c["dtype"])
        jax.block_until_ready(params)
        t1 = time.time()
        # the configuration of a control is built off the published one
        # (``topk_48``: a selection short of it; ``depth_root_8``: the
        # residual's root over the depth that is RUN, not the published 32)
        overrides = {"topk_48": {"topk": 48},
                     "depth_root_8": {"depth_base": c["num_hidden_layers"]}
                     }.get(fault, {})
        pinned = pinned_check(c, params, family, reference, spec["seed"],
                              overrides)
        recurrence = recurrence_check(c, family, reference, spec["seed"])
        common.write_json(os.path.join(notes, f"replica-{pid}.json"), {
            **in_worker.devices_note(), "weights_s": t1 - t0,
            "reference_s": time.time() - t1,
            "weight_bytes": sum(x.nbytes for x in jax.tree.leaves(params)),
            "pinned": pinned, "recurrence": recurrence})
        threading.Thread(target=in_worker._side_channel, args=(spec, clock),
                         name="bench-side", daemon=True).start()
        threading.Thread(target=_verify_channel,
                         args=(spec, params, family, reference),
                         name="bench-verify", daemon=True).start()
        return params, family.model_config(c, **overrides)

    return load


def _verify_channel(spec: dict, params, family, reference):
    """Serves the driver's ``cmd-arm.json`` ({preempt}) and then its one
    ``cmd-verify.json`` ({prompts, outputs, steps, pad_to}), as
    ``in_worker_windowed._verify_channel`` does, for this family's rows."""
    notes, pid = spec["notes_dir"], os.getpid()
    ask = _wait_for(notes, "cmd-arm.json")
    if ask is None:
        return
    try:
        engine = _engine()
        noted = arm(engine, ask["preempt"], family.engine_state)
        common.write_json(os.path.join(notes, f"armed-{pid}.json"), {})
    except Exception as e:  # noqa: BLE001 - the driver reports it
        common.write_json(os.path.join(notes, f"armed-{pid}.json"),
                          {"error": f"{type(e).__name__}: {e}"})
        return
    try:
        ask, t = _wait_for(notes, "cmd-verify.json"), time.time()
        if ask is None:
            return
        try:
            out = verify_and_rows(spec["config"], params, engine, reference,
                                  family, ask, noted, ask["steps"],
                                  ask["pad_to"])
        except Exception as e:  # noqa: BLE001 - the driver reports it
            import traceback

            traceback.print_exc()
            out = {"error": f"{type(e).__name__}: {e}"}
        out["verify_s"] = time.time() - t
    finally:
        disarm(engine)  # the engine is idle: nothing is inside any
        del noted[:]
    out["disarmed"] = not (set(_WRAPPED) & set(vars(engine)))
    common.write_json(os.path.join(notes, f"verify-{pid}.json"), out)
