"""The replica's side of a serving cell whose model runs a state-space
mixer BESIDE attention in every block, state rows and pages in one layer
(``runners/serve_parallel_ssm.py``): the loader ``in_worker.make_loader``
would be, and the comparisons such a model is held to.  Three from ONE
reference pass a check sequence (``reference.forward``), after the engine
has answered the check's prompts and while it is idle:

- (a) LOGITS of the engine's own programs, ``jit_prefill*`` and then
  ``jit_decode_step*`` THROUGH its pages and state rows (``engine._run``
  over the engine's own pools and rows, at the cell's slots and widths: the
  executables the window times), the check's sequences side by side in
  slots 0, 1, ..., against the reference's full forward over the same
  tokens;
- (b) the rows those programs LEFT: every layer's float32 state (pooled,
  and layer 0's a head at a time) and the convolution's last inputs in the
  slots' rows, K and V in the pages, against the reference's ``S_t``, its
  convolution inputs and its k, v;
- (c) the engine's greedy tokens, as it generated them through the
  scheduler, bursts and ``decode_step_greedy``, each held against the
  reference ON THE ENGINE'S OWN HISTORY; and the share of them that the
  replay of (a) also puts first (the two paths run the same layers).

And one AFTER THE LOAD, because the three above see six sequences in six of
the engine's slots and nothing of the window (``window_check``):

- (d) a sample of the sequences the engine FINISHED INSIDE THE WINDOW, with
  most of its slots live, slots taken again as they came free and prompts
  admitted between decode bursts: their greedy tokens held against the
  reference on the engine's own history, as (c).  The client keeps no
  token, so the replica notes what its engine finishes (``note_finished``:
  one list entry a finished request, four or five a second, from the check's
  end on; it holds the request's own lists, no copy).

A CONTROL (``runners/serve_parallel_ssm.py`` ``control``, never a run)
hands the loader a ``fault``, planted HERE in the replica's process before
anything compiles (``families/falcon_h1.py`` ``plant``), so that the
engine's programs and with them (a), (b) and (c) all run it.
"""

from __future__ import annotations

import os
import threading
import time

from benchmarks import common, in_worker
from benchmarks.in_worker_recurrent import _engine  # the replica's engine
from benchmarks.in_worker_windowed import _wait_for


def replay(engine, prompts: list, outputs: list, steps: int):
    """The check's sequences through the engine's OWN programs and buffers,
    sequence i in slot i: a prefill each, then ``steps - 1`` decode steps
    of all of them side by side, each fed the token the engine generated.
    Returns (logits [n][steps, vocab] on the host, the pages each holds);
    the slots' rows and the pages are left as the programs wrote them."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm import model as lm

    ps, n = engine.cfg.page_size, len(prompts)
    if n > engine.cfg.max_slots or any(s is not None for s in engine._slots):
        raise RuntimeError("the replay wants an idle engine and a slot a "
                           "sequence")
    pages, got = [], [[] for _ in prompts]
    for i, p in enumerate(prompts):
        held = engine.allocator.allocate(-(-(len(p) + steps) // ps))
        pages.append(held)
        bucket = engine.cfg.bucket_for(len(p))
        tokens = np.zeros(bucket, np.int32)
        tokens[:len(p)] = p
        # the page of every padded position, the null page past its pages
        rows = np.asarray(held + [0], np.int32)[
            np.minimum(np.arange(bucket) // ps, len(held))]
        logits, _ = engine._run(
            lm.prefill, jnp.asarray(tokens), jnp.asarray(rows),
            jnp.int32(len(p)), jnp.asarray(np.arange(bucket, dtype=np.int32)
                                           % ps), slot=i)
        got[i].append(np.asarray(logits))
    B = engine.cfg.max_slots
    tables = np.zeros((B, engine.max_pages_per_seq), np.int32)
    for i, held in enumerate(pages):
        tables[i, :len(held)] = held
    tables = jnp.asarray(tables)
    active = jnp.asarray(np.arange(B) < n)
    for t in range(steps - 1):
        tokens, positions = np.zeros(B, np.int32), np.zeros(B, np.int32)
        for i, (p, o) in enumerate(zip(prompts, outputs)):
            tokens[i], positions[i] = o[t], len(p) + t
        logits, _ = engine._run(lm.decode_step, jnp.asarray(tokens), tables,
                                jnp.asarray(positions), active)
        logits = np.asarray(logits[:n])
        for i in range(n):
            got[i].append(logits[i])
    return [np.stack(g) for g in got], pages


def served_check(c: dict, params, engine, family, reference, ask: dict):
    """(a), (b) and (c) over ``ask``: prompts, outputs (``steps`` greedy
    tokens each, the engine's), steps, pad_to, margin."""
    import jax.numpy as jnp
    import numpy as np

    prompts, steps = ask["prompts"], ask["steps"]
    outputs = [list(o) for o in ask["outputs"]]
    if any(len(o) < steps for o in outputs):
        raise RuntimeError("the engine answered fewer tokens than asked")
    got, pages = replay(engine, prompts, outputs, steps)
    acc = {k: [0.0, 0.0] for k in ("logits", "state", "tail", "kv",
                                   "first_kv")}
    by_head = []  # a sequence's [H]: layer 0's state, each head's relative

    def add(name, have, want):
        have, want = (np.asarray(x, np.float32) for x in (have, want))
        acc[name][0] += float(np.sum((have - want) ** 2))
        acc[name][1] += float(np.sum(want ** 2))

    gaps, same, worst, seeded, ref_s = [], 0, 0.0, [], 0.0
    try:
        for i, (p, o) in enumerate(zip(prompts, outputs)):
            seq = list(p) + o[:steps - 1]  # what the slot's rows have taken
            at = len(p) - 1 + np.arange(steps)
            t = time.time()
            ref = reference.forward(c, params, seq, at, len(seq),
                                    ask["pad_to"])
            want = np.asarray(ref["logits"])
            ref_s += time.time() - t
            add("logits", got[i], want)
            worst = max(worst, float(np.abs(got[i] - want).max()))
            theirs = want[np.arange(steps), np.asarray(o[:steps])]
            gaps.append([float(g) for g in want.max(axis=-1) - theirs])
            same += int(np.sum(got[i].argmax(axis=-1) == np.asarray(
                o[:steps])))
            rows = family.engine_state(engine, i)
            add("state", rows["S"], ref["S"])
            have, want = (np.asarray(x["S"][0], np.float32)
                          for x in (rows, ref))
            by_head.append(np.sum((have - want) ** 2, axis=(1, 2))
                           / np.sum(want ** 2, axis=(1, 2)))
            add("tail", rows["conv"], ref["conv"])
            held = family.engine_rows(engine, pages[i])
            for name in ("k", "v"):
                add("kv", held[name][:, :len(seq)], ref[name][:, :len(seq)])
                add("first_kv", held[name][0, :len(seq)],
                    ref[name][0, :len(seq)])
            seeded.append({k: [float(x) for x in np.asarray(ref[k])]
                           for k in ("mixer_rms", "attention_rms",
                                     "score_std")})
    finally:
        for held in pages:
            engine.allocator.free(held)
    flat = [g for row in gaps for g in row]
    count = sum(g.size for g in got)
    rel = {k: (e / r) ** 0.5 if r else None for k, (e, r) in acc.items()}
    # Layer 0's state (its mixer reads the embedding's rows through one
    # bf16 product, the same on both sides), each head's held against ITS
    # OWN size, rms over heads and sequences.  A pooled reading weighs a
    # head by its state's size, and the largest states are the heads that
    # write most (a large dt), which forget soonest; a state kept in fewer
    # bits shows in the heads that REMEMBER longest, whose states are small.
    rel["first_state_by_head"] = float(np.mean(by_head) ** 0.5)
    return {"logit_rms_error": (acc["logits"][0] / count) ** 0.5,
            "logit_max_error": worst,
            "logit_rms": (acc["logits"][1] / count) ** 0.5,
            "rows": {k: v for k, v in rel.items() if k != "logits"},
            "gaps": gaps,
            "within_margin_share": sum(g < ask["margin"] for g in flat)
            / len(flat),
            "furthest_under_best": max(flat),
            "replay_puts_first_share": same / len(flat),
            "positions": len(flat),
            "state_dtype": str(engine.state["S"].dtype),
            "seeded_weights": seeded,
            "reference_s": ref_s,  # the reference's passes, compile and all
            "all_free_after":
                engine.allocator.num_free() == engine.allocator.num_pages - 1}


def note_finished(engine) -> list:
    """From now on, every sequence the engine finishes: (wall time, the
    request's prompt, the tokens generated in its slot), in order.  Wraps
    ``LLMEngine._release_slot`` (a private name: a program that renames it
    fails here by name, not silently)."""
    log, release = [], engine._release_slot

    def noting(i, s):
        log.append((time.time(), s.request.prompt_tokens, s.generated))
        return release(i, s)

    engine._release_slot = noting
    return log


def window_check(c: dict, params, reference, ask: dict, log: list) -> dict:
    """(d) over ``ask``: t0_wall and seconds (the window), requests (how
    many to judge), positions (of each, spread over its answer, the last
    among them), pad_to, margin.  The sample is the finished sequences at
    even spacing in the order they finished: early and late in the window,
    short and long, whatever slots they had."""
    import numpy as np

    t0 = ask["t0_wall"]
    inside = [(p, o) for t, p, o in log
              if t0 <= t < t0 + ask["seconds"] and len(o) > 1]
    if not inside:
        raise RuntimeError("the engine finished no sequence in the window")
    pick = np.unique(np.linspace(0, len(inside) - 1, min(
        ask["requests"], len(inside))).round().astype(int))
    gaps, lengths = [], []
    for p, o in (inside[i] for i in pick):
        seq = list(p) + list(o[:-1])
        idx = np.unique(np.linspace(0, len(o) - 1, min(
            ask["positions"], len(o))).round().astype(int))
        want = np.asarray(reference.forward(
            c, params, seq, len(p) - 1 + idx, len(seq),
            ask["pad_to"])["logits"])
        theirs = want[np.arange(len(idx)), np.asarray(o)[idx]]
        gaps += [float(g) for g in want.max(axis=-1) - theirs]
        lengths.append([len(p), len(o)])
    return {"window_within_margin_share":
            sum(g < ask["margin"] for g in gaps) / len(gaps),
            "window_furthest_under_best": max(gaps),
            "window_positions": len(gaps), "window_judged": lengths,
            "window_finished": len(inside)}


def make_loader(spec: dict):
    """``spec`` as ``in_worker.make_loader``'s, and ``fault`` (a control's,
    never a run's)."""

    def load():
        import jax  # noqa: F401 - first use of the chip in this process

        notes, pid = spec["notes_dir"], os.getpid()
        t0 = time.time()
        clock = in_worker.CompileClock(
            os.path.join(notes, f"compile-{pid}.json"))
        c, fault = spec["config"], spec.get("fault")
        family = common.module("families", c["family"])
        reference = common.module("reference", c["family"])
        overrides = family.plant(fault)[0] if fault else {}
        params = family.make_params(c, spec["seed"], c["dtype"])
        jax.block_until_ready(params)
        common.write_json(os.path.join(notes, f"replica-{pid}.json"), {
            **in_worker.devices_note(), "weights_s": time.time() - t0,
            "reference_s": 0.0,  # the reference runs in ``check_correct``
            "weight_bytes": sum(x.nbytes for x in jax.tree.leaves(params))})
        threading.Thread(target=in_worker._side_channel, args=(spec, clock),
                         name="bench-side", daemon=True).start()
        threading.Thread(target=_verify_channel,
                         args=(spec, params, family, reference),
                         name="bench-verify", daemon=True).start()
        return params, family.model_config(c, **overrides)

    return load


def _answer(notes: str, name: str, work) -> dict:
    """``work()``'s result, or its failure for the driver to report, with
    its seconds, into ``<name>-<pid>.json``."""
    t = time.time()
    try:
        out = work()
    except Exception as e:  # noqa: BLE001 - the driver reports it
        import traceback

        traceback.print_exc()
        out = {"error": f"{type(e).__name__}: {e}"}
    out[f"{name.replace('-', '_')}_s"] = time.time() - t
    common.write_json(os.path.join(notes, f"{name}-{os.getpid()}.json"), out)
    return out


def _verify_channel(spec: dict, params, family, reference):
    """Serves the driver's one ``cmd-verify.json`` ({prompts, outputs,
    steps, pad_to, margin}: what the engine answered; the engine is idle
    meanwhile, its answers are in and the load has not begun), then notes
    what the engine finishes, and serves the one ``cmd-verify-window.json``
    that follows the load and its drain."""
    notes, c = spec["notes_dir"], spec["config"]
    ask = _wait_for(notes, "cmd-verify.json")
    if ask is None:
        return
    engine = _engine()
    _answer(notes, "verify", lambda: served_check(
        c, params, engine, family, reference, ask))
    log = note_finished(engine)
    ask = _wait_for(notes, "cmd-verify-window.json")
    if ask is not None:
        _answer(notes, "verify-window", lambda: window_check(
            c, params, reference, ask, log))
