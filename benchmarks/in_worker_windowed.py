"""The replica's side of a serving cell whose model has WINDOW and full
attention layers over a page pool a kind, prompts computed in chunks, and
routed experts (``runners/serve_windowed.py``): the loader
``in_worker.make_loader`` would be, with the three comparisons such a model
can be held to.

- (a) before the engine exists, LOGITS of the program's layers with the
  reference's expert sets handed to them (``families/afmoe.py``
  ``pinned_logits`` says why they are pinned) against the reference's own,
  at positions INSIDE the first window, PAST it (where a window layer's
  mask cuts keys off that a full layer still sees) and past the third
  chunk's boundary;
- (b) after the ENGINE has answered the check's prompts (1,500-5,000 tokens,
  each computed in 2-5 chunks, then greedy steps through the kernel with
  its bound), the K and V rows ITS programs left in BOTH pools for those
  sequences against the reference's K and V of the same tokens: a row
  written to the wrong page or slot or kept in fewer bits shows in layer
  0's rows; a fault of the window layers' gather (a chunk) or of the
  kernel's bounded walk (a step) in layer 1's, which lie behind layer 0's
  attention and before any router; and that the window layers' pages
  behind the window HAD been given back when the sequence ended (the null
  entries of its table, and the allocator's count afterwards);
- (c) ITS greedy tokens held against the reference on its own history
  (``reference.verify``), one of the sequences PREEMPTED after a few steps
  and recomputed through the same chunks.

How the rows are found.  This model is served without a prefix index, so a
finished sequence's pages go back to their allocators; they are not written
again before the free lists come round (a list is first-in first-out and
the check allocates a fraction of either pool).  ``arm`` wraps the engine's
``_release_slot`` to note which pages of each kind a sequence held as it
ended, and its ``_ensure_capacity`` to preempt ONE sequence once, on the
scheduler's own thread: both are private names of ``ray_tpu.llm.engine``,
and a program that renames them fails here by name, not silently.  Both
come off again (``disarm``) when the verdict is written, before the load:
the window times the engine as it is deployed.

A CONTROL (``runners/serve_windowed.py`` ``control``, never a run) hands the
loader a ``fault``: (a) then runs ``families/afmoe.py`` ``pinned_logits``
with it, and for ``window_short`` the ENGINE too is built with a window one
page short of the configuration's, which (b) and (c) hold it to.
"""

from __future__ import annotations

import os
import threading
import time

from benchmarks import common, in_worker
from benchmarks.in_worker_recurrent import _engine  # the replica's engine

# (a): two sequences, three groups of positions each
PINNED = {"sequences": 2, "length": 3200, "pad_to": 3584, "rows": 16,
          "at": {"inside_first_window": 1000, "past_the_window": 2100,
                 "past_the_third_chunk": 3180}}
PREEMPT_AFTER = 8  # tokens the preempted sequence has generated by then


def pinned_check(c: dict, params, family, reference, seed: int,
                 fault: str = None) -> dict:
    """Logit error of the program's layers under the reference's routing,
    rms over the positions of each group and the whole vocabulary."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    r = np.random.default_rng([int(seed), 77])
    n, s, k = PINNED["sequences"], PINNED["pad_to"], PINNED["rows"]
    at = np.concatenate([p + np.arange(k) for p in PINNED["at"].values()])
    ref = jax.jit(lambda p, t, rows: reference.logits_and_routing(
        c, p, t, rows))
    got = jax.jit(lambda p, t, rows, w, e: family.pinned_logits(
        c, p, t, rows, w, e, fault=fault))
    sq = np.zeros(len(PINNED["at"]))
    ref_sq, worst = 0.0, 0.0
    for _ in range(n):
        tokens = np.zeros((1, s), np.int32)
        tokens[0, :PINNED["length"]] = r.integers(
            3, c["vocab_size"], PINNED["length"])
        t, rows = jnp.asarray(tokens), jnp.asarray(at[None], jnp.int32)
        want, weights, chosen = ref(params, t, rows)
        err = np.asarray(got(params, t, rows, weights, chosen) - want)[0]
        sq += (err.reshape(len(sq), k, -1) ** 2).sum(axis=(1, 2))
        ref_sq += float(jnp.sum(want * want))
        worst = max(worst, float(np.abs(err).max()))
    count = n * k * c["vocab_size"]
    by_group = dict(zip(PINNED["at"], (sq / count) ** 0.5))
    return {"logit_rms_error": {g: float(v) for g, v in by_group.items()},
            "logit_max_error": worst,
            "logit_rms": (ref_sq / (count * len(sq))) ** 0.5,
            "positions": n * k * len(sq)}


def arm(engine, preempt_prompt: list) -> list:
    """Note what every sequence holds as it is released, and preempt the
    sequence whose prompt is ``preempt_prompt`` once it has generated
    ``PREEMPT_AFTER`` tokens.  Returns the list the notes go to."""
    notes = []
    release, ensure = engine._release_slot, engine._ensure_capacity

    def noting(i, s):
        notes.append({"tokens": list(s.request.prompt_tokens + s.generated),
                      "held": s.num_tokens, "pages": list(s.pages),
                      "wpages": list(s.wpages),
                      "preempts": s.request.preempts})
        return release(i, s)

    def preempting(steps):
        for i, s in enumerate(engine._slots):
            if (s is not None and s.prefill_at is None
                    and not s.request.preempts
                    and len(s.generated) >= PREEMPT_AFTER
                    and s.request.prompt_tokens == preempt_prompt):
                engine._preempt(i, s)
        return ensure(steps)

    engine._release_slot, engine._ensure_capacity = noting, preempting
    return notes


def disarm(engine) -> None:
    """Take ``arm``'s two wrappers off: the engine's own methods again."""
    for name in ("_release_slot", "_ensure_capacity"):
        engine.__dict__.pop(name, None)


def verify_and_rows(c: dict, params, engine, reference, family, ask: dict,
                    notes: list, steps: int, pad_to: int) -> dict:
    """(c) and (b) from ONE reference pass a sequence (a check prompt and
    the tokens the engine continued it with).

    ``gaps``: ``reference.verify``'s, the engine's tokens on their own
    history.  ``rows``: K and V in the engine's two pools against that
    pass's, relative rms over the positions a layer still HELD when the
    sequence ended (a full layer all of them, a window layer those from its
    first page that was not given back), the prompt's rows (the chunks')
    and the generated tokens' (decode steps') apart, for ``first`` (layer
    0), ``second`` (layer 1: behind layer 0's windowed attention and its
    dense MLP, before any router), ``full`` (the full layers' own rows) and
    ``all``.  ``given_back``: for each sequence the null entries of its
    window table at the end against what the window implies."""
    import jax.numpy as jnp
    import numpy as np

    # (the configuration's window, which a control's engine falls short of)
    ps, W = engine.cfg.page_size, c["sliding_window"]
    kinds = [k for k, _ in engine.model_cfg.kinds()]
    groups = {"first": [0], "second": [1],
              "full": [i for i, k in enumerate(kinds) if k == "full"],
              "all": list(range(len(kinds)))}
    acc = {f"{g}_{part}": [0.0, 0.0] for g in groups
           for part in ("prefill", "decode")}
    gaps, found = [], []
    for p, o in zip(ask["prompts"], ask["outputs"]):
        g, (want_k, want_v) = reference.verify(c, params, [p], [o], steps,
                                               pad_to, rows=True)
        gaps += g
        seq = p + o
        note = next((n for n in reversed(notes)
                     if n["tokens"][:len(seq)] == seq[:len(n["tokens"])]
                     and len(n["tokens"]) >= len(seq) - 1), None)
        if note is None:
            raise RuntimeError("a check sequence was never released")
        held, wp = note["held"], note["wpages"]
        got_k, got_v = family.engine_rows(engine, note["pages"], wp)
        first_kept = next((i for i, page in enumerate(wp) if page), len(wp))
        behind = max(0, held - W + 1) // ps  # what the window implies
        found.append({"tokens": len(seq), "held": held,
                      "window_pages_null": first_kept,
                      "window_pages_behind": behind,
                      "window_pages_kept": sum(x != 0 for x in wp),
                      "preempts": note["preempts"]})
        n = min(len(p), held)
        for li, kind in enumerate(kinds):
            lo = first_kept * ps if kind == "window" else 0
            for name, a, b in (("prefill", lo, n), ("decode", max(lo, n),
                                                    held)):
                if b <= a:
                    continue
                err = sum(float(jnp.sum((got[li, a:b].astype(jnp.float32)
                                         - want[li, 0, a:b]) ** 2))
                          for got, want in ((got_k, want_k), (got_v, want_v)))
                ref = sum(float(jnp.sum(want[li, 0, a:b] ** 2))
                          for want in (want_k, want_v))
                for gname, layers in groups.items():
                    if li in layers:
                        acc[f"{gname}_{name}"][0] += err
                        acc[f"{gname}_{name}"][1] += ref
    rows = {k: (e / w) ** 0.5 if w else None for k, (e, w) in acc.items()}
    rows["sequences"] = found
    free = engine.window_allocator.num_free()
    return {"gaps": gaps, "rows": rows, "given_back": {
        # (the last burst's steps are trimmed behind by the NEXT burst: a
        # page may be one burst late)
        "every_sequence": all(0 <= f["window_pages_behind"]
                              - f["window_pages_null"] <= 1 for f in found),
        "some_page": sum(f["window_pages_null"] for f in found),
        "window_pages_free_after": free,
        "all_free_after": free == engine.window_allocator.num_pages - 1
        and engine.allocator.num_free() == engine.allocator.num_pages - 1,
        "preempted": int(np.sum([f["preempts"] for f in found]))}}


def make_loader(spec: dict):
    """``spec`` as ``in_worker.make_loader``'s.  The note it leaves has
    ``pinned`` where that one has ``reference``."""

    def load():
        import jax  # noqa: F401 - first use of the chip in this process

        notes, pid = spec["notes_dir"], os.getpid()
        t0 = time.time()
        clock = in_worker.CompileClock(
            os.path.join(notes, f"compile-{pid}.json"))
        c = spec["config"]
        family = common.module("families", c["family"])
        reference = common.module("reference", c["family"])
        params = family.make_params(c, spec["seed"], c["dtype"])
        jax.block_until_ready(params)
        t1 = time.time()
        fault = spec.get("fault")  # a control's, never a run's
        pinned = pinned_check(c, params, family, reference, spec["seed"],
                              fault)
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
        short = ({"sliding_window": c["sliding_window"]
                  - c["engine"]["page_size"]}
                 if fault == "window_short" else {})
        return params, family.model_config(c, **short)

    return load


def _wait_for(notes: str, name: str):
    cmd = os.path.join(notes, name)
    while not os.path.exists(cmd):
        time.sleep(0.05)
        if os.path.exists(os.path.join(notes, "cmd-finish")):
            return None
    return common.load_json(cmd)


def _verify_channel(spec: dict, params, family, reference):
    """Serves the driver's ``cmd-arm.json`` ({preempt}: the hooks go on the
    engine before the check's requests are sent) and then its one
    ``cmd-verify.json`` ({prompts, outputs, steps, pad_to}: what the engine
    answered): the reference's verdict on those tokens and on the rows they
    left in both pools, computed here because the weights and the engine
    are here.  The engine is idle meanwhile."""
    notes, pid = spec["notes_dir"], os.getpid()
    ask = _wait_for(notes, "cmd-arm.json")
    if ask is None:
        return
    try:
        engine = _engine()
        noted = arm(engine, ask["preempt"])
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
        disarm(engine)  # the engine is idle: nothing is inside either
        del noted[:]
    out["disarmed"] = not ({"_release_slot", "_ensure_capacity"}
                           & set(vars(engine)))
    common.write_json(os.path.join(notes, f"verify-{pid}.json"), out)
