"""The ``breakdown`` of a traced run: the ten device operations that took
most time, and the five longest idle gaps, each labelled with what the
host was doing (the engine span or loop phase that overlaps it most)."""

from __future__ import annotations

from benchmarks import common

LABEL_SPANS = ("llm.prefill", "llm.queue", "replica.handle", "serve.route",
               "openai.request", "train.data_wait", "train.h2d",
               "train.step")


def _label(gap_wall, spans) -> str:
    best, best_overlap = "unattributed", 0.0
    for s in spans:
        if s.get("name") not in LABEL_SPANS:
            continue
        o = min(gap_wall[1], s["end_ts"]) - max(gap_wall[0], s["start_ts"])
        if o > best_overlap:
            best, best_overlap = s["name"], o
    return best


def build(ctx: dict) -> dict:
    tr = ctx["device_trace"]
    ops = sorted(tr["ops"].items(), key=lambda kv: -kv[1]["seconds"])[:10]
    zero = common.trace_zero(tr)
    idle = []
    for a, b in tr["gaps"][:5]:
        idle.append([_label((zero + a, zero + b), ctx.get("spans") or []),
                     b - a])
    return {"device_ops": [[k, v["seconds"]] for k, v in ops],
            "idle_gaps": idle}
