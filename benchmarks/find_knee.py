#!/usr/bin/env python3
"""Find the highest arrival rate a serving cell sustains, once, by a sweep
on the chip.  Not part of a run: the cell's traffic mix then holds 0.8 of
the knee as a number, and PERF.md records every rate tried.

    python benchmarks/find_knee.py <cell> <seconds> <rate> [<rate> ...]

One cluster and one replica for the whole sweep; each rate is one window of
``seconds`` of the cell's own generator with the rate replaced.  A rate is
sustained when every request drained and the time to first token did not
grow through the window (last third against first third).
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")

from benchmarks import common  # noqa: E402
from benchmarks.runners import serve  # noqa: E402


def main():
    cell = common.load_cell(sys.argv[1])
    seconds = float(sys.argv[2])
    rates = [float(r) for r in sys.argv[3:]]
    mix = cell["mix"]
    key = "rate_per_s" if "rate_per_s" in mix else "turn_rate_per_s"
    gen = common.module("generators", mix["kind"])
    stack = serve.Stack(cell, 0, False, os.path.join(
        common.OUT, "runs", f"knee.{cell['name']}"))
    rows = []
    try:
        stack.start()
        for i, rate in enumerate(rates):
            sched = gen.generate({**mix, key: rate}, 100 + i, seconds,
                                 stack.cfg["engine"],
                                 stack.cfg["vocab_size"])
            load = stack.run_load(sched, seconds, tag=f"rate{i}")
            ctx = {"records": load["records"], "seconds": seconds,
                   "schedule_mode": "open"}
            recs = common.window_records(ctx)
            ok = [r for r in recs if r["ok"]]
            ttft = [r["first"] - r["due"] for r in ok]
            third = seconds / 3.0
            early = [r["first"] - r["due"] for r in ok if r["due"] < third]
            late = [r["first"] - r["due"] for r in ok
                    if r["due"] >= 2 * third]
            tpot = [(r["last"] - r["first"]) / (r["n_out"] - 1)
                    for r in ok if r["n_out"] > 1]
            row = {
                "rate": rate, "due": len(recs), "ok": len(ok),
                "ttft_p50_ms": 1e3 * (common.median(ttft) or 0),
                "ttft_p90_ms": 1e3 * (common.percentile(ttft, 0.9) or 0),
                "ttft_first_third_p50_ms": 1e3 * (common.median(early) or 0),
                "ttft_last_third_p50_ms": 1e3 * (common.median(late) or 0),
                "tpot_p50_ms": 1e3 * (common.median(tpot) or 0),
                "tpot_p90_ms": 1e3 * (common.percentile(tpot, 0.9) or 0),
                "late_p99_ms": 1e3 * (common.percentile(
                    [r["sent"] - r["due"] for r in recs], 0.99) or 0),
                "counters": {k: load["counters"].get(k) for k in (
                    "prefills", "decode_steps", "tokens_generated",
                    "preempted", "page_evictions", "prefill_tokens_saved")}}
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        stack.stop()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"knee_{cell['name']}.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
