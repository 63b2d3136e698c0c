#!/usr/bin/env python3
"""One run of one benchmark cell:

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that sets the system up, warms up every program the cell's
traffic reaches, measures for ``--seconds``, checks the outputs, prints one
JSON object as the LAST line of its standard output and exits.  With
``--trace 0`` the line carries the cell's end-to-end metrics, taken on the
host's clock with tracing off; with ``--trace 1`` its per-layer metrics,
from the program's spans and counters and a profiler trace of a slice of
the window.  Earlier lines (prefixed ``#``) are for a reader.

The harness holds no table of names.  ``--workload`` names
``cells/<cell>.json``; that names ``configs/<config>.json`` and
``traffic/<mix>.json``; the mix names ``generators/<kind>.py``, whose
``RUNNER`` names ``runners/<runner>.py``; the configuration names
``families/<family>.py`` and ``reference/<family>.py``; and every metric
BENCHMARK.json lists for the cell is read by ``end_to_end/<metric>.py`` or
``layer_metrics/<metric>.py``.  A later cell, configuration, mix or metric
is a new file and a new entry.

This process never imports JAX: the chip belongs to the worker the
scheduler grants it to.  Without a TPU (or with fewer chips than the cell
asks for) the run fails and prints no result.  ``BENCH_REHEARSE=1`` lets a
rehearsal run on the CPU with faked chips; its line names the platform it
ran on, which no reader takes for a chip's.
"""

import time

_T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")

from benchmarks import common  # noqa: E402


def note(text: str):
    print("# " + text, flush=True)


def read_metrics(section: str, kind: str, ctx: dict) -> dict:
    """Every metric BENCHMARK.json lists for this cell in ``section``, each
    from its own reader.  A reader that finds nothing to read returns None
    and the metric is left out."""
    out = {}
    for entry in common.metric_entries(section, ctx["cell"]["name"]):
        value = common.module(kind, entry["name"]).read(ctx)
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    seconds = args.seconds or float(common.benchmark_json()["run_seconds"])
    cell = common.load_cell(args.workload)
    gen = common.module("generators", cell["mix"]["kind"])
    runner = common.module("runners", gen.RUNNER)
    try:
        ctx = runner.run(cell, args.seed, seconds, bool(args.trace), _T_START)
    except BaseException as e:  # noqa: BLE001 - report and fail, no result
        traceback.print_exc()
        print(f"benchmark FAILED: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        return 1
    ctx["peaks"] = None
    if ctx["device"]["platform"] == "tpu":
        ctx["peaks"] = common.peaks(ctx["device"]["kind"])  # unknown: error
    ctx.setdefault("notes", [])
    device = dict(ctx["device"])
    if args.trace:
        metrics = read_metrics("per_layer", "layer_metrics", ctx)
        tr = ctx.get("device_trace")
        if tr is None:
            print(f"benchmark FAILED: no device trace: "
                  f"{ctx.get('trace_error')}", file=sys.stderr)
            return 1
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        # for a reader: what tracing does to the end-to-end numbers
        traced = read_metrics("end_to_end", "end_to_end", ctx)
        ctx["notes"].append("end-to-end in this traced run: " + ", ".join(
            f"{k} {v['value']:.4f}" for k, v in traced.items()))
    else:
        metrics = read_metrics("end_to_end", "end_to_end", ctx)
    for line in ctx["notes"]:
        note(line)
    note("setup parts: " + json.dumps(ctx.get("setup_parts")))
    note("correct: " + json.dumps(ctx["correct"]))
    result = {"correct": bool(ctx["correct"]["ok"])
              and ctx["compiles_in_window"] == 0,
              "attempted": ctx["attempted"], "failed": ctx["failed"],
              "metrics": metrics, "device": device}
    if args.trace:
        from benchmarks.trace import breakdown

        result["breakdown"] = breakdown.build(ctx)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
