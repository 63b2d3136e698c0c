"""What every part of the harness shares: where files are, how a cell, a
configuration, a traffic mix, a generator, a family and a metric reader are
found BY NAME (there is no table of names anywhere in the harness), and the
percentile the metrics use."""

from __future__ import annotations

import importlib
import json
import math
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, "out")  # run outputs and traces; has its own .gitignore


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def write_json(path: str, obj) -> None:
    """Atomic: readers poll for these files."""
    with open(path + ".tmp", "w") as f:
        json.dump(obj, f)
    os.replace(path + ".tmp", path)


def read_json_when_there(path: str, deadline: float, alive=None):
    """Poll for a file another process writes; ``alive()`` false or the
    deadline passing raises."""
    while not os.path.exists(path):
        if time.monotonic() > deadline:
            raise TimeoutError(f"no {os.path.basename(path)} in time")
        if alive is not None and not alive():
            raise RuntimeError(f"the process that writes "
                               f"{os.path.basename(path)} has ended")
        time.sleep(0.02)
    with open(path) as f:
        return json.load(f)


def benchmark_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """A cell with its configuration and traffic mix resolved."""
    cell = load_json("cells", name + ".json")
    cell["name"] = name
    cell["config_file"] = load_json("configs", cell["config"] + ".json")
    cell["mix"] = load_json("traffic", cell["traffic"] + ".json")
    return cell


def module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py``."""
    return importlib.import_module(f"benchmarks.{kind}.{name}")


def peaks(device_kind: str) -> dict:
    table = load_json("peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json: "
                       f"add it with its source, there is no default")
    return table[device_kind]


def metric_entries(section: str, cell: str) -> list:
    """The metrics of ``section`` (end_to_end | per_layer) that
    BENCHMARK.json says this cell reports."""
    return [m for m in benchmark_json()[section]
            if "workloads" not in m or cell in m["workloads"]]


def percentile(xs, q: float):
    """Nearest-rank percentile (the value at or above share q); None if
    empty.  Callers put +inf in for what failed or never finished."""
    xs = sorted(xs)
    if not xs:
        return None
    return xs[min(len(xs) - 1, max(0, math.ceil(q * len(xs)) - 1))]


def median(xs):
    return percentile(xs, 0.5)


# -- what the metric readers share -------------------------------------------

def window_records(ctx: dict) -> list:
    """The client's records that belong to the window: in an open loop the
    requests DUE in it, in a closed loop those in flight during it."""
    recs, secs = ctx.get("records") or [], ctx["seconds"]
    if ctx.get("schedule_mode") == "open":
        return [r for r in recs
                if r["due"] is not None and 0.0 <= r["due"] < secs]
    return [r for r in recs if r["sent"] < secs
            and (r["last"] if r["last"] is not None else r["sent"]) >= 0.0]


def rehearsing() -> bool:
    """A rehearsal on the CPU with faked chips (``BENCH_REHEARSE=1``)."""
    return os.environ.get("BENCH_REHEARSE") == "1"


def trace_zero(trace: dict) -> float:
    """Wall-clock time of the trace's own zero: the instant the profiler
    session began, which lies between the two wall-clock readings taken
    around ``start_trace``."""
    return (trace["wall_start"] + trace["wall_started"]) / 2.0


def slice_wall(ctx: dict):
    """Wall-clock (start, end) of the traced slice, or None."""
    tr = ctx.get("device_trace")
    if not tr:
        return None
    zero = trace_zero(tr)
    return zero + tr["t_lo_s"], zero + tr["t_hi_s"]


def steps_done(ctx: dict) -> list:
    """The train steps that finished inside the window."""
    return [s for s in ctx.get("steps") or []
            if 0.0 <= s["end"] <= ctx["seconds"]]


def train_tokens_per_s(ctx: dict):
    """Tokens of the steps that finished inside the window, over the part of
    the window they took (the window opens as the first step begins; the
    step that the window's end cuts is left out with its time).  Dividing by
    the whole window instead would quantise the rate by one step in ~47,
    2 %, according to whether the last step just made it."""
    done = steps_done(ctx)
    if not done:
        return None
    return sum(s["tokens"] for s in done) / done[-1]["end"]


def spans_named(ctx: dict, name: str, within=None) -> list:
    """The program's spans of one name that ENDED inside ``within`` (wall
    clock), by default the window."""
    if within is None:
        t0 = ctx["window"]["t0_wall"]
        within = (t0, t0 + ctx["seconds"])
    return [s for s in ctx.get("spans") or [] if s.get("name") == name
            and within[0] <= s["end_ts"] < within[1]]


def module_time(ctx: dict, prefix: str):
    """(runs, device seconds) of the traced programs whose name starts
    with ``prefix``; None without a trace."""
    tr = ctx.get("device_trace")
    if not tr:
        return None
    hit = [v for k, v in tr["modules"].items() if k.startswith(prefix)]
    return sum(v["count"] for v in hit), sum(v["seconds"] for v in hit)
