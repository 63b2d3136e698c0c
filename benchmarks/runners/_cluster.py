"""What both runners do before anything else: build the native components,
fix the compile cache, start the cluster and see that it has the chips the
cell asks for.  Never imports JAX."""

from __future__ import annotations

import glob
import os
import shutil

from benchmarks import common


def start(cell: dict, run_dir: str, store_bytes: int):
    """Returns the node.  ``run_dir`` is emptied: it takes this run's notes,
    plans and traces."""
    import ray_tpu
    from ray_tpu._private import direct
    from ray_tpu.native import build
    from ray_tpu.util import compile_cache

    # built from their sources on first use (a checkout holds no binaries)
    for name in ("shm_store", "gcs_server", "_rtpu_core",
                 "libmutable_channel"):
        build.binary_path(name)
    if direct.native_core() is None:
        raise RuntimeError("the native transport did not load")
    os.environ.setdefault("RTPU_LOG_TO_DRIVER", "0")
    compile_cache.enable()  # the workers inherit it
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    chips = int(cell["chips"])
    node = ray_tpu.init(
        resources={"CPU": 8.0, **({"TPU": float(chips)}
                                  if common.rehearsing() else {})},
        min_workers=2, max_workers=8, object_store_memory=store_bytes)
    found = int(node.resources.get("TPU", 0))
    if found < chips:
        ray_tpu.shutdown()
        raise RuntimeError(f"the cell needs {chips} TPU chip(s) and the "
                           f"machine shows {found}")
    return node


def run_dir(cell: dict, seed: int, trace: bool) -> str:
    return os.path.join(common.OUT, "runs",
                        f"{cell['name']}.s{seed}.t{int(trace)}")


def stderr_tails(node, lines: int = 30) -> str:
    """Worker output goes to files the driver never shows: their ends."""
    out = []
    for path in sorted(glob.glob(os.path.join(
            node.session_dir, "logs", "worker-*.err"))):
        with open(path, errors="replace") as f:
            tail = f.readlines()[-lines:]
        if tail:
            out.append(f"--- {os.path.basename(path)}\n{''.join(tail)}")
    return "\n".join(out)
