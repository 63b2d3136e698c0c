"""Share of the device's busy time spent in the flash attention kernels
(events of ``flash_attention_fwd``, ``_bwd_dkv``, ``_bwd_dq`` on the first
device).  Each kernel's own time and roofline share, with the bound that
holds, go to an earlier line."""

from benchmarks import common

KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dkv",
           "flash_attention_bwd_dq")


def read(ctx):
    tr = ctx.get("device_trace")
    if not tr or ctx["kind"] != "train":
        return None
    # the reduction keys a kernel's calls by the kernel's own name
    per = {k: (tr["ops"][k]["count"], tr["ops"][k]["seconds"])
           for k in KERNELS if k in tr["ops"]}
    if not per:
        return None
    family = common.module("families", ctx["config"]["family"])
    lay = ctx["kernel_layout"]  # per device: batch, heads, seq, dtype_bytes
    for k, (count, seconds) in per.items():
        if not ctx.get("peaks"):
            continue
        cost = family.flash_kernel_cost(ctx["config"], k, lay["batch"],
                                        lay["seq_len"], lay["heads"],
                                        lay["dtype_bytes"])
        t_flops = cost["flops"] / ctx["peaks"]["bf16_flops_per_s"]
        t_bytes = cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
        bound = "compute" if t_flops >= t_bytes else "memory"
        ctx["notes"].append(
            f"{k}: {count} calls, {seconds / count * 1e3:.3f} ms a call, "
            f"{k}_roofline {100 * max(t_flops, t_bytes) / (seconds / count):.1f}"
            f" % ({bound}-bound)")
    busy = tr["per_device"][min(tr["per_device"])]["busy_s"]
    return 100.0 * sum(v[1] for v in per.values()) / busy
