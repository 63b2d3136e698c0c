"""Page bytes the live sequences hold, over the tokens they hold: the
engine's gauges ``full_pages_in_use`` and ``window_pages_in_use`` (pages of
a layer of each kind) and ``live_tokens``, sampled at both ends of the
window (``runners/serve_windowed.py`` keeps the samples), times the layers
of each kind and a page's bytes from the published sizes.  Pools of one
shape would read the family's ``kv_bytes_per_token`` without a context
(10,240 at five layers) plus the last page's slack."""

from benchmarks import common


def read(ctx):
    samples = [s for s in ctx.get("page_samples") or []
               if s.get("live_tokens")]
    fam = common.module("families", ctx["config"]["family"])
    if not samples or not hasattr(fam, "layers_by_kind"):
        return None
    c = ctx["config"]
    win, full = fam.layers_by_kind(c)
    page = c["engine"]["page_size"] * fam.kv_row_bytes(c, c["dtype"])
    each = [(full * s["full_pages_in_use"] + win * s["window_pages_in_use"])
            * page / s["live_tokens"] for s in samples]
    ctx["notes"].append(
        "resident K/V a token at the window's ends: " + ", ".join(
            f"{v:.0f} B over {s['live_tokens']} tokens"
            for v, s in zip(each, samples))
        + f" (one-shape pools: {fam.kv_bytes_per_token(c):.0f})")
    return sum(each) / len(each)
