"""Share (%) of the summed ``llm.admission`` time (spans ended in the
window) that was not the prompt's own prefill (``own_prefill_s``): the
bursts, other prompts and host work between its chunks, and for block
diffusion the passes before its first block is final.  ~0 where a prompt is
one program."""

from benchmarks.layer_metrics import _request_time


def read(ctx):
    spans = _request_time.spans(ctx, "llm.admission")
    total = sum(_request_time.length(s) for s in spans)
    if total <= 0:
        return None
    own = sum(s["args"]["own_prefill_s"] for s in spans)
    return 100.0 * max(0.0, total - own) / total
