"""Attention's share of a decode step in which it runs BESIDE a state-space
mixer: the operation time under ``attn/attend`` (the paged kernel over a
slot's pages) as a share (%) of the operation time of the ``jit_decode_step*``
programs that ALSO hold ``ssm/state`` (the mixer's update of the slot's
state row, in the same layer).  ``lin_attn_decode_share`` gives the mixer's
side of the same step; no other reader gives the attention's for a layer
that has both.  None on any other family's cell, and on a parent without
the part."""

from benchmarks.trace import device_parts

STATE_PART, ATTEND_PART = "ssm/state", "attn/attend"


def read(ctx):
    progs = [p for name, p in (device_parts.read(ctx) or {}).items()
             if name.startswith("jit_decode_step")
             and STATE_PART in p["parts"]]
    total = sum(p["ops_s"] for p in progs)
    if total <= 0:
        return None
    hit = sum(sum(c.values()) for p in progs
              for part, c in p["parts"].items()
              if part == ATTEND_PART or part.startswith(ATTEND_PART + "/"))
    return 100.0 * hit / total
