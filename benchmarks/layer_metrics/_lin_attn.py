"""What the linear-attention metrics share: the part names the family gives
(``lin_attn/*``) and the programs of the traced slice that hold such a part.
A trace without them (any other family's cell, a parent without the layer)
gives None everywhere."""

from benchmarks import common
from benchmarks.trace import device_parts


def family(ctx):
    fam = common.module("families", ctx["config"]["family"])
    return fam if hasattr(fam, "STATE_PART") else None


def programs(ctx, prefix: str) -> list:
    """The per-part tables of the programs named ``prefix*`` in which some
    operation lies under a linear-attention part; [] without any."""
    fam = family(ctx)
    if fam is None:
        return []
    return [p for name, p in (device_parts.read(ctx) or {}).items()
            if name.startswith(prefix) and any(
                part.startswith(fam.PARTS_PREFIX) for part in p["parts"])]


def share(ctx, prefix: str):
    """Share (%) of the operation time of the programs ``prefix*`` that
    lies under the linear-attention parts; None where they have none."""
    progs = programs(ctx, prefix)
    total = sum(p["ops_s"] for p in progs)
    if total <= 0:
        return None
    fam = family(ctx)
    hit = sum(sum(c.values()) for p in progs for part, c in p["parts"].items()
              if part.startswith(fam.PARTS_PREFIX))
    return 100.0 * hit / total
