"""The longest ``llm.loop.*`` phase other than idle that ended in the
window.  A burst's ``decode_fetch`` is 0.9 s; a reading over 2,000 is a
stall, and the note line says in which phase, in which iteration and how
long after the window opened."""

from benchmarks.trace import host_phases


def read(ctx):
    s = host_phases.longest_phase(ctx)
    if s is None:
        return None
    ms = (s["end_ts"] - s["start_ts"]) * 1e3
    ctx.setdefault("notes", []).append(
        f"longest engine-loop phase: {s['name']} {ms:.1f} ms, iteration "
        f"{(s.get('args') or {}).get('it')}, from "
        f"{s['start_ts'] - ctx['window']['t0_wall']:.3f} s into the window")
    return ms
