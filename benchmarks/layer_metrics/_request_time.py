"""A request's time by what the engine loop was doing while it stood
(ISSUE 51).  The engine's ``llm.queue``, ``llm.admission`` and ``llm.decode``
spans each carry five attributes, in seconds, that sum to the span's length:
``step_s`` (decode bursts on the device and their launch), ``own_prefill_s``
and ``other_prefill_s`` (this request's admission, and other requests'),
``host_s`` (the loop's own work between programs) and ``idle_s``.  The loop
sums them on its one clock and a request reads the sums as it changes
state: nothing here intersects spans or converts clocks.

Requests are the spans that ENDED in the window (``common.spans_named``).
A program that does not put the five on its spans (the parent of the PR that
added them) has nothing to read: every function returns None."""

from benchmarks import common

# (the program's ``tracing.WAIT_ATTRS``, named here too: the harness reads
# spans of programs that do not have it and never imports the program)
FIVE = ("step_s", "own_prefill_s", "other_prefill_s", "host_s", "idle_s")


def spans(ctx, name):
    """``name``'s spans that ended in the window and carry the five."""
    return [s for s in common.spans_named(ctx, name)
            if all(k in (s.get("args") or {}) for k in FIVE)]


def length(span):
    return span["end_ts"] - span["start_ts"]


def per_token(ctx):
    """[(engine-side seconds a token, the ``llm.decode`` span)] of the
    requests that got two tokens or more: first token counted to stream
    end, over the tokens after the first."""
    return [(length(s) / (s["args"]["tokens"] - 1), s)
            for s in spans(ctx, "llm.decode")
            if (s["args"].get("tokens") or 0) >= 2]


def tail(ctx):
    """The anatomy of a tail token: over the slowest fifth of the requests
    (engine-side time a token at or above its 80th percentile), the mean
    ms a token by what the loop was doing.  ``step`` + ``other_prefill`` +
    ``host`` is the tail's mean engine-side ms a token: ``host`` takes
    ``host_s``, ``idle_s`` and the request's own prefill (the statements
    between its first token and the next phase; a resumed request's second
    admission), which is printed apart.  None without such spans."""
    if "_request_time_tail" not in ctx:
        ctx["_request_time_tail"] = None
        rates = per_token(ctx)
        if rates:
            cut = common.percentile([r for r, _ in rates], 0.8)
            slow = [(r, s) for r, s in rates if r >= cut]

            def mean_ms(*keys):
                return sum(sum(s["args"][k] for k in keys) * 1e3
                           / (s["args"]["tokens"] - 1)
                           for _, s in slow) / len(slow)

            out = {"requests": len(slow), "of": len(rates),
                   "ms_a_token": sum(r for r, _ in slow) * 1e3 / len(slow),
                   "step": mean_ms("step_s"),
                   "other_prefill": mean_ms("other_prefill_s"),
                   "host": mean_ms("host_s", "idle_s", "own_prefill_s"),
                   "own_prefill": mean_ms("own_prefill_s"),
                   "idle": mean_ms("idle_s")}
            ctx["_request_time_tail"] = out
            ctx.setdefault("notes", []).append(
                "a tail token by what the engine loop was doing (ms a "
                "token, mean over the slowest {requests} of {of} requests "
                "that ended in the window): {ms_a_token:.3f} = step "
                "{step:.3f} + other requests' prefill {other_prefill:.3f} "
                "+ host {host:.3f} (of which idle {idle:.3f}, its own "
                "prefill {own_prefill:.3f})".format(**out))
    return ctx["_request_time_tail"]


def tail_ms(ctx, part):
    out = tail(ctx)
    return None if out is None else out[part]
