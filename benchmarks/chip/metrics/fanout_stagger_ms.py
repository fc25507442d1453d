"""Mean stagger of the front end's shard fan-out: for every ``pump``
span starting in the window that holds two or more ``dispatch`` spans,
the start of its last dispatch minus the start of its first, ms. It is
how long the last chip of a fan-out waits on the host's work for the
chips before it. None where no pump fanned out (every one-shard run)."""


def read(ctx):
    lo, hi = ctx.trace.window
    pumps = [(a, b) for a, b in ctx.trace.spans_named("pump") if lo <= a < hi]
    starts = [a for a, _ in ctx.trace.spans_named("dispatch")]
    staggers, j = [], 0
    for a, b in pumps:
        while j < len(starts) and starts[j] < a:
            j += 1
        inside = []
        while j < len(starts) and starts[j] < b:
            inside.append(starts[j])
            j += 1
        if len(inside) > 1:
            staggers.append(inside[-1] - inside[0])
    if not staggers:
        return None
    return sum(staggers) / len(staggers) / 1e6
