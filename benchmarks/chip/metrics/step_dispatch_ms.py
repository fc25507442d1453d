"""Mean host span of one shard's tracker-step call, through
``block_until_ready`` of its result, ms."""


def read(ctx):
    disp = ctx.trace.spans_named("dispatch")
    if not disp:
        return None
    return sum(b - a for a, b in disp) / len(disp) / 1e6
