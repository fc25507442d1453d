"""Mean host time per pump outside the tracker step: each ``pump`` span
minus the ``dispatch`` spans inside it (batch forming, lane select,
snapshot copies, checkpoints), ms."""


def read(ctx):
    pumps = ctx.trace.spans_named("pump")
    disp = ctx.trace.spans_named("dispatch")
    if not pumps:
        return None
    total, j = 0, 0
    for a, b in pumps:
        inner = 0
        while j < len(disp) and disp[j][0] < b:
            if disp[j][0] >= a:
                inner += disp[j][1] - disp[j][0]
            j += 1
        total += (b - a) - inner
    return total / len(pumps) / 1e6
