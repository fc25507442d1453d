"""Device time per dispatch of the tracker step's ops other than the
frame kernel: the step program's spawn/prune and counters, inside the
``dispatch`` spans, and the lane select after it, inside the ``select``
spans, us."""
import tracing

KERNELS = {"katana_frame_step", "katana_imm_frame_step"}


def read(ctx):
    disp = ctx.trace.spans_named("dispatch")
    if not disp or not ctx.trace.ops:
        return None
    kernel = tracing.op_time_ns(ctx.trace, KERNELS)
    step = (tracing.device_ns_in(ctx.trace, "dispatch")
            + tracing.device_ns_in(ctx.trace, "select"))
    return (step - kernel) / len(disp) / 1e3
