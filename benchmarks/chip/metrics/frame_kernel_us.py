"""Device time per dispatch of the fused frame kernel, us."""
import tracing

NAMES = {"katana_frame_step", "katana_imm_frame_step"}


def read(ctx):
    n = tracing.op_count(ctx.trace, NAMES)
    if not n:
        return None
    return tracing.op_time_ns(ctx.trace, NAMES) / n / 1e3
