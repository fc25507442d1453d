"""The frame kernel's share of the HBM roofline: the bytes its inputs
and outputs hold (``kernel_bytes.frame_kernel_bytes``) over the chip's
published HBM bandwidth, as a share of its measured device time, %."""
import tracing

NAMES = {"katana_frame_step", "katana_imm_frame_step"}


def read(ctx):
    n = tracing.op_count(ctx.trace, NAMES)
    if not n:
        return None
    t = tracing.op_time_ns(ctx.trace, NAMES) / n / 1e9
    return 100.0 * ctx.run["frame_bytes"] / ctx.peaks["hbm_bytes_per_s"] / t
