"""`pim_decode_pallas` (`kernels/pim_decode.py`; the trace names its custom
call `pim_decode_pallas.N`):
the roofline's least time of the traced calls over their summed device
time, in percent.  Least time per call: max(ops / int8 peak, bytes / HBM
bandwidth), with the counts of `bench/lib/counts.py`."""
from bench.lib import work

KERNEL = "pim_decode_pallas"


def read(ctx):
    if ctx.reduction is None:
        return None
    spent = ctx.reduction.kernel_seconds(KERNEL)
    least = work.kernel_least_seconds(ctx.steps, ctx.config, ctx.layout,
                                      ctx.device_kind, "decode",
                                      ctx.server["page_size"])
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
