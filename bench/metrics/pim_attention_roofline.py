"""`pim_attention_pallas` (`kernels/pim_attention.py`; the trace names its
custom call `pim_attention_pallas.N`), the kernel of prefill-chunk rows: the roofline's least
time of the traced calls over their summed device time, in percent."""
from bench.lib import work

KERNEL = "pim_attention_pallas"


def read(ctx):
    if ctx.reduction is None:
        return None
    spent = ctx.reduction.kernel_seconds(KERNEL)
    least = work.kernel_least_seconds(ctx.steps, ctx.config, ctx.layout,
                                      ctx.device_kind, "prefill",
                                      ctx.server["page_size"])
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
