"""The PIM linears of the layer stack (`core/pim.pim_linear_apply`, ops in
the name scope `pim_linear/`): the roofline's least time of the traced
steps' forwards over the linears' device time, in percent.  Least time per
forward: max(ops / int8 peak, bytes / HBM bandwidth), with the least
(ops, bytes) of the cell's layout (`linear_work`: for a dense decoder 2 ops
per weight per token, the int8 weights read once).  A mixed step is one
forward over its prefill tokens and decode rows; a decode chunk-scan is one
forward per recorded iteration.  Layer: model step."""
from bench.lib import progspans


def forwards(step):
    """Useful tokens of each forward of the layer stack in a recorded
    step."""
    if step.prefill:
        return [sum(n for n, _ in step.prefill)
                + sum(len(it) for it in step.decode)]
    return [len(it) for it in step.decode]


def least_seconds(steps, cfg, layout, peaks) -> float:
    total = 0.0
    for s in steps:
        for t in forwards(s):
            ops, nbytes = layout.linear_work(cfg, t)
            total += max(ops / peaks["int8_ops"],
                         nbytes / peaks["hbm_bytes_per_s"])
    return total


def read(ctx):
    if ctx.reduction is None or not ctx.steps:
        return None
    spent = progspans.scoped_seconds(ctx.reduction, "pim_linear")
    least = least_seconds(ctx.steps, ctx.config, ctx.layout, ctx.peaks)
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
