"""Device time per traced step of the KV pool's relayout to the kernels'
head-major layout (`kernels/ops.paged_kernel_layout`, ops in the name scope
`kv_relayout/`), in ms.  Layer: kernels."""
from bench.lib import progspans


def read(ctx):
    red = ctx.reduction
    if red is None or not red.steps():
        return None
    spent = progspans.scoped_seconds(red, "kv_relayout")
    if spent <= 0:
        return None
    return spent * 1e3 / len(red.steps())
