"""Device time per traced step of the MoE layers' routing (`models/moe.py`,
ops in the name scope `moe_route/`: router, softmax and top-k, the
dispatch to the held experts and the gated combine), in ms.  Layer: model
step."""
from bench.lib import progspans


def read(ctx):
    red = ctx.reduction
    if red is None or not red.steps():
        return None
    spent = progspans.scoped_seconds(red, "moe_route")
    if spent <= 0:
        return None
    return spent * 1e3 / len(red.steps())
