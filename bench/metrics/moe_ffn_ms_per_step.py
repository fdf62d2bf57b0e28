"""Device time per traced step of the MoE layers' FFN (`models/moe.py`,
ops in the name scope `moe_ffn/`: router, dispatch, held and shared
experts, combine), in ms.  Layer: model step."""
from bench.lib import progspans


def read(ctx):
    red = ctx.reduction
    if red is None or not red.steps():
        return None
    spent = progspans.scoped_seconds(red, "moe_ffn")
    if spent <= 0:
        return None
    return spent * 1e3 / len(red.steps())
