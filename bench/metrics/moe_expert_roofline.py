"""The held routed experts (`models/moe.py`, ops in the name scope
`moe_experts/`: their int8 matmuls and activation): the roofline's least
time of the traced steps' routed work over the experts' device time, in
percent.  Least time per step: max(ops / int8 peak, bytes / HBM
bandwidth), from the step's `moe.load` span (`bench/lib/moeload.py`):
ops = 2 * 3 * hidden * expert width per routed assignment, bytes = the
int8 weights of each expert that received any (3 * hidden * expert width
per (forward, layer, held expert) triple).  A lower bound: it counts the
router's choices, not the padded work the implementation does.  Layer:
model step."""
from bench.lib import moeload, progspans


def least_seconds(loads, cfg, peaks) -> float:
    weights = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return sum(max(2 * weights * assignments / peaks["int8_ops"],
                   weights * active / peaks["hbm_bytes_per_s"])
               for assignments, active in loads)


def read(ctx):
    loads = moeload.step_loads(ctx, __file__)
    if not loads:
        return None
    spent = progspans.scoped_seconds(ctx.reduction, "moe_experts")
    least = least_seconds(loads, ctx.config, ctx.peaks)
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
