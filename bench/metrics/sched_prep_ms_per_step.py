"""Host time per scheduler step before the device runs the step: the part
of the program's `sched.admit`, `sched.plan` and `sched.dispatch` spans
(shedding and admission, the step's host arrays, the argument transfers
and the jitted call) in which no device operation runs, over the traced
`step` spans.  Layer: scheduler (`runtime/serve_lib.Scheduler.step`)."""
from bench.lib import progspans


def read(ctx):
    return progspans.idle_ms_per_step(ctx, __file__, progspans.PREP)
