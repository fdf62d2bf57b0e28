"""Host time per scheduler step after the device ran the step: the part of
the program's `sched.readback` and `sched.commit` spans (reading the
outputs, token bookkeeping, retirement, the step's closing hooks) in which
no device operation runs, over the traced `step` spans.  Layer: scheduler
(`runtime/serve_lib.Scheduler.step`)."""
from bench.lib import progspans


def read(ctx):
    return progspans.idle_ms_per_step(ctx, __file__, progspans.COMMIT)
