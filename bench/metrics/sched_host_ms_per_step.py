"""Host time per scheduler step: the part of each harness `step` span (one
`Scheduler.step` call) in which no device operation runs, averaged over the
traced steps.  Layer: scheduler (`runtime/serve_lib.Scheduler`)."""


def read(ctx):
    if ctx.reduction is None:
        return None
    return ctx.reduction.host_ms_per_step()
