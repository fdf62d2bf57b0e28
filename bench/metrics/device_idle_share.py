"""Share of the traced window in which no operation runs on the device:
1 - (union of device-operation intervals) / window, in percent."""


def read(ctx):
    if ctx.reduction is None or not ctx.reduction.busy:
        return None
    return 100.0 * ctx.reduction.idle_share()
