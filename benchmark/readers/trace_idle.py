"""The device's idle share of the traced window."""


def read(ctx, spec):
    summary = ctx.trace_summary()
    if summary is None or summary.window_s <= 0:
        return None
    return 100.0 * (1.0 - summary.busy_s / summary.window_s)
