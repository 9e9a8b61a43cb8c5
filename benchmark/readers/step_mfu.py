"""The whole step's share of the chip's peak: the least time the chip could
take for the window's work (counted by the traffic kind with benchmark/
work.py, from the model and the shapes alone) over the window's time."""


def read(ctx, spec):
    window_s = ctx.counts.get("window_s")
    if ctx.least_s is None or not window_s:
        return None
    return 100.0 * ctx.least_s[0] / window_s
