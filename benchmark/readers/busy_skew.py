"""How unevenly the chips of a traced window were busy: the busiest
device's busy time over the least busy one's, less one, in %
(`TraceSummary.busy_by_device`: the union of each device's operations
inside the window). A trace of fewer than two devices, or of one that ran
nothing, reads nothing."""


def skew_pct(busy_by_device: dict):
    busy = list(busy_by_device.values())
    if len(busy) < 2 or min(busy) <= 0:
        return None
    return 100.0 * (max(busy) / min(busy) - 1.0)


def read(ctx, spec):
    summary = ctx.trace_summary()
    if summary is None:
        return None
    return skew_pct(summary.busy_by_device)
