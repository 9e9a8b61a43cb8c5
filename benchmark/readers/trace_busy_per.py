"""Device busy time of the traced window per unit of a count."""


def read(ctx, spec):
    summary = ctx.trace_summary()
    per = ctx.counts.get(spec["per"])
    if summary is None or not per:
        return None
    return float(spec.get("scale", 1.0)) * summary.busy_s / per
