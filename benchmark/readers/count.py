"""A count or a host-clock span that the traffic kind recorded, optionally
per another count."""


def read(ctx, spec):
    value = ctx.counts.get(spec["count"])
    if value is None:
        return None
    if "per" in spec:
        per = ctx.counts.get(spec["per"])
        if not per:
            return None
        value = value / per
    return float(value)
