"""Device self time of the trace events whose name matches the metric's
`pattern`, optionally per a count. Nothing matched: nothing returned."""
import re


def matched_seconds(ctx, pattern: str):
    summary = ctx.trace_summary()
    if summary is None:
        return None
    rx = re.compile(pattern)
    hits = [s for name, s in summary.op_self_s.items() if rx.search(name)]
    return sum(hits) if hits else None


def read(ctx, spec):
    seconds = matched_seconds(ctx, spec["pattern"])
    if seconds is None:
        return None
    per = ctx.counts.get(spec["per"]) if "per" in spec else 1
    if not per:
        return None
    return float(spec.get("scale", 1.0)) * seconds / per
