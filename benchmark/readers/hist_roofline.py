"""The histogram kernel's share of its roofline: the least time for the
rows it was handed (benchmark/work.py hist_work) over the device
time of its trace events."""
import work
from harness import load_module


def read(ctx, spec):
    seconds = load_module("readers", "trace_ops", ctx.roots).matched_seconds(
        ctx, spec["pattern"])
    rows = ctx.counts.get("hist_rows")
    if not seconds or not rows:
        return None
    least, _ = work.least_seconds(work.hist_work(
        rows, ctx.counts["features"], ctx.counts["bin_bytes"],
        ctx.counts["gh_bytes"], ctx.counts["operand"]), ctx.device["kind"])
    return 100.0 * least / seconds
