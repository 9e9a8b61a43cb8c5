"""Device self time of the trace events whose name matches the metric's
`pattern`, per unit of work the program counted for them: the sum of one
`field` of the flight notes of one `kind` stamped inside the window
(`trace_ops` reads the time per tree, `flight_notes` the count per tree;
this is the one over the other, which does not drift with what the window's
trees hold). `scale` turns seconds into the unit (1e6: microseconds). In a
cell of several chips both sides are sums over the chips, so the ratio is
a chip's own. No trace, no matching event, no such note, a ring that has
dropped records, or a count of 0: nothing read."""
from harness import load_module


def per_unit(seconds, units, scale: float = 1.0):
    if not seconds or not units:
        return None
    return scale * seconds / units


def read(ctx, spec):
    seconds = load_module("readers", "trace_ops", ctx.roots).matched_seconds(
        ctx, spec["pattern"])
    units = load_module("readers", "flight_notes", ctx.roots).read(
        ctx, {"kind": spec["kind"], "field": spec["field"]})
    return per_unit(seconds, units, float(spec.get("scale", 1.0)))
