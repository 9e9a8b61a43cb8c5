"""The share (%) of the traced window in which no operation ran on the
device while the host was inside a span named by the metric's `span`:
every idle gap, whole, not the longest few that `breakdown.idle_gaps`
names by their middles. The mean over the devices. A trace that holds no
such span reads nothing."""
import trace as trace_mod
import xplane


def idle_seconds_in(devices: dict, host: list, span: str, lo: float,
                    hi: float):
    inside = trace_mod.merged(trace_mod.clipped(
        [e for e in host if e[0] == span], lo, hi))
    if not inside:
        return None
    total = 0.0
    for events in devices.values():
        busy = trace_mod.merged(trace_mod.clipped(events, lo, hi))
        for gap_lo, gap_hi in trace_mod.gaps_of(busy, lo, hi):
            total += sum(min(b, gap_hi) - max(a, gap_lo) for a, b in inside
                         if min(b, gap_hi) > max(a, gap_lo))
    return total / 1e9 / len(devices)


def read(ctx, spec):
    if ctx.trace_summary() is None:
        return None
    raw = xplane.of(ctx)
    lo, hi = trace_mod.window_of(raw["host"])
    devices = {plane: [e[:3] for e in events]
               for plane, events in raw["devices"].items()}
    idle_s = idle_seconds_in(devices, raw["host"], spec["span"], lo, hi)
    if idle_s is None or hi <= lo:
        return None
    return 100.0 * idle_s / ((hi - lo) / 1e9)
