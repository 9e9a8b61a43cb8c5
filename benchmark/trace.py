"""From a profiler trace (`.xplane.pb`) to device busy and idle time, device
time per operation, and the `breakdown` of a result line.

Read with jax's own `ProfileData`, nothing else. The reduction works on
plain (name, start_ns, duration_ns) tuples, so the tests drive it with
hand-made events as well as with a recorded trace.

  busy    the union of the intervals in which an operation ran on a device
          ("XLA Ops" line of a "/device:" plane), clipped to the window;
  window  the host span named WINDOW_SPAN that the harness puts around the
          traced part of the measured window;
  idle    the window minus busy; each of the longest gaps is named by the
          innermost host span that covers its middle;
  self    an operation's duration minus the operations nested inside it
          (a `while` holds its body's operations), so times add up.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass

WINDOW_SPAN = "bench_window"
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "/host:"


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                 # mean over the devices
    busy_by_device: dict
    op_self_s: dict               # name -> seconds, summed over devices
    idle_gaps: list               # [(host span name, seconds)], longest first
    n_device_events: int


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(event_name: str) -> str:
    """A device event is named by its whole HLO instruction
    ("%fusion.59 = f32[...] fusion(...), kind=kCustom, calls=..."); the
    instruction's own name is enough, with the computation it calls where
    that says more (a Pallas kernel is a custom-call to its wrapper)."""
    head, sep, rest = event_name.partition(" = ")
    head = head.lstrip("%")
    if not sep:
        return head
    for key in ("custom_call_target=", "calls="):
        at = rest.find(key)
        if at >= 0:
            target = rest[at + len(key):].split(",")[0].strip('"% ')
            return f"{head}:{target}"[:120]
    return head


def load(path: str) -> dict:
    """{"devices": {plane: [(name, start_ns, dur_ns)]},
        "host": [(name, start_ns, dur_ns)]}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (op_name(e.name), float(e.start_ns),
                         float(e.duration_ns)) for e in line.events)
        elif plane.name.startswith(HOST_PREFIX):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events if e.duration_ns > 0)
    return {"devices": devices, "host": host}


def window_of(host: list, span: str = WINDOW_SPAN) -> tuple:
    """(start_ns, end_ns) of the harness's window span."""
    hits = [(s, s + d) for name, s, d in host if name == span]
    if not hits:
        raise ValueError(f"the trace holds no host span named {span!r}")
    return min(h[0] for h in hits), max(h[1] for h in hits)


def clipped(events: list, lo: float, hi: float) -> list:
    """[(start, end)] of the events' parts inside [lo, hi], by start."""
    out = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    out.sort()
    return out


def merged(intervals: list) -> list:
    """The union of sorted intervals as disjoint sorted intervals."""
    out = []
    for a, b in intervals:
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def gaps_of(busy: list, lo: float, hi: float) -> list:
    """The complement of disjoint sorted `busy` in [lo, hi]."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events: list, lo: float, hi: float) -> tuple:
    """({name: self ns}, {name: calls}) of the events that start inside
    [lo, hi): an event's self time is its duration minus the events nested
    in it on the same line."""
    inside = sorted((e for e in events if lo <= e[1] < hi),
                    key=lambda e: (e[1], -e[2]))
    self_ns, calls, stack = {}, {}, []  # stack of [name, end, child_ns, dur]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, child, dur = stack.pop()
            self_ns[name] = self_ns.get(name, 0.0) + max(dur - child, 0.0)
            if stack:
                stack[-1][2] += dur

    for name, s, d in inside:
        close(s)
        calls[name] = calls.get(name, 0) + 1
        stack.append([name, s + d, 0.0, d])
    close(float("inf"))
    return self_ns, calls


def name_gap(gap: tuple, host: list) -> str:
    """The innermost host span over the middle of an idle gap."""
    mid = 0.5 * (gap[0] + gap[1])
    best, best_d = "no host span", float("inf")
    for name, s, d in host:
        if name != WINDOW_SPAN and s <= mid < s + d and d < best_d:
            best, best_d = name, d
    return best


def reduce(path: str, top: int = 10) -> TraceSummary:
    raw = load(path)
    if not raw["devices"]:
        raise ValueError(f"{path}: no '{OPS_LINE}' line on any "
                         f"'{DEVICE_PREFIX}' plane: nothing ran on a device")
    return reduce_events(raw["devices"], raw["host"], top)


def reduce_events(devices: dict, host: list, top: int = 10) -> TraceSummary:
    lo, hi = window_of(host)
    busy_by_device, self_ns, n_events = {}, {}, 0
    gap_s = {}
    for plane, events in sorted(devices.items()):
        busy = merged(clipped(events, lo, hi))
        busy_by_device[plane] = sum(b - a for a, b in busy) / 1e9
        s_ns, c = self_times(events, lo, hi)
        for name, ns in s_ns.items():
            self_ns[name] = self_ns.get(name, 0.0) + ns
        n_events += sum(c.values())
        longest = sorted(gaps_of(busy, lo, hi), key=lambda g: g[0] - g[1])
        for gap in longest[:4 * top]:
            name = name_gap(gap, host)
            gap_s[name] = gap_s.get(name, 0.0) + (gap[1] - gap[0]) / 1e9
    n_dev = len(busy_by_device)
    return TraceSummary(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy_by_device.values()) / n_dev,
        busy_by_device=busy_by_device,
        op_self_s={k: v / 1e9 for k, v in self_ns.items()},
        idle_gaps=sorted(gap_s.items(), key=lambda kv: -kv[1])[:top],
        n_device_events=n_events)


def breakdown(summary: TraceSummary, top: int = 10) -> dict:
    """The result line's `breakdown`: the device operations that took most
    time (self time, seconds, summed over devices) and the longest idle
    gaps by what the host was doing."""
    ops = sorted(summary.op_self_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps[:top]]}
