"""One field of the program's flight notes over another, both summed over
the notes of one `kind` stamped inside the window: a share the program
states about itself (`flight_notes` reads one field per a count). No such
note, a ring that has dropped records, or a denominator of 0: nothing
read."""
from harness import load_module


def read(ctx, spec):
    from lightgbm_tpu import tracing

    recorder = tracing.recorder()
    window_s = ctx.counts.get("window_s")
    if ctx.window_open_at is None or window_s is None or recorder.dropped:
        return None
    notes_sum = load_module("readers", "flight_notes", ctx.roots).notes_sum
    lo, hi = ctx.window_open_at, ctx.window_open_at + window_s
    notes = recorder.snapshot()
    top = notes_sum(notes, spec["kind"], spec["field"], lo, hi)
    bottom = notes_sum(notes, spec["kind"], spec["over"], lo, hi)
    if top is None or not bottom:
        return None
    return top / bottom
