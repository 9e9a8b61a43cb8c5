"""A field of the program's flight notes (lightgbm_tpu.tracing: a bounded
in-memory ring of records, each stamped with `perf_counter`, the clock of
`ctx.window_open_at`), summed over the notes of one `kind` stamped inside
the window or, with `"when": "setup"`, before it opened; optionally per a
count. No such note, or a ring that has dropped records: nothing read."""


def notes_sum(notes: list, kind: str, field: str, lo: float, hi: float):
    """The sum of `field` over the notes of `kind` with lo <= t < hi, or
    None where there is none."""
    hits = [n[field] for n in notes
            if n["kind"] == kind and field in n and lo <= n["t"] < hi]
    return float(sum(hits)) if hits else None


def read(ctx, spec):
    from lightgbm_tpu import tracing

    recorder = tracing.recorder()
    window_s = ctx.counts.get("window_s")
    if ctx.window_open_at is None or window_s is None or recorder.dropped:
        return None
    if spec.get("when") == "setup":
        lo, hi = float("-inf"), ctx.window_open_at
    else:
        lo, hi = ctx.window_open_at, ctx.window_open_at + window_s
    value = notes_sum(recorder.snapshot(), spec["kind"], spec["field"],
                      lo, hi)
    per = ctx.counts.get(spec["per"]) if "per" in spec else 1
    if value is None or not per:
        return None
    return value / per
