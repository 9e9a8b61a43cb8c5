"""A traffic kind the harness has never seen: found by the name in the
traffic file, it needs no edit of any file that was there."""
import time


def run(ctx) -> None:
    opened = ctx.open_window()
    calls = int(ctx.traffic["calls"])
    ctx.close_window()
    ctx.counts["unseen_calls"] = calls
    ctx.e2e["unseen_calls_per_s"] = calls / max(
        time.perf_counter() - opened, 1e-9)
    ctx.attempted = calls
    ctx.compare("unseen_gap", 0.0, 0.0)
