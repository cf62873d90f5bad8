"""Host API: device-idle milliseconds per step inside the program's
``repro.*`` host spans (copies, dispatches, waits, flag reads)."""


def read(ctx):
    n = ctx.span_count.get("bench.step", 0)
    gaps = ctx.program_gap_s
    return sum(gaps.values()) / n * 1e3 if n and gaps else None
