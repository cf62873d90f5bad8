"""Host API: megabytes the program copies between host and device per
step, its counters ``host.bytes_in`` plus ``host.bytes_out`` over 1e6."""


def read(ctx):
    n = ctx.span_count.get("bench.step", 0)
    moved = (ctx.counters.get("host.bytes_in", 0)
             + ctx.counters.get("host.bytes_out", 0))
    return moved / n / 1e6 if n and moved else None
