"""Drain layer: device milliseconds per drain call (``bench.drain`` spans)."""


def read(ctx):
    n = ctx.span_count.get("bench.drain", 0)
    t = ctx.span_device_s.get("bench.drain", 0.0)
    return t / n * 1e3 if n and t > 0 else None
