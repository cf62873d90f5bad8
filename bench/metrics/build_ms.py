"""Build layer: device milliseconds per forest build (``bench.build`` spans)."""


def read(ctx):
    n = ctx.span_count.get("bench.build", 0)
    t = ctx.span_device_s.get("bench.build", 0.0)
    return t / n * 1e3 if n and t > 0 else None
