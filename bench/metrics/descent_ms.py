"""Drain layer: device milliseconds per step under the program's scope
``ops.forest_sample``: the 1-D descent, and the marginal descent of a map."""


def read(ctx):
    n = ctx.span_count.get("bench.step", 0)
    t = ctx.scope_device_s.get("ops.forest_sample", 0.0)
    return t / n * 1e3 if n and t > 0 else None
