"""Update layer: device milliseconds per map update (``bench.update``
spans): the band's CDFs, the skip key, the row builds, the scatter into the
class stack and the marginal."""


def read(ctx):
    n = ctx.span_count.get("bench.update", 0)
    t = ctx.span_device_s.get("bench.update", 0.0)
    return t / n * 1e3 if n and t > 0 else None
