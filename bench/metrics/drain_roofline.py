"""Drain layer: the algorithm's drain bytes over the drain's device time,
as a share of the chip's HBM bandwidth."""


def read(ctx):
    n = ctx.span_count.get("bench.drain", 0)
    t = ctx.span_device_s.get("bench.drain", 0.0)
    if not n or t <= 0 or not ctx.work.get("drain"):
        return None
    return 100.0 * ctx.work["drain"] * n / t / ctx.hbm_bytes_per_s
