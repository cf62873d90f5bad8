"""Build layer: the algorithm's build bytes over the build's device time,
as a share of the chip's HBM bandwidth."""


def read(ctx):
    n = ctx.span_count.get("bench.build", 0)
    t = ctx.span_device_s.get("bench.build", 0.0)
    if not n or t <= 0 or not ctx.work.get("build"):
        return None
    return 100.0 * ctx.work["build"] * n / t / ctx.hbm_bytes_per_s
