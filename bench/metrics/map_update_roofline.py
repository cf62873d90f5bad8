"""Update layer: the algorithm's bytes per map update over the update's
device time, as a share of the chip's HBM bandwidth."""


def read(ctx):
    n = ctx.span_count.get("bench.update", 0)
    t = ctx.span_device_s.get("bench.update", 0.0)
    if not n or t <= 0 or not ctx.work.get("update"):
        return None
    return 100.0 * ctx.work["update"] * n / t / ctx.hbm_bytes_per_s
