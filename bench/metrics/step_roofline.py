"""Device: the window's algorithmic bytes (drains plus builds) over the
window, as a share of the chip's HBM bandwidth. It bounds what any kernel
of the step can claim, whichever kernels a later change removes."""


def read(ctx):
    moved = sum(ctx.work.get(layer, 0) * ctx.span_count.get(f"bench.{layer}", 0)
                for layer in ("drain", "build"))
    if moved <= 0 or ctx.window_s <= 0:
        return None
    return 100.0 * moved / ctx.window_s / ctx.hbm_bytes_per_s
