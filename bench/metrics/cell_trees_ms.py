"""Build layer: device milliseconds per update under the program's scope
``forest.cell_trees``: cell ownership, node and leaf scatters, guide table."""


def read(ctx):
    n = ctx.span_count.get("bench.build", 0)
    t = ctx.scope_device_s.get("forest.cell_trees", 0.0)
    return t / n * 1e3 if n and t > 0 else None
