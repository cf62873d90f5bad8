"""Update layer: device-idle milliseconds per map update inside
``bench.update`` spans: the host's part of an update (its row
normalisation, uploads, flag reads and dispatches)."""


def read(ctx):
    n = ctx.span_count.get("bench.update", 0)
    if not n:
        return None
    idle = dict(ctx.breakdown["idle_gaps"]).get("bench.update", 0.0)
    return idle / n * 1e3
