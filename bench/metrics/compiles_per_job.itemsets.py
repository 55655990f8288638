"""Mean XLA compiles of the jobs of the traced window: the compiles the
service recorded on each ``repro.request`` span (its execution group's),
counted once for each request the span served."""

from bench.metrics import spans


def read(ctx):
    got = spans.of_run(ctx, __file__)
    if got is None:
        return None
    n = sum(k for _, k in got.request_compiles)
    if n == 0:
        return 0.0
    return {"value": sum(c * k for c, k in got.request_compiles) / n, "requests": n}
