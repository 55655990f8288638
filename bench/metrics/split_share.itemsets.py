"""Self time of ``repro.split`` as a % of the traced window: the service's
site split of the table for each grid job (concatenate, split, pack,
upload)."""

from bench.metrics import spans


def read(ctx):
    return spans.share(ctx, __file__, "repro.split")
