"""Self time of ``repro.level.fold`` as a % of the traced window: the fold
of counts into per-site dicts and frequent lists."""

from bench.metrics import spans


def read(ctx):
    return spans.share(ctx, __file__, "repro.level.fold")
