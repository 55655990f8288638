"""Self time of ``repro.level.stage`` as a % of the traced window: building
each count's inputs: the candidate lists to count, their packed masks,
the padded site tables and their upload."""

from bench.metrics import spans


def read(ctx):
    return spans.share(ctx, __file__, "repro.level.stage")
