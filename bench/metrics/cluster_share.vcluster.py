"""Self time of ``repro.vc.cluster`` as a % of the traced window: site
k-means (every site's seeding, Lloyd steps and sub-cluster statistics),
dispatched and waited for."""

from bench.metrics import spans

SPAN = "repro.vc.cluster"


def read(ctx):
    got = spans.of_run(ctx, __file__)
    if got is None or SPAN not in got.spans:  # a program that opens no such span
        return None
    return spans.share(ctx, __file__, SPAN)
