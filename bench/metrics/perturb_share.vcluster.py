"""Self time of ``repro.vc.perturb`` as a % of the traced window: the
border perturbation of every site, dispatched and waited for."""

from bench.metrics import spans

SPAN = "repro.vc.perturb"


def read(ctx):
    got = spans.of_run(ctx, __file__)
    if got is None or SPAN not in got.spans:  # a program that opens no such span
        return None
    return spans.share(ctx, __file__, SPAN)
