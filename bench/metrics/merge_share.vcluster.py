"""Self time of ``repro.vc.merge`` as a % of the traced window: the
logical merge over the gathered sub-cluster statistics, dispatched and
waited for; with the merges it made (the span's ``merges``) per job."""

from bench.metrics import span_stats, spans

SPAN = "repro.vc.merge"


def read(ctx):
    got = spans.of_run(ctx, __file__)
    if got is None or SPAN not in got.spans:  # a program that opens no such span
        return None
    got = spans.share(ctx, __file__, SPAN)
    stats = span_stats.totals(ctx, __file__, SPAN)
    if got is not None and stats is not None:
        n, sums = stats
        got["merges_per_job"] = sums["merges"] / n
    return got
