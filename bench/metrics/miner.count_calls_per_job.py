"""Mean support-count passes (``comm.count_calls``) of the grid mining
jobs completed in the window."""


def read(ctx):
    calls = [r["count_calls"] for r in ctx.requests if r["ok"] and "count_calls" in r]
    if not calls:
        return None
    return sum(calls) / len(calls)
