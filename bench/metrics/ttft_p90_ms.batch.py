"""`ttft_p90_ms` under the bound of the steadier closed-loop batch cell:
90th percentile of time to first token of the requests submitted in the
window, waited for past it as `ttft_p90_ms` does, in ms."""
from bench.stats import percentile, ttft_ms


def read(ctx):
    recs = ctx.log.records.values()
    return percentile(ttft_ms(recs, *ctx.window, until=ctx.log.closed), 90)
