"""95th percentile of the gaps between consecutive tokens of each request,
pooled over every gap that ends in the window, in ms."""
from bench.stats import itl_ms, percentile


def read(ctx):
    return percentile(itl_ms(ctx.log.records.values(), *ctx.window), 95)
