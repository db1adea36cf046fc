"""Tokens delivered in the window over the window's seconds."""
from bench.stats import tokens_in


def read(ctx):
    w0, w1 = ctx.window
    return tokens_in(ctx.log.records.values(), w0, w1) / (w1 - w0)
