"""90th percentile of time to first token of the requests started in the
window, in ms: from a request's due time (open loop) or submission (closed
loop) to the return of the step that delivered its first token. The run
serves on past the window until each has its first token (at most
`harness.GRACE_S`); one still without counts at its wait so far."""
from bench.stats import percentile, ttft_ms


def read(ctx):
    recs = ctx.log.records.values()
    return percentile(ttft_ms(recs, *ctx.window, until=ctx.log.closed), 90)
