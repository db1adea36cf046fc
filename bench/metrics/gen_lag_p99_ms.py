"""Load generator: 99th percentile of how late requests were submitted
after they were due (open loop), over the traced interval, in ms. The
single-threaded loop submits only between engine steps, so this is at most
about one step when the generator keeps up."""
from bench.stats import percentile


def read(ctx):
    t0, t1 = ctx.layer_window
    lags = [1e3 * (r.submitted - r.due) for r in ctx.log.records.values()
            if r.due is not None and t0 <= r.due <= t1]
    return percentile(lags, 99)
