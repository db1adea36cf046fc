"""Model step: forward FLOPs of the valid tokens the steps of the traced
interval processed (prompt and generated tokens, attention at each token's
real context, padding not counted) over the traced window times the chip's
bf16 peak, in %."""
from bench import work


def read(ctx):
    t = ctx.trace
    steps = ctx.layer_steps()
    if t is None or not steps or not all(s.consistent for s in steps):
        return None
    tokens = keys = 0
    for s in steps:
        for a, b in s.prefill_rows:
            tokens += b - a
            keys += work.prefix_keys(a, b)
        tokens += len(s.decode_keys)
        keys += sum(s.decode_keys)
    flops = work.tokens_flops(ctx.shape, tokens, keys)
    peak = work.peaks(ctx.device_kind)["bf16_flop_s"]
    return 100.0 * flops / (t.window_s * peak) if tokens else None
