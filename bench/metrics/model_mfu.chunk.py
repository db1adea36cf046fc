"""Model step: forward FLOPs of the prompt tokens the chunk-width step
program processed in the traced interval over that program's device time
times the chip's bf16 peak, in %: how much of the peak the chunked-prefill
step turns into model work, whichever kernels run inside it."""
from bench import work


def read(ctx):
    t = ctx.trace
    steps = ctx.layer_steps()
    runs = t.step_ms.get("chunk") if t is not None else None
    if not runs or not all(s.consistent for s in steps):
        return None
    tokens = keys = 0
    for s in steps:
        for a, b in s.prefill_rows:
            tokens += b - a
            keys += work.prefix_keys(a, b)
    flops = work.tokens_flops(ctx.shape, tokens, keys)
    peak = work.peaks(ctx.device_kind)["bf16_flop_s"]
    return 100.0 * flops / (1e-3 * sum(runs) * peak) if tokens else None
