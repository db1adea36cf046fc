"""Kernels: varlen flash-prefill's share of its roofline, in %: the larger
of the FLOP and byte bounds of the chunked-prefill attention of the traced
steps (each prompt token over its causal context; each row's keys and values
read once per launch, plus q and out) over the kernel's trace time. The
bound that binds is printed by the harness."""
from bench import work


def read(ctx):
    steps = ctx.layer_steps()
    secs = ctx.kernel_s.get("flash_prefill", 0.0)
    if secs <= 0 or not all(s.consistent for s in steps):
        return None
    flops = nbytes = 0
    for s in steps:
        for a, b in s.prefill_rows:
            f, n = work.prefill_attention_work(ctx.shape, a, b)
            flops += f
            nbytes += n
    share, _ = work.roofline_share(flops, nbytes, secs, ctx.device_kind)
    return share
