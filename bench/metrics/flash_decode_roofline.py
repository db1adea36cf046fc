"""Kernels: flash-decode's share of its roofline, in %: the bytes and FLOPs
the decode attention of the traced steps needs (each generated token's valid
context read once at the cache's width, plus q and out) at the chip's peaks,
over the trace time of the flash-decode kernel."""
from bench import work


def read(ctx):
    steps = ctx.layer_steps()
    secs = ctx.kernel_s.get("flash_decode", 0.0)
    if secs <= 0 or not all(s.consistent for s in steps):
        return None
    flops = nbytes = 0
    for s in steps:
        for k in s.decode_keys:
            f, b = work.decode_attention_work(ctx.shape, k)
            flops += f
            nbytes += b
    share, _ = work.roofline_share(flops, nbytes, secs, ctx.device_kind)
    return share
