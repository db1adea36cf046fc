"""Model step: mean device time of one execution of the decode-width step
program (the module that runs the flash-decode kernel), in ms."""


def read(ctx):
    t = ctx.trace
    runs = t.step_ms.get("decode") if t is not None else None
    return sum(runs) / len(runs) if runs else None
