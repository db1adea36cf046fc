"""Seconds from the process's start to the window's: imports, weights made
on the device, the step programs compiled or loaded from the persistent
cache, and the warm period of the cell's traffic."""


def read(ctx):
    return ctx.setup_s
