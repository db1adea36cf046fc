"""Scheduler: rows each decode launch carried over the traced interval:
tokens generated by decode launches over the launches
(EngineStats.decode_steps). A prompt's first token comes from its last
chunk launch and is not counted."""
from bench.stats import rows_per_decode


def read(ctx):
    return rows_per_decode(ctx.layer_steps())
