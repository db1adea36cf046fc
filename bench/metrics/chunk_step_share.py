"""Scheduler: share of engine steps in the traced interval that launched a
chunked-prefill program (EngineStats.prefill_chunk_calls)."""


def read(ctx):
    steps = ctx.layer_steps()
    if not steps:
        return None
    return sum(s.chunk_launches > 0 for s in steps) / len(steps)
