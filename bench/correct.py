"""Whether what the timed path served is right.

After the window has closed, a sample drawn from the seed of the requests
that finished in the window (the longest among them always in it, the rest
from as many distinct engine slots as there are) is replayed through the
plain reference (`bench.reference`, at the precision the configuration
states for its run) over each prompt and its served tokens. At each served
token the reference's best logit is compared with the reference's logit of
the token the program served. Greedy decoding serves the program's own best
token, so a sound program only loses where rounding reorders near-ties.

The number compared is the mean of those gaps over every served token
(`mean_gap`). The widest gap (`max_gap`) is reported beside it but not
compared: the program's own rounding puts it at about half the control's,
so no limit lies between them (PERF.md, section 6).

The control is the reference one step below that precision
(`reference.CONTROL`, every activation in bfloat16): at each position of
the same prompts and served tokens, the gap of the token the control puts
first. `bench/readings.py` reads it; the benchmark's own runs never do.
"""
from __future__ import annotations

from typing import List

import numpy as np

from . import reference
from .loadgen import rng_for

MIN_TOKENS = 300        # served tokens compared per run, at least
MAX_REQUESTS = 48


def pick(records, requests, seed: int, window: tuple) -> List[tuple]:
    """(prompt, served tokens, slot) of requests that finished in
    `window`: the longest, then one from each other engine slot in a seeded
    order, then the rest in a seeded order, up to MAX_REQUESTS requests and
    on past that until MIN_TOKENS served tokens."""
    w0, w1 = window
    done = sorted((r for r in records
                   if r.status == "done" and r.finished is not None
                   and w0 <= r.finished <= w1 and r.rid in requests),
                  key=lambda r: r.rid)
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + r.max_new, -r.rid))
    rest = [done[i] for i in rng_for(seed, 9).permutation(len(done))
            if done[i] is not longest]
    slots = {longest.slot}
    first, later = [], []
    for r in rest:
        (later if r.slot in slots else first).append(r)
        slots.add(r.slot)
    out, served = [], 0
    for r in [longest] + first + later:
        if len(out) >= MAX_REQUESTS and served >= MIN_TOKENS:
            break
        prompt, tokens = requests[r.rid]
        out.append((np.asarray(prompt), np.asarray(tokens), r.slot))
        served += len(tokens)
    return out


def _summary(gaps: List[np.ndarray]) -> dict:
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    if not g.size:
        return {"max_gap": None, "mean_gap": None, "not_best_share": None}
    return {"max_gap": float(g.max()), "mean_gap": float(g.mean()),
            "not_best_share": float((g > 0).mean())}


def compare(spec: dict, key, sample: List[tuple], seq_len: int,
            max_new: int, control: bool = False) -> dict:
    """Replay `sample` through the reference at the configuration's
    precision. Returns, over every served token, the widest gap below the
    reference's best logit (`max_gap`), the mean gap, the share that is not
    the reference's best, the tokens and requests compared and the slots
    they came from; with `control`, the same of the tokens the control puts
    first, under "control"."""
    prec = reference.as_run(spec)
    gaps, ctl = [], []
    for prompt, out, _ in sample:
        n, p = len(out), len(prompt)
        seq = np.zeros(seq_len, np.int32)
        seq[:p] = prompt
        seq[p:p + n - 1] = out[:-1]
        # rows and tokens padded to `max_new`, so that each piece compiles
        # once
        rows = np.zeros(max_new, np.int32)
        rows[:n] = np.arange(p - 1, p - 1 + n)
        served = np.zeros(max_new, np.int32)
        served[:n] = out
        ref = reference.forward_logits(spec, key, seq, rows, prec)
        gaps.append(np.asarray(reference.gaps_below_best(ref, served))[:n])
        if control:
            low = reference.forward_logits(spec, key, seq, rows,
                                           reference.CONTROL)
            ctl.append(np.asarray(
                reference.gaps_below_best(ref, low.argmax(-1)))[:n])
        del ref
    out = {**_summary(gaps), "tokens": int(sum(g.size for g in gaps)),
           "requests": len(sample),
           "slots": len({slot for _, _, slot in sample})}
    if control:
        out["control"] = _summary(ctl)
    return out
