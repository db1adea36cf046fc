"""What a client sees, from the harness's own clock: time to first token,
gaps between tokens and tokens per second, over all of a window.

A request's record holds its start (its due time in an open loop, its
submission in a closed one) and the time each of its tokens was delivered,
which is the return of the engine step that produced it. Tails are taken
over every sample of the window, never as medians of pieces.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, List, Optional

import numpy as np


@dataclasses.dataclass
class Record:
    rid: int
    prompt_len: int
    max_new: int
    start: float                     # due (open loop) or submitted (closed)
    submitted: float
    due: Optional[float] = None
    client: Optional[int] = None
    slot: Optional[int] = None       # the engine slot that served it
    token_times: List[float] = dataclasses.field(default_factory=list)
    finished: Optional[float] = None
    status: str = "queued"


def ttft_ms(records: Iterable[Record], t0: float, t1: float,
            until: Optional[float] = None) -> List[float]:
    """First-token waits of the requests that started in [t0, t1], whose
    first tokens are waited for until `until` (t1 if not given). One with no
    first token by then counts at its wait so far."""
    until = t1 if until is None else until
    out = []
    for r in records:
        if not t0 <= r.start <= t1:
            continue
        first = r.token_times[0] if r.token_times else None
        end = first if first is not None and first <= until else until
        out.append(1e3 * (end - r.start))
    return out


def itl_ms(records: Iterable[Record], t0: float, t1: float) -> List[float]:
    """Gaps between consecutive tokens of each request, for every gap that
    ends in (t0, t1], pooled over all requests."""
    out = []
    for r in records:
        tt = r.token_times
        out.extend(1e3 * (b - a) for a, b in zip(tt, tt[1:]) if t0 < b <= t1)
    return out


def tokens_in(records: Iterable[Record], t0: float, t1: float) -> int:
    """Tokens delivered in (t0, t1]."""
    return sum(t0 < t <= t1 for r in records for t in r.token_times)


def percentile(xs: List[float], q: float) -> Optional[float]:
    """The q-th percentile (linear interpolation); None for no samples."""
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs \
        else None


def rows_per_decode(steps) -> Optional[float]:
    """Tokens generated by decode launches over the launches, in `steps`
    (`driver.StepLog`s); None where there are none or a step's split of
    the work does not add up to the engine's counters."""
    launches = sum(s.decode_launches for s in steps)
    if not launches or not all(s.consistent for s in steps):
        return None
    return sum(len(s.decode_keys) for s in steps) / launches
