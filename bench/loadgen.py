"""Requests from a traffic mix file and a seed.

Every length and every gap between arrivals is drawn by stratified sampling:
n values at the evenly spaced quantiles (i + 0.5) / n of the mix's
distribution, put in an order drawn from the seed. So every seed gets the
same multiset of sizes and arrivals in another order, and runs with
different seeds do the same work. Token ids are uniform over the
vocabulary, drawn from the seed.

Two loops. Open (`"loop": "open"`): arrivals are a schedule fixed in
advance (Poisson at `rate_per_s`), sent whether or not earlier requests
have finished; each request is timed from when it was due. The schedule is
made of segments (the warm period, the window, a tail), each sampled on its
own and stretched to end on its boundary, so that every seed's window holds
the same requests in another order. Closed
(`"loop": "closed"`): `clients_per_slot` clients per engine slot, each
sending its next request when its previous one completes; a client's first
answer length is drawn from the residual-life distribution of the answer
lengths, so completions are staggered from the start.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np

_GOLDEN = (math.sqrt(5.0) - 1.0) / 2.0


@dataclasses.dataclass
class Planned:
    """One request as the generator plans it."""
    rid: int
    prompt: np.ndarray          # (len,) int32 token ids
    max_new: int
    due: Optional[float] = None  # open loop: seconds after the schedule starts
    client: Optional[int] = None


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any integer seed."""
    return np.random.default_rng([stream, seed % (1 << 64)])


def quantile_fn(dist: dict):
    """u in (0, 1) -> a value of the distribution `dist` (a mix entry)."""
    kind = dist["dist"]
    if kind == "lognormal":
        std = NormalDist()

        def q(u):
            v = dist["median"] * math.exp(dist["sigma"] * std.inv_cdf(u))
            return int(min(max(round(v), dist["min"]), dist["max"]))
        return q
    if kind == "uniform":
        lo, hi = dist["min"], dist["max"]
        return lambda u: lo + min(int(u * (hi - lo + 1)), hi - lo)
    if kind == "exponential":
        return lambda u: -math.log1p(-u) / dist["rate"]
    raise ValueError(f"unknown distribution {kind!r}")


def stratified(n: int, dist: dict, rng: np.random.Generator,
               spread: bool = False) -> np.ndarray:
    """n values at the quantiles (i + 0.5) / n of `dist`, in a seeded order:
    shuffled, or with `spread` in the golden-ratio order from a seeded
    start, so that any run of consecutive values covers the distribution
    evenly."""
    q = quantile_fn(dist)
    vals = np.asarray([q((i + 0.5) / n) for i in range(n)])
    if not spread:
        return vals[rng.permutation(n)]
    keys = (rng.random() + np.arange(n) * _GOLDEN) % 1.0
    return vals[np.argsort(np.argsort(keys))]


def residual_life(lengths: np.ndarray, n: int,
                  rng: np.random.Generator) -> np.ndarray:
    """n stratified draws of the tokens left in a request caught mid-answer:
    P(R = r) is proportional to P(L >= r) for r >= 1, L drawn from
    `lengths`. In a seeded order."""
    top = int(lengths.max())
    r = np.arange(1, top + 1)
    surv = (lengths[None, :] >= r[:, None]).mean(axis=1)
    cdf = np.cumsum(surv) / surv.sum()
    u = (np.arange(n) + 0.5) / n
    vals = r[np.minimum(np.searchsorted(cdf, u), top - 1)]
    return vals[rng.permutation(n)]


def _prompts(lengths, vocab_ids: int, rng) -> List[np.ndarray]:
    return [rng.integers(1, vocab_ids, int(n)).astype(np.int32)
            for n in lengths]


class OpenLoop:
    """A Poisson schedule at the mix's rate over consecutive segments of
    the given lengths: round(rate * length) requests in each. Gaps are
    shuffled, as a Poisson process's are; lengths are spread (see
    `stratified`), so that long prompts do not bunch by the seed's chance."""

    def __init__(self, mix: dict, seed: int, vocab_ids: int,
                 segments: List[float]):
        rate = float(mix["rate_per_s"])
        self.planned: List[Planned] = []
        t0 = 0.0
        for k, length in enumerate(segments):
            n = max(1, round(rate * length))
            gaps = stratified(n, {"dist": "exponential", "rate": rate},
                              rng_for(seed, 10 * k + 1))
            due = t0 + np.cumsum(gaps) * (length / gaps.sum())
            plen = stratified(n, mix["prompt_len"],
                              rng_for(seed, 10 * k + 2), True)
            olen = stratified(n, mix["output_len"],
                              rng_for(seed, 10 * k + 3), True)
            prompts = _prompts(plen, vocab_ids, rng_for(seed, 10 * k + 4))
            rid0 = len(self.planned)
            self.planned += [Planned(rid0 + i, prompts[i], int(olen[i]),
                                     float(due[i])) for i in range(n)]
            t0 += length
        self._next = 0

    def due_by(self, t: float) -> List[Planned]:
        """Requests due at or before t (seconds into the schedule) and not
        yet handed out."""
        out = []
        while self._next < len(self.planned) and \
                self.planned[self._next].due <= t:
            out.append(self.planned[self._next])
            self._next += 1
        return out

    def next_due(self) -> Optional[float]:
        if self._next < len(self.planned):
            return self.planned[self._next].due
        return None


class ClosedLoop:
    """`clients` waiting clients over a pool of `pool` requests, cycled. A
    window sees only the few requests that clients send in it, so the pool
    is spread (see `stratified`) rather than shuffled: every window's
    requests then cover the distribution alike."""

    def __init__(self, mix: dict, seed: int, vocab_ids: int, clients: int):
        n = int(mix["pool"])
        plen = stratified(n, mix["prompt_len"], rng_for(seed, 2), True)
        self._olen = stratified(n, mix["output_len"], rng_for(seed, 3), True)
        self._prompts = _prompts(plen, vocab_ids, rng_for(seed, 4))
        if mix.get("first_output") == "residual_life":
            first = residual_life(self._olen, clients, rng_for(seed, 5))
        else:
            first = self._olen[np.arange(clients) % n]
        self.clients = clients
        self._first = [int(x) for x in first]
        self._k = 0

    def _take(self, client: int, max_new: Optional[int]) -> Planned:
        i = self._k % len(self._prompts)
        rid = self._k
        self._k += 1
        return Planned(rid, self._prompts[i],
                       int(self._olen[i]) if max_new is None else max_new,
                       client=client)

    def first(self) -> List[Planned]:
        """Each client's first request, its answer cut to a residual life."""
        return [self._take(c, self._first[c]) for c in range(self.clients)]

    def next(self, client: int) -> Planned:
        """The request a client sends once its previous one completes."""
        return self._take(client, None)


def make(mix: dict, seed: int, vocab_ids: int, slots: int,
         segments: List[float]):
    """The generator a mix file describes; `segments` are the lengths of
    the schedule's parts (open loops)."""
    if mix["loop"] == "open":
        return OpenLoop(mix, seed, vocab_ids, segments)
    if mix["loop"] == "closed":
        return ClosedLoop(mix, seed, vocab_ids,
                          int(mix["clients_per_slot"]) * slots)
    raise ValueError(f"unknown loop {mix['loop']!r}")
