"""The correctness sample: requests that finished in the window, spread
over the engine's slots."""
import numpy as np

from bench import correct
from bench.stats import Record


def _done(rid, slot, finished, n_out=40, prompt_len=8):
    rec = Record(rid=rid, prompt_len=prompt_len, max_new=n_out, start=0.0,
                 submitted=0.0, slot=slot, finished=finished, status="done")
    served = (np.arange(prompt_len), list(range(n_out)))
    return rec, served


def _pick(pairs, window=(10.0, 20.0), seed=5):
    records = [r for r, _ in pairs]
    requests = {r.rid: s for r, s in pairs}
    return records, correct.pick(records, requests, seed, window)


def test_only_requests_finished_in_the_window_are_compared():
    pairs = [_done(0, 0, 5.0, n_out=400), _done(1, 1, 12.0),
             _done(2, 2, 25.0, n_out=400), _done(3, 3, 19.0)]
    _, sample = _pick(pairs)
    assert sorted(slot for _, _, slot in sample) == [1, 3]


def test_the_longest_comes_first_then_one_request_per_slot():
    pairs = [_done(i, i % 4, 11.0 + i * 0.1, n_out=10) for i in range(12)]
    pairs.append(_done(12, 2, 15.0, n_out=30))
    _, sample = _pick(pairs)
    slots = [slot for _, _, slot in sample]
    assert len(sample[0][1]) == 30 and slots[0] == 2
    assert sorted(slots[:4]) == [0, 1, 2, 3]
    # under MIN_TOKENS served tokens, so every finished request is taken
    assert len(sample) == 13


def test_the_sample_stops_at_max_requests_once_it_has_min_tokens():
    n = correct.MAX_REQUESTS
    pairs = [_done(i, i, 11.0 + i * 0.001, n_out=40) for i in range(2 * n)]
    _, sample = _pick(pairs)
    assert len(sample) == n and len({s for _, _, s in sample}) == n
    few = [_done(i, i % 2, 11.0 + i * 0.001, n_out=5) for i in range(200)]
    _, sample = _pick(few)
    assert len(sample) == max(n, correct.MIN_TOKENS // 5)


def test_the_same_seed_draws_the_same_sample():
    pairs = [_done(i, i % 8, 11.0 + i * 0.01) for i in range(30)]
    slots = [[s for _, _, s in _pick(pairs, seed=k)[1]] for k in (7, 7, 8)]
    assert slots[0] == slots[1] and slots[0] != slots[2]
