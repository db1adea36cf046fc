"""The client-side arithmetic: pooled tails, censoring, rates."""
import pytest

from bench import stats
from bench.stats import Record


def _steady(n_requests=10, tokens=10, step=0.05, start=0.0):
    recs = []
    for i in range(n_requests):
        s = start + 0.01 * i
        recs.append(Record(rid=i, prompt_len=8, max_new=tokens, start=s,
                           submitted=s,
                           token_times=[s + 0.1 + step * k
                                        for k in range(tokens)]))
    return recs


def _stall(recs, at, seconds):
    for r in recs:
        r.token_times = [t + seconds if t >= at else t
                         for t in r.token_times]
    return recs


def test_one_stall_moves_the_pooled_tails():
    w0, w1 = 0.0, 10.0
    calm = _steady()
    stalled = _stall(_steady(), at=0.25, seconds=2.0)
    itl_calm = stats.percentile(stats.itl_ms(calm, w0, w1), 95)
    itl_stall = stats.percentile(stats.itl_ms(stalled, w0, w1), 95)
    assert itl_calm == pytest.approx(50.0)
    assert itl_stall > 1000.0
    # a stall before the first tokens delays every first token
    late = _stall(_steady(), at=0.0, seconds=2.0)
    assert stats.percentile(stats.ttft_ms(calm, w0, w1), 90) == \
        pytest.approx(100.0)
    assert stats.percentile(stats.ttft_ms(late, w0, w1), 90) == \
        pytest.approx(2100.0)


def test_a_censored_ttft_counts_at_its_wait_so_far():
    w0, w1 = 0.0, 1.0
    done = Record(rid=0, prompt_len=8, max_new=4, start=0.2, submitted=0.2,
                  token_times=[0.3])
    waiting = Record(rid=1, prompt_len=8, max_new=4, start=0.5,
                     submitted=0.5)
    late = Record(rid=2, prompt_len=8, max_new=4, start=0.6, submitted=0.6,
                  token_times=[1.5])
    before = Record(rid=3, prompt_len=8, max_new=4, start=-0.5,
                    submitted=-0.5, token_times=[0.1])
    got = stats.ttft_ms([done, waiting, late, before], w0, w1)
    assert got == pytest.approx([100.0, 500.0, 400.0])
    # served on past the window, the late first token is waited for
    got = stats.ttft_ms([done, waiting, late, before], w0, w1, until=2.0)
    assert got == pytest.approx([100.0, 1500.0, 900.0])


def test_gaps_and_tokens_count_inside_the_window():
    r = Record(rid=0, prompt_len=8, max_new=5, start=0.0, submitted=0.0,
               token_times=[0.5, 1.0, 1.0, 1.5, 2.5])
    assert stats.itl_ms([r], 0.9, 2.0) == pytest.approx([500.0, 0.0, 500.0])
    assert stats.tokens_in([r], 0.9, 2.0) == 3
    assert stats.percentile([], 90) is None
