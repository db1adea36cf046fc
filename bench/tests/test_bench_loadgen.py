"""The load generator: seeds, rates and residual lives."""
import numpy as np
import pytest

from bench import loadgen

OPEN = {"loop": "open", "rate_per_s": 2.0,
        "prompt_len": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                       "min": 32, "max": 2048},
        "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.6,
                       "min": 16, "max": 512}}
CLOSED = {"loop": "closed", "clients_per_slot": 1, "pool": 64,
          "prompt_len": {"dist": "uniform", "min": 32, "max": 128},
          "output_len": {"dist": "uniform", "min": 128, "max": 256},
          "first_output": "residual_life"}


def _open(seed, horizon=100.0):
    return loadgen.make(OPEN, seed, 1000, 32, [horizon]).planned


def _closed(seed, clients=8):
    g = loadgen.make(CLOSED, seed, 1000, clients, 0)
    return g.first() + [g.next(c) for c in range(clients)]


@pytest.mark.parametrize("make", [_open, _closed])
def test_a_seed_reproduces_the_same_requests(make):
    seed = 3_000_000_017            # more than 31 bits
    a, b = make(seed), make(seed)
    assert [(p.rid, p.max_new, p.due, p.client) for p in a] == \
        [(p.rid, p.max_new, p.due, p.client) for p in b]
    assert all(np.array_equal(p.prompt, q.prompt) for p, q in zip(a, b))
    c = make(seed + 1)
    assert [p.max_new for p in a] != [p.max_new for p in c]


def test_every_seed_gets_the_same_sizes_in_another_order():
    a, b = _open(11), _open(12)
    assert sorted(len(p.prompt) for p in a) == \
        sorted(len(p.prompt) for p in b)
    assert sorted(p.max_new for p in a) == sorted(p.max_new for p in b)
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]


def test_every_seed_has_the_same_requests_in_its_window():
    def window(seed):
        plan = loadgen.make(OPEN, seed, 1000, 32, [10.0, 30.0, 5.0]).planned
        return [p for p in plan if 10.0 < p.due <= 40.0]

    a, b = window(1), window(2)
    assert len(a) == len(b) == 60
    assert sorted((len(p.prompt), p.max_new) for p in a) != \
        sorted((len(p.prompt), p.max_new) for p in b)   # pairs reshuffled
    assert sorted(len(p.prompt) for p in a) == \
        sorted(len(p.prompt) for p in b)
    assert sorted(p.max_new for p in a) == sorted(p.max_new for p in b)
    assert [p.rid for p in a] == list(range(20, 80))


def test_a_closed_loop_spreads_its_pool():
    g = loadgen.make(CLOSED, 4, 1000, 8, 0)
    out = np.asarray([g.next(0).max_new for _ in range(64)])
    assert sorted(out) == sorted(loadgen.stratified(
        64, CLOSED["output_len"], np.random.default_rng(0)))
    # any 8 consecutive requests cover the range: their mean stays near
    # the distribution's (192) where a shuffle would stray
    means = [out[i:i + 8].mean() for i in range(0, 57)]
    assert max(abs(m - 192) for m in means) < 12


def test_open_loop_due_times_follow_the_rate():
    plan = _open(5, horizon=500.0)
    due = np.asarray([p.due for p in plan])
    assert len(plan) == 1000                       # ceil(2.0 * 500)
    assert np.all(np.diff(due) > 0)
    gaps = np.diff(np.concatenate([[0.0], due]))
    assert due[-1] == pytest.approx(500.0)
    assert gaps.mean() == pytest.approx(0.5, rel=1e-9)
    # Poisson: exponential gaps have a standard deviation equal to the mean
    assert gaps.std() == pytest.approx(0.5, rel=0.1)
    in_first_100s = int((due <= 100.0).sum())
    assert abs(in_first_100s - 200) < 4 * np.sqrt(200)


def test_lengths_follow_the_mix():
    plan = _open(5, horizon=500.0)
    plen = np.asarray([len(p.prompt) for p in plan])
    assert plen.min() >= 32 and plen.max() <= 2048
    assert np.median(plen) == pytest.approx(512, rel=0.03)
    assert all(p.prompt.min() >= 1 and p.prompt.max() < 1000 for p in plan)


def test_residual_life_first_outputs():
    # lengths uniform on [a, b]: P(R = r) ~ P(L >= r), so
    # E[R] = E[L (L + 1)] / (2 E[L])
    a, b = 128, 256
    lengths = np.arange(a, b + 1)
    r = loadgen.residual_life(lengths, 20000, np.random.default_rng(0))
    want = (lengths * (lengths + 1)).mean() / (2 * lengths.mean())
    assert r.mean() == pytest.approx(want, rel=0.01)
    assert r.min() >= 1 and r.max() <= b
    # below a the density is flat: every r < a is as likely as r = 1
    hist = np.bincount(r, minlength=b + 1)
    assert hist[1:a].std() / hist[1:a].mean() < 0.1
    # the closed loop's first requests carry such residual lives
    g = loadgen.make(CLOSED, 9, 1000, 1000, 0)
    first = np.asarray([p.max_new for p in g.first()])
    assert first.mean() == pytest.approx(want, rel=0.02)
    assert all(p.client == c for c, p in enumerate(g.first()[:5]))
