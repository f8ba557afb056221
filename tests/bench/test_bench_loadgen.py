"""The traffic generator: deterministic per seed, the mix's rate and shares
held exactly, and the same work for every seed in another order."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import loadgen  # noqa: E402

MIX = {"arrival": "poisson", "rate": 5.0, "steps": {"20": 1, "50": 3},
       "guidance": {"1.0": 0.5, "4.0": 0.5}}


def fields(reqs):
    return [(r.due, r.cond, r.steps, r.guidance, r.noise_seed)
            for r in reqs]


def test_same_seed_same_traffic():
    a = loadgen.poisson(MIX, 2**31 + 17, 40.0, 1000)
    b = loadgen.poisson(MIX, 2**31 + 17, 40.0, 1000)
    assert fields(a) == fields(b)
    assert fields(a) != fields(loadgen.poisson(MIX, 3, 40.0, 1000))


@pytest.mark.parametrize("seed", [0, 1, 99, 2**31 + 5])
def test_rate_and_shares_are_exact(seed):
    reqs = loadgen.poisson(MIX, seed, 40.0, 1000)
    due = np.array([r.due for r in reqs])
    assert len(reqs) == 200                       # 5 req/s for 40 s
    assert np.all(np.diff(due) >= 0) and 0 <= due[0] and due[-1] < 40.0
    assert sorted(r.steps for r in reqs) == [20] * 50 + [50] * 150
    assert sum(r.guidance == 1.0 for r in reqs) == 100
    assert all(0 <= r.cond < 1000 for r in reqs)
    # exponential gaps: their spread matches their mean
    gaps = np.diff(due)
    assert 0.8 < np.std(gaps) / np.mean(gaps) < 1.2


def test_seeds_share_the_arrivals():
    """Every seed's requests arrive at the same times; the seed deals the
    requests onto them."""
    a = loadgen.poisson(MIX, 1, 40.0, 10)
    b = loadgen.poisson(MIX, 2, 40.0, 10)
    assert [r.due for r in a] == [r.due for r in b]
    assert [r.steps for r in a] != [r.steps for r in b]
    assert sorted(r.steps for r in a) == sorted(r.steps for r in b)


def test_piecewise_bursts():
    mix = dict(MIX, segments=[[15, 2.0], [20, 10.0]], period_s=20)
    mix.pop("rate")
    reqs = loadgen.poisson(mix, 4, 40.0, 1000)
    assert len(reqs) == 2 * (15 * 2 + 5 * 10)
    due = np.array([r.due for r in reqs])
    burst = ((due % 20) >= 15).sum()
    assert burst == pytest.approx(100, abs=12)


def test_backlog_blocks_hold_the_shares():
    it = loadgen.backlog(MIX, 7, 1000, block=20)
    first = [next(it) for _ in range(40)]
    again = loadgen.backlog(MIX, 7, 1000, block=20)
    assert fields(first) == fields([next(again) for _ in range(40)])
    for blk in (first[:20], first[20:]):
        assert sorted(r.steps for r in blk) == [20] * 5 + [50] * 15
    assert [r.rid for r in first] == list(range(40))
    assert loadgen.max_steps(MIX) == 50
