"""Statistics over whole windows, and traffic that repeats per seed."""
from __future__ import annotations

import numpy as np
import pytest

import chipbench_tiny  # noqa: F401  (puts chipbench on the path)
from chipbench import stats, traffic


def test_percentile_uses_every_sample():
    gaps = list(range(1, 101))                  # 1..100
    assert stats.percentile(gaps, 90) == pytest.approx(90.1)
    assert stats.percentile(gaps, 50) == pytest.approx(50.5)
    assert stats.percentile([], 90) is None


def test_rate_is_work_over_the_whole_window():
    assert stats.rate(120, 40.0) == 3.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_window_gaps_keep_only_pairs_inside_the_window():
    stamps = [0.5, 1.0, 1.5, 2.5, 4.0]
    assert stats.window_gaps(stamps, 1.0, 3.0) == [0.5, 1.0]


def _mix(**kw):
    raw = dict(chipbench_tiny.MIX, name="m", clients=2, max_batch=2)
    raw.update(kw)
    return traffic.Mix.from_dict(raw)


def test_same_seed_same_requests():
    a = traffic.make_requests(_mix(), 512, 2**33 + 1)
    b = traffic.make_requests(_mix(), 512, 2**33 + 1)
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]


def test_every_seed_gets_the_same_lengths_in_another_order():
    mix = _mix()
    a = traffic.make_requests(mix, 512, 1)
    b = traffic.make_requests(mix, 512, 2)
    for lo in range(0, mix.requests, mix.block):
        blk = slice(lo, lo + mix.block)
        assert sorted(len(r.prompt) for r in a[blk]) == \
            sorted(len(r.prompt) for r in b[blk])
        assert sorted(r.max_new_tokens for r in a[blk]) == \
            sorted(r.max_new_tokens for r in b[blk])
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert set(len(r.prompt) for r in a) <= set(traffic.prompt_lengths(mix))


def test_lengths_stay_in_their_band():
    q = traffic.quantile_lengths({"median": 128, "sigma": 0.8, "lo": 32,
                                  "hi": 512}, 8)
    assert q.min() >= 32 and q.max() <= 512
    assert list(q) == sorted(q)
    assert np.median(q) == pytest.approx(128, rel=0.1)


def test_open_loop_due_times_repeat_per_seed():
    mix = _mix(loop="open", arrivals={"process": "bursty", "rate": 2.0,
                                      "burst_size": 4.0})
    a = [r.due_s for r in traffic.make_requests(mix, 512, 5)]
    b = [r.due_s for r in traffic.make_requests(mix, 512, 5)]
    assert a == b and a == sorted(a) and a[0] >= 0


def test_copied_generators_agree_with_the_program():
    from repro.core.timing import poisson_arrivals
    from repro.serve.workload import bursty_arrivals
    assert traffic.poisson_arrivals(3.0, 50, seed=4) == \
        poisson_arrivals(3.0, 50, seed=4)
    assert traffic.bursty_arrivals(3.0, 50, seed=4, burst_size=3.0) == \
        bursty_arrivals(3.0, 50, seed=4, burst_size=3.0)


@pytest.mark.parametrize("bad", [dict(loop="sideways"), dict(clients=0),
                                 dict(loop="open"), dict(cache_window=20)])
def test_malformed_mix_is_refused(bad):
    with pytest.raises(ValueError):
        _mix(**bad)
