"""The traffic generator reproduces its schedule from a seed."""

import json
import os

import numpy as np
import pytest

import benchtest
import traffic

MIXES = ("chat", "tiny")


def _mix(name):
    """A mix file of the benchmark, or ``tiny``: the tests' short mix with
    uniform outputs."""
    if name == "tiny":
        return dict(benchtest.TINY_MIX, arrivals={"process": "poisson"})
    return json.load(open(os.path.join(benchtest.BENCH, "traffic",
                                       f"{name}.json")))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a = traffic.schedule(_mix(name), 3.0, 20.0, 3000000019, 50257, 1024)
    b = traffic.schedule(_mix(name), 3.0, 20.0, 3000000019, 50257, 1024)
    assert np.array_equal(a.due, b.due)
    assert np.array_equal(a.prompt_len, b.prompt_len)
    assert np.array_equal(a.max_new, b.max_new)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_sizes_and_gaps(name):
    """Two seeds send the same sizes and gaps, in another order."""
    a = traffic.schedule(_mix(name), 3.0, 20.0, 11, 50257, 1024)
    b = traffic.schedule(_mix(name), 3.0, 20.0, 2 ** 31 + 5, 50257, 1024)
    assert len(a) == len(b) == int(3.0 * 20.0 * traffic.FILL)
    assert sorted(a.prompt_len) == sorted(b.prompt_len)
    assert sorted(a.max_new) == sorted(b.max_new)
    assert not np.array_equal(a.prompt_len, b.prompt_len)
    # each schedule uses all but one of the same n gaps
    ga, gb = (set(np.round(np.diff(x.due), 9)) for x in (a, b))
    assert len(ga & gb) >= len(a) - 2


@pytest.mark.parametrize("name", MIXES)
def test_lengths_and_arrivals_within_bounds(name):
    mix = _mix(name)
    s = traffic.schedule(mix, 5.0, 30.0, 7, 50257, 1024)
    assert s.prompt_len.min() >= mix["prompt"]["min"]
    assert s.prompt_len.max() <= mix["prompt"]["max"]
    assert s.max_new.min() >= mix["output"]["min"]
    assert s.max_new.max() <= mix["output"]["max"]
    assert (s.prompt_len + s.max_new).max() <= 1024
    assert s.due[0] == 0.0 and np.all(np.diff(s.due) >= 0)
    assert s.due[-1] < 30.0
    assert all(len(p) == n for p, n in zip(s.prompts, s.prompt_len))
    assert all(p.dtype == np.int32 and p.max() < 50257 for p in s.prompts)


def test_chat_follows_the_mix():
    mix = _mix("chat")
    s = traffic.schedule(mix, 10.0, 40.0, 1, 50257, 1024)
    assert abs(np.median(s.prompt_len) - mix["prompt"]["median"]) <= 3
    assert abs(np.median(s.max_new) - mix["output"]["median"]) <= 3
    # the LMSYS-Chat-1M means the mix was fitted to, after clipping
    assert abs(s.prompt_len.mean() - 69.5) <= 3
    assert abs(s.max_new.mean() - 214.5) <= 5
    # Poisson: mean gap 1/rate
    assert abs(np.diff(s.due).mean() - 0.1) < 0.01


def test_uniform_lengths_cover_their_range():
    dist = {"dist": "uniform", "min": 8, "max": 32}
    x = traffic.lengths(dist, (np.arange(250) + 0.5) / 250)
    assert x.min() == 8 and x.max() == 32
    assert sorted(set(x)) == list(range(8, 33))


def test_unknown_process_or_distribution_is_refused():
    with pytest.raises(ValueError):
        traffic.schedule(dict(_mix("chat"), arrivals={"process": "bursty"}),
                         1.0, 10.0, 1, 50257, 1024)
    with pytest.raises(ValueError):
        traffic.lengths({"dist": "zipf", "min": 1, "max": 9},
                        np.array([0.5]))


def test_output_lengths_are_every_seeds():
    """Set-up warms the finish-time gather of each of these lengths, so
    they are exactly the window's, whatever the seed."""
    want = traffic.window_lengths(_mix("chat"), "output", 2.8, 51.0)
    assert len(want) == 139 and want.min() >= 16 and want.max() <= 640
    for seed in (1, 2 ** 31 + 5):
        s = traffic.schedule(_mix("chat"), 2.8, 51.0, seed, 50257, 1024)
        assert sorted(s.max_new) == sorted(want)


def test_mix_that_cannot_fit_is_refused():
    with pytest.raises(ValueError):
        traffic.schedule(_mix("chat"), 1.0, 10.0, 1, 50257, 256)


def test_warm_plan():
    """Every wave size at the first bucket and at buckets with a fifth of
    the prompts or more; waves of up to 16 rows at the rarer ones."""
    import harness

    def width(n):
        return max(8, 1 << (n - 1).bit_length())
    lens = traffic.window_lengths(_mix("chat"), "prompt", 2.8, 51.0)
    plan = harness.warm_plan(lens, width, 32, seq_axis=True)
    assert sorted(plan) == [16, 32, 64, 128, 256, 512]
    assert [len(ks) for _, ks in plan.values()] == [32, 32, 32, 32, 16, 16]
    assert all(width(n) == b for b, (n, _) in plan.items())
    # a state with no sequence axis: one full wave past the first bucket
    plan = harness.warm_plan(lens, width, 32, seq_axis=False)
    assert [ks for _, ks in plan.values()][1:] == [(32,)] * 5
