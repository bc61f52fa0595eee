"""The one traffic generator: seeded, reproducible, the same work on
every seed."""
import numpy as np
import pytest

from traffic import generator

BIG = 2**31 + 12345


@pytest.mark.parametrize("name", ["chat", "offline-long"])
def test_same_seed_same_schedule(name):
    mix = generator.load(name)
    a = generator.schedule(mix, BIG, 20.0, 1000)
    b = generator.schedule(mix, BIG, 20.0, 1000)
    np.testing.assert_array_equal(a.arrival_s, b.arrival_s)
    np.testing.assert_array_equal(a.max_new, b.max_new)
    for x, y in zip(a.prompts, b.prompts):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("name", ["chat", "offline-long"])
def test_every_seed_gets_the_same_work(name):
    mix = generator.load(name)
    a = generator.schedule(mix, 7, 20.0, 1000)
    b = generator.schedule(mix, BIG, 20.0, 1000)
    np.testing.assert_array_equal(a.max_new, b.max_new)
    np.testing.assert_array_equal([p.size for p in a.prompts],
                                  [p.size for p in b.prompts])
    np.testing.assert_array_equal(a.arrival_s, b.arrival_s)
    assert any(not np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))


@pytest.mark.parametrize("name", ["chat", "offline-long"])
def test_lengths_within_the_mix(name):
    mix = generator.load(name)
    s = generator.schedule(mix, 2**40 + 3, 30.0, 1000)
    lens = np.array([p.size for p in s.prompts])
    assert lens.min() >= mix["prompt"]["min"]
    assert lens.max() <= mix["prompt"]["max"]
    assert s.max_new.min() >= mix["output"]["min"]
    assert s.max_new.max() <= mix["output"]["max"]
    assert (lens + s.max_new).max() <= mix["max_len"]
    assert np.all(np.diff(s.arrival_s) >= 0)


def test_poisson_rate_and_enough_arrivals():
    """Every seed's window holds the rate's count of arrivals, all inside
    it, at the same times."""
    mix = generator.load("chat")
    a = generator.schedule(mix, 3, 60.0, 1000)
    b = generator.schedule(mix, BIG, 60.0, 1000)
    for s in (a, b):
        assert len(s) == round(mix["rate_per_s"] * 60.0)
        assert 0.0 <= s.arrival_s[0] and s.arrival_s[-1] < 60.0
    np.testing.assert_array_equal(a.arrival_s, b.arrival_s)
    gaps = np.diff(a.arrival_s)
    assert 1 / gaps.mean() == pytest.approx(mix["rate_per_s"], rel=0.2)


def test_warm_up_covers_every_prefill_bucket():
    mix = {"prompt": {"min": 32, "max": 512}}
    lens = generator.prefill_lengths(mix)
    buckets = {max(8, 1 << (n - 1).bit_length()) for n in lens}
    want = {max(8, 1 << (n - 1).bit_length()) for n in range(32, 513)}
    assert buckets == want


def test_unknown_mix_is_an_error():
    with pytest.raises(FileNotFoundError):
        generator.load("no-such-mix")
