"""The benchmark's traffic generator: determinism, rounding, and the same
sizes on the same schedule for every seed."""
import numpy as np

from bench.common import traffic

MIX = {"driver": "open_loop_single",
       "arrivals": {"process": "poisson", "rate_rps": 2.0},
       "prompt": {"median": 512, "sigma": 0.8, "min": 64, "max": 2048,
                  "round_to": 128},
       "output": {"median": 160, "sigma": 0.7, "min": 16, "max": 512},
       "schedule_seed": 1}


def test_same_seed_same_inputs():
    a = traffic.build(MIX, 2**31 + 5, 30, 1000)
    b = traffic.build(MIX, 2**31 + 5, 30, 1000)
    assert len(a) == len(b) > 20
    for x, y in zip(a, b):
        assert x.offset_s == y.offset_s and x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


def test_prompts_rounded_up_to_128_and_clamped():
    items = traffic.build(MIX, 7, 60, 1000)
    lens = [it.prompt_len for it in items]
    assert all(n % 128 == 0 and 128 <= n <= 2048 for n in lens)
    assert all(16 <= it.max_new <= 512 for it in items)
    assert traffic.round_up(np.array([1, 128, 129]), 128).tolist() == \
        [128, 128, 256]
    assert traffic.max_seq_len(MIX) == 2048 + 512


def test_seeds_share_sizes_and_schedule_and_differ_in_tokens():
    a = traffic.build(MIX, 1, 30, 1000)
    b = traffic.build(MIX, 2**31 + 2, 30, 1000)
    assert [(x.offset_s, x.prompt_len, x.max_new) for x in a] == \
        [(y.offset_s, y.prompt_len, y.max_new) for y in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


def test_schedule_seed_changes_the_sizes():
    a = traffic.build(MIX, 1, 30, 1000)
    b = traffic.build(dict(MIX, schedule_seed=2), 1, 30, 1000)
    assert [(x.prompt_len, x.max_new) for x in a] != \
        [(y.prompt_len, y.max_new) for y in b]


def test_poisson_keeps_its_rate():
    mix = dict(MIX, arrivals={"process": "poisson", "rate_rps": 4.0})
    n = len(traffic.arrival_offsets(mix, 500))
    assert 0.9 * 2000 < n < 1.1 * 2000


def test_a_faster_rate_serves_the_same_requests_closer_together():
    slow = traffic.build(MIX, 3, 30, 1000)
    fast = traffic.build(dict(MIX, arrivals={"process": "poisson",
                                             "rate_rps": 4.0}), 3, 30, 1000)
    assert len(fast) > len(slow)
    for x, y in zip(slow, fast):
        assert (x.prompt_len, x.max_new) == (y.prompt_len, y.max_new)
        assert abs(x.offset_s - 2 * y.offset_s) < 1e-9
