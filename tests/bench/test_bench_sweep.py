"""The knee sweep's test of a sustained rate: TTFT does not grow across
the window."""
from bench import sweep


class Rq:
    def __init__(self, arrival, ttft):
        self.arrival_time, self._ttft = arrival, ttft

    def ttft(self):
        return self._ttft


def test_thirds_take_the_median_of_each_third():
    reqs = [Rq(t, 0.1 * (1 + t)) for t in range(30)]
    med = sweep.thirds(reqs, 0.0, 30.0)
    assert [round(m, 6) for m in med] == [0.55, 1.55, 2.55]


def test_growing_ttft_is_not_sustained_and_flat_ttft_is():
    flat = [Rq(t, 0.5 + 0.01 * (t % 3)) for t in range(30)]
    grow = [Rq(t, 0.5 + 0.2 * t) for t in range(30)]
    unloaded = sweep.thirds(flat, 0.0, 30.0)
    assert sweep.sustained(sweep.thirds(flat, 0.0, 30.0), unloaded)
    assert not sweep.sustained(sweep.thirds(grow, 0.0, 30.0), unloaded)
    assert not sweep.sustained([0.5, 0.6, None], unloaded)


def test_growth_is_judged_against_the_unloaded_rate():
    # long prompts fall in the last third: TTFT there is twice the first
    # third's already without load, and that alone is no growth
    unloaded = [0.2, 0.3, 0.4]
    assert sweep.sustained([0.3, 0.5, 0.7], unloaded)
    assert not sweep.sustained([0.3, 0.9, 1.9], unloaded)
