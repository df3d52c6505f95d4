import json
import os

import numpy as np
import pytest

from bench.harness import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mix(name):
    with open(os.path.join(BENCH, "mixes", name + ".json")) as f:
        return json.load(f)


def test_schedule_is_the_same_set_for_every_seed_in_another_order():
    mix = {"rate_rps": 40.0}
    a_due, a_fr = traffic.open_loop_schedule(mix, 10.0, 256, seed=1)
    b_due, b_fr = traffic.open_loop_schedule(mix, 10.0, 256,
                                             seed=3_000_000_019)
    assert len(a_due) == len(b_due) == 400
    assert np.all(np.diff(a_due) >= 0) and a_due[0] == 0 and a_due[-1] < 10
    ga, gb = np.sort(np.diff(a_due)), np.sort(np.diff(b_due))
    # one gap (the first) differs between the two orders at most
    assert np.allclose(ga[5:-5], gb[5:-5], rtol=0.2)
    assert not np.array_equal(a_fr, b_fr)
    assert np.array_equal(np.sort(a_fr), np.sort(b_fr))
    again, _ = traffic.open_loop_schedule(mix, 10.0, 256, seed=1)
    assert np.array_equal(a_due, again)


def test_gaps_look_exponential_at_the_rate():
    due, _ = traffic.open_loop_schedule({"rate_rps": 100.0}, 20.0, 8, seed=5)
    gaps = np.diff(due)
    assert gaps.mean() == pytest.approx(0.01, rel=0.02)
    assert gaps.std() == pytest.approx(0.01, rel=0.1)      # cv of 1


def test_bursts_keep_the_count_and_pile_arrivals_into_the_on_phase():
    mix = {"rate_rps": 50.0,
           "burst": {"on_s": 1.0, "off_s": 3.0, "factor": 3.0}}
    due, _ = traffic.open_loop_schedule(mix, 20.0, 8, seed=5)
    assert len(due) == 1000 and due.max() <= 20.0
    on = np.mean((due % 4.0) < 1.0)
    assert on == pytest.approx(0.75, abs=0.03)             # 3x for 1/4


def test_pool_sizes_follow_the_mix_and_only_the_order_moves():
    pools = _mix("pools_serving")
    a = traffic.pool_sizes(pools["small"], seed=1)
    b = traffic.pool_sizes(pools["small"], seed=2)
    assert sorted(a) == sorted(b) and a != b and len(a) == 256
    assert sum(1 for s in a if s == 1) == 179
    assert sum(1 for s in a if 2 <= s <= 64) == 51
    assert sum(1 for s in a if 65 <= s <= 1000) == 26
