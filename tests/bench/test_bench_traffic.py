"""The seeded open-loop generator (bench/traffic.py)."""
import json

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from bench import traffic

BIG_SEED = 2 ** 31 + 12345


def _mix(name):
    return json.loads((bench_tiny.BENCH / "traffic" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("mix", ["chat", "docs"])
def test_same_seed_same_requests(mix):
    a = traffic.generate(_mix(mix), 2.0, 20, BIG_SEED, 151936)
    b = traffic.generate(_mix(mix), 2.0, 20, BIG_SEED, 151936)
    assert len(a) == len(b) == 40
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("mix", ["chat", "docs"])
def test_lengths_in_range_and_work_fixed_across_seeds(mix):
    m = _mix(mix)
    runs = [traffic.generate(m, 3.0, 30, s, 151936) for s in (1, BIG_SEED)]
    for reqs in runs:
        P = [len(r.prompt) for r in reqs]
        O = [r.max_new for r in reqs]
        assert m["prompt"]["min"] <= min(P) and max(P) <= m["prompt"]["max"]
        assert m["output"]["min"] <= min(O) and max(O) <= m["output"]["max"]
        due = [r.due_s for r in reqs]
        assert due == sorted(due) and 0 <= due[0] and due[-1] < 30
        assert all(0 <= r.prompt.min() and r.prompt.max() < 151936
                   for r in reqs)
    # a seed changes the tokens, not the schedule of sizes and arrivals
    a, b = runs
    assert [(len(r.prompt), r.max_new, r.due_s) for r in a] == \
        [(len(r.prompt), r.max_new, r.due_s) for r in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [len(r.prompt) for r in a] != sorted(len(r.prompt) for r in a)


def test_lognormal_quantiles_follow_the_median():
    q = traffic.quantile_lengths({"dist": "lognormal", "median": 768,
                                  "sigma": 0.8, "min": 128, "max": 4096},
                                 101)
    assert q[50] == 768 and q[0] >= 128 and q[-1] <= 4096
    assert np.all(np.diff(q) >= 0)


def test_onoff_arrivals_fall_in_on_periods():
    mix = {"arrivals": {"kind": "onoff", "on_s": 1.0, "off_s": 3.0},
           "prompt": {"dist": "fixed", "value": 10},
           "output": {"dist": "uniform", "min": 2, "max": 4}}
    reqs = traffic.generate(mix, 2.0, 40, 7, 100)
    assert len(reqs) == 80
    assert all((r.due_s % 4.0) < 1.0 for r in reqs)


def test_shared_prefixes():
    mix = {"prompt": {"dist": "fixed", "value": 5},
           "output": {"dist": "fixed", "value": 3},
           "shared_prefix": {"groups": 2, "length": {"dist": "fixed",
                                                     "value": 32}}}
    reqs = traffic.generate(mix, 1.0, 10, 3, 1000)
    heads = {tuple(r.prompt[:32]) for r in reqs}
    assert len(heads) == 2 and all(len(r.prompt) == 37 for r in reqs)
    assert traffic.longest_request(mix) == 40


def test_release_page_counts():
    reqs = [traffic.TrafficRequest(0, 0.0, np.zeros(16, np.int32), 1),
            traffic.TrafficRequest(1, 0.0, np.zeros(16, np.int32), 2),
            traffic.TrafficRequest(2, 0.0, np.zeros(30, np.int32), 3)]
    # 16 tokens fill one page; a 17th (the first decoded token) needs two
    assert traffic.release_page_counts(reqs, 16) == [1, 2]
    # a preempted request frees any count up to its longest
    assert traffic.release_page_counts(reqs[2:], 8) == [1, 2, 3, 4]
