"""The generator's schedule is a function of the seed and the traffic
file alone: Poisson arrivals, lengths, budgets and token ids all come
from the seed."""

from benchmark.harness import loadgen

TRAFFIC = {"rate_per_s": 12.0,
           "prompt_median": 128, "prompt_sigma": 0.8, "prompt_min": 16,
           "prompt_max": 512, "budget_median": 64, "budget_sigma": 0.7,
           "budget_min": 8, "budget_max": 256}


def _shape(reqs):
    return [(r["due_s"], len(r["prompt"]), r["budget"]) for r in reqs]


def test_same_seed_same_schedule():
    a = loadgen.schedule(TRAFFIC, 2**31 + 5, 30.0, 50257)
    b = loadgen.schedule(TRAFFIC, 2**31 + 5, 30.0, 50257)
    assert a == b


def test_another_seed_is_another_schedule():
    a = loadgen.schedule(TRAFFIC, 1, 30.0, 50257)
    b = loadgen.schedule(TRAFFIC, 2, 30.0, 50257)
    assert _shape(a) != _shape(b)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]


def test_arrivals_are_poisson_at_the_rate():
    counts = [len(loadgen.schedule(TRAFFIC, seed, 30.0, 50257))
              for seed in range(40)]
    mean = sum(counts) / len(counts)
    var = sum((c - mean) ** 2 for c in counts) / (len(counts) - 1)
    assert 350 < mean < 370          # 12 a second for 30 s is 360
    assert 0.4 < var / mean < 2.0    # a Poisson count: variance = mean
    assert len(loadgen.schedule({**TRAFFIC, "rate_per_s": 6.0}, 1, 30.0,
                                50257)) < 230


def test_every_request_is_due_inside_the_window_and_in_range():
    reqs = loadgen.schedule(TRAFFIC, 7, 30.0, 50257)
    assert all(0 < r["due_s"] < 30.0 for r in reqs)
    assert [r["due_s"] for r in reqs] == sorted(r["due_s"] for r in reqs)
    assert all(16 <= len(r["prompt"]) <= 512 for r in reqs)
    assert all(8 <= r["budget"] <= 256 for r in reqs)
    assert all(0 <= t < 50257 for r in reqs for t in r["prompt"])
