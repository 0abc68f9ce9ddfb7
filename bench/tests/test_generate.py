"""The traffic generator: a seed fixes the requests, another seed orders
the same work differently."""
import json
import pathlib

import numpy as np
import pytest

import generate

TRAFFIC = pathlib.Path(__file__).resolve().parents[1] / "traffic"
SERVE = sorted(p for p in TRAFFIC.glob("*.json")
               if json.loads(p.read_text())["kind"] == "serve")
PLAN = sorted(p for p in TRAFFIC.glob("*.json")
              if json.loads(p.read_text())["kind"] == "plan")


def _serve(path, seed):
    return generate.serve_requests(json.loads(path.read_text()), seed, 20.0,
                                   vocab=100352, capacity=2560)


@pytest.mark.parametrize("path", SERVE, ids=lambda p: p.stem)
def test_serve_mix_same_seed_same_requests(path):
    a, b = _serve(path, 2**33 + 5), _serve(path, 2**33 + 5)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x["due"] == y["due"] and x["max_new"] == y["max_new"]
        assert np.array_equal(x["prompt"], y["prompt"])


@pytest.mark.parametrize("path", SERVE, ids=lambda p: p.stem)
def test_serve_mix_other_seed_same_work_other_order(path):
    mix = json.loads(path.read_text())
    a, b = _serve(path, 1), _serve(path, 2)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert not np.array_equal(a[0]["prompt"][:8], b[0]["prompt"][:8])
    # the same multiset of sizes, on the mix's grid and inside its bounds
    assert sorted(len(r["prompt"]) for r in a) == sorted(len(r["prompt"]) for r in b)
    assert sorted(r["max_new"] for r in a) == sorted(r["max_new"] for r in b)
    assert abs(a[-1]["due"] - b[-1]["due"]) < 20.0
    p = mix["prompt_tokens"]
    for r in a:
        assert len(r["prompt"]) % p["grid"] == 0
        assert p["min"] <= len(r["prompt"]) <= p["max"]
        assert len(r["prompt"]) + r["max_new"] <= 2559
    assert len(a) == round(mix["rate_per_s"] * 20.0)


def test_lognormal_quantiles_hit_the_median():
    x = generate.lognormal_quantiles(
        {"median": 100, "sigma": 1.0, "min": 1, "max": 10**6}, 101)
    assert x[50] == pytest.approx(100)
    assert np.all(np.diff(x) > 0)


@pytest.mark.parametrize("path", PLAN, ids=lambda p: p.stem)
def test_plan_order_same_seed_same_other_seed_other(path):
    mix = json.loads(path.read_text())
    costs = list(range(len(mix["problems"])))
    a = generate.plan_order(costs, mix["strata"], 2**40 + 1, 60)
    assert a == generate.plan_order(costs, mix["strata"], 2**40 + 1, 60)
    assert a != generate.plan_order(costs, mix["strata"], 2**40 + 2, 60)


def test_plan_order_rounds_take_one_of_each_stratum():
    costs = [5, 1, 4, 2, 3, 6, 8, 7]          # strata of sorted pairs
    order = generate.plan_order(costs, 4, 9, 8)
    for r in (0, 1):
        rnd = [costs[i] for i, k in order if k == r]
        assert sorted((c - 1) // 2 for c in rnd) == [0, 1, 2, 3]
        # cheap and dear strata alternate: 0, 3, 1, 2
        assert [(c - 1) // 2 for c in rnd] == [0, 3, 1, 2]
    assert sorted(i for i, _ in order) == list(range(8))


def test_zigzag():
    assert generate.zigzag(5) == [0, 4, 1, 3, 2]
    assert generate.zigzag(1) == [0]
