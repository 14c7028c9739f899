"""The request generator: every seed gets the same sizes in the same order."""

import json
import os

from harness import requests

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spec():
    with open(os.path.join(BENCH, "traffic", "rollout.json")) as f:
        return json.load(f)["requests"]


def sizes(pool):
    return [(len(r["prompt"]), r["new"]) for r in pool]


def test_every_seed_gets_the_same_sizes_in_the_same_order():
    a, b = (requests.make_pool(spec(), 50257, seed, 1024) for seed in (3, 2**31 + 5))
    assert len(a) == 128 and sizes(a) == sizes(b)
    assert all((x["prompt"] != y["prompt"]).any() for x, y in zip(a, b))  # the seed draws the token ids
    for r in a:
        assert 32 <= len(r["prompt"]) <= 128 and 16 <= r["new"] <= 768 and len(r["prompt"]) + r["new"] <= 1024


def test_a_group_shares_its_prompt():
    pool = requests.make_pool(spec(), 50257, 7, 1024)
    for i in range(0, len(pool), 8):
        assert all((r["prompt"] == pool[i]["prompt"]).all() and r["group"] == pool[i]["group"] for r in pool[i:i + 8])
