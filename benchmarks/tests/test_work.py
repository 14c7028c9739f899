"""``work.py``'s FLOPs and bytes against counts made by hand."""

import json
import os

import pytest

from harness import work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_gpt2_medium_one_layer_by_hand():
    cfg = dict(config("gpt2-medium"), n_layer=1)
    # one block at d=1024, ff=4096: qkv 1024x3072, proj 1024x1024, up 1024x4096, down 4096x1024
    body = 1024 * 3072 + 1024 * 1024 + 1024 * 4096 + 4096 * 1024
    assert work.lm_body_params(cfg) == body == 12_582_912
    # 10 tokens through the block, 3 through the head, 55 (query, key) pairs
    by_hand = 2 * body * 10 + 2 * 50257 * 1024 * 3 + 4 * 1024 * 55
    assert work.lm_forward_flops(cfg, 10, 3, work.causal_pairs(10)) == by_hand
    # K and V of one token in bfloat16: 2 tensors x 1024 x 2 bytes
    assert work.kv_bytes_per_token(cfg) == 4096


def test_gpt2_medium_parameter_count():
    n = work.lm_param_count(config("gpt2-medium"))
    # 354.8M published for gpt2-medium; this block has no qkv/proj bias (24 x 4096 fewer)
    assert n == 354_823_168 - 24 * 4096


def test_grpo_step_is_five_forwards():
    cfg = config("gpt2-medium")
    one = work.lm_sequence_flops(cfg, 6, 256)
    assert work.grpo_step_flops(cfg, [6] * 32, [256] * 32) == pytest.approx(5 * 32 * one)


def test_roofline_names_its_bound():
    peaks = {"flops": 100.0, "bytes_per_s": 10.0}
    assert work.roofline_seconds(1000.0, 10.0, peaks) == (10.0, "compute")
    assert work.roofline_seconds(10.0, 1000.0, peaks) == (100.0, "memory")
