"""The looped-decoder cell run end to end on the CPU at a tiny size, through
the same code path as on the chip (only the harness's look for a chip is
stubbed): the last line's keys with and without ``--trace``, the float8
control and the 3-loop fault reading as not correct, and
``harness/work_decoder.py``'s counts checked by hand at the published sizes.

Tolerances at the tiny size (2 layers x 4 loops, 32 wide, bfloat16
against the float32 reference): the served tokens' mean log-prob gap reads
~1e-3 and the worst ~1e-2; the float8 control reads ~1e-2 in the mean and
a run with 3 loops of 4 ~0.05. The limit 4e-3 lies between.
"""

import json
import os

import pytest

import tiny
from harness import work_decoder as work

CELL = "ouro-2.6b.rollout-kvbound"
OURO_TINY = {
    "vocab_size": 211, "hidden_size": 32, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 64, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1,
}
LIMITS = {"lp_gap_mean": 4e-3, "lp_gap_max": 0.1, "budget_gap": 0.0}


def edit(traffic):
    """The cell's traffic at a size a test holds, the pool still tight: 4
    slots whose requests need up to 9 blocks each over 20 usable blocks."""
    traffic["engine"].update(n_slots=4, n_blocks=21, block_size=4, max_seq_len=64, prompt_buckets=[8, 16])
    traffic["requests"].update(count=64, group=4, prompt={"dist": "uniform", "low": 4, "high": 12},
                               output={"dist": "lognormal", "median": 8, "sigma": 0.6, "low": 2, "high": 24})
    traffic.update(outstanding=8, warm_seconds=1.0, check_requests=6, trace_seconds=0.2, limits=dict(LIMITS))


def run(monkeypatch, tmp_path, **kw):
    return tiny.run_cell(monkeypatch, tmp_path, CELL, OURO_TINY, traffic_edit=edit, **kw)


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_end_to_end_and_last_line(monkeypatch, tmp_path, trace):
    rc, last, text = run(monkeypatch, tmp_path, trace=trace)
    assert rc == 0
    assert len(text.strip().splitlines()) == 1
    keys = list(last)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics", "device"] and keys[-1] == "compared"
    assert last["correct"] is True, last["compared"]
    assert last["attempted"] > 0 and last["failed"] == 0
    if trace:
        # counters need no device: their readers found something to read
        # (the kernel rooflines need a device trace and are left out here)
        assert {"step_mfu.gen.looped", "step_mbu.gen.looped", "kv_pool_occupancy.gen",
                "slot_occupancy.gen"} <= set(last["metrics"])
        assert 0 < last["metrics"]["kv_pool_occupancy.gen"]["value"] <= 100
        assert {"busy_s", "window_s"} <= set(last["device"]) and "breakdown" in last
    else:
        assert set(last["metrics"]) == {"gen_tokens_per_s", "setup_s"}
    for c in last["compared"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("kind", ["control_fp8", "fault_loops_minus_one"])
def test_control_and_fault_read_not_correct(monkeypatch, tmp_path, kind):
    seen = {}

    def spy(self):
        from harness import compare

        ref = self.reference_readings()
        other = (self.reference_readings(quant="fp8") if kind == "control_fp8"
                 else self.reference_readings(fault="loops_minus_one"))
        seen["other"] = compare.judge(self.gaps(other, ref), self.traffic["limits"], self.log)
        return compare.judge(self.gaps(self.program_readings(), ref), self.traffic["limits"], self.log)

    real_load = tiny.bench_run.load_module

    def load(kind_, name):
        mod = real_load(kind_, name)
        if kind_ == "drivers":
            mod.Driver.check = spy
        return mod

    monkeypatch.setattr(tiny.bench_run, "load_module", load)
    rc, last, _ = run(monkeypatch, tmp_path)
    assert rc == 0 and last["correct"] is True, last
    assert [k for k, c in seen["other"].items() if c["value"] > c["limit"]], seen["other"]


def test_pool_not_slots_bounds_the_tiny_cell(monkeypatch, tmp_path):
    """The tiny cell keeps the cell's character: admissions wait for blocks."""
    seen = {}
    real_load = tiny.bench_run.load_module

    def load(kind, name):
        mod = real_load(kind, name)
        if kind == "drivers":
            real_release = mod.Driver.release

            def release(self):
                seen.update(self.eng.metrics_snapshot())
                real_release(self)

            mod.Driver.release = release
        return mod

    monkeypatch.setattr(tiny.bench_run, "load_module", load)
    rc, last, _ = run(monkeypatch, tmp_path)
    assert rc == 0 and last["correct"] is True
    assert seen["admissions_deferred_kv"] > 0 and seen["cache_entries"] == 8
    assert seen["loop_steps_run"] == 4 * seen["tokens_generated"]


# -- the arithmetic, by hand, at the published sizes -------------------------------


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(tiny.BENCH, "configs", "ouro-2.6b.json")) as f:
        return json.load(f)


def test_published_counts(published):
    c = published
    # a layer: qkv 3 x 2048 x 2048, o 2048 x 2048, gate/up/down 3 x 2048 x 5632
    assert work.layer_matrix_params(c) == 12_582_912 + 4_194_304 + 34_603_008 == 51_380_224
    # + 4 gains of 2048 a layer; embedding and head 2 x 49152 x 2048; final gain; gate 2048 + 1
    assert work.param_count(c) == 48 * (51_380_224 + 8_192) + 201_326_592 + 2_048 + 2_049 == 2_667_974_657
    assert work.cache_entries(c) == 192
    assert work.kv_bytes_per_token(c) == 4 * 48 * 2 * 16 * 128 * 2 == 1_572_864
    # a decode step: the layers' 4.93 GB four times, the head's 0.2 GB once
    assert work.decode_weight_bytes(c) == 4 * 48 * 51_380_224 * 2 + 49_152 * 2_048 * 2 == 19_931_332_608
    assert work.decode_step_bytes(c, 1000) == 19_931_332_608 + 1_572_864_000


def test_published_flops_and_attention_cost(published):
    c = published
    # one token through 4 loops of 48 layers and the head, attending 100 keys in each of 192 entries
    body = 2 * 4 * 48 * 51_380_224
    head = 2 * 49_152 * 2_048
    attn = 4 * 192 * 2_048 * 100
    assert work.forward_flops(c, 1, 1, 100) == body + head + attn == 19_730_006_016 + 201_326_592 + 157_286_400
    # one entry's call: 16 rows against 1,000 live keys in all
    flops, byts = work.decode_attention_cost(c, 1000, 16)
    assert flops == 4 * 2_048 * 1000
    assert byts == 2 * 2_048 * 2 * 1000 + 2 * 16 * 2_048 * 2
