"""Each driver run end to end on the CPU at a tiny size, through the same
code path as on the chip (only the harness's look for a chip is stubbed):
the last line's keys, each plain reference tied to its program, each
control reading as not correct, and the timed path broken underneath, once
for each fault a cell can have, reading as not correct.

Tolerances (the limits these tiny runs are held to) and their reasons:

- GRPO, bfloat16 activations against a float32 reference at a 2-layer, 32-wide
  model: the sampled tokens' log-probs agree to ~1e-3 (bfloat16 has 8 bits
  of mantissa, logits are O(1)); losses to ~4e-3 (the program's importance
  ratio carries that rounding, the reference's is exactly 1); gradient
  norms to ~1e-2 and the parameters' change to ~3e-2 by the worst leaf.
  Limits are 3-4 times those readings.
- Engine log-probs: the served tokens' mean gap reads ~5e-4 and the float8 control's
  ~3e-3 (which requests a 0.3 s window finishes follows the wall clock, so both move a
  little from run to run); limit 1.5e-3, between them.
"""

import numpy as np
import pytest

import tiny

GRPO_LIMITS = {"lp_gap_step1": 5e-3, "lp_gap_last": 5e-3, "loss_gap": 2e-2, "grad_norm_gap": 3e-2,
               "dparam_norm_gap": 0.1}
ENGINE_LIMITS = {"lp_gap_mean": 1.5e-3, "lp_gap_max": 3e-2, "budget_gap": 0.0}

CELLS = {
    "gpt2-medium.grpo": (tiny.GPT2_TINY, GRPO_LIMITS),
    "gpt2-medium.rollout": (tiny.GPT2_TINY, ENGINE_LIMITS),
}


def run(monkeypatch, tmp_path, cell, **kw):
    config, limits = CELLS[cell]

    def edit(traffic):
        traffic["limits"] = dict(limits)

    return tiny.run_cell(monkeypatch, tmp_path, cell, config, traffic_edit=edit, **kw)


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_end_to_end_and_last_line(monkeypatch, tmp_path, cell, trace):
    rc, last, text = run(monkeypatch, tmp_path, cell, trace=trace)
    assert rc == 0
    assert len(text.strip().splitlines()) == 1  # the result is the only line on standard output
    keys = list(last)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics", "device"] and keys[-1] == "compared"
    assert last["correct"] is True, last["compared"]
    assert last["attempted"] > 0 and last["failed"] == 0
    bench = tiny.bench_run.load_benchmark()
    kind = "per_layer" if trace else "end_to_end"
    listed = {m["name"] for m in tiny.bench_run.metrics_for(bench[kind], cell)}
    assert set(last["metrics"]) <= listed
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    if trace:
        assert {"busy_s", "window_s"} <= set(last["device"]) and "breakdown" in last
        # counters need no device: every reader of one found something to read
        assert any(n.startswith(("slot_occupancy", "step_mfu")) for n in last["metrics"])
    else:
        assert set(last["metrics"]) == listed and "setup_s" in last["metrics"]
    for c in last["compared"].values():
        assert c["value"] <= c["limit"]


# -- the control: the reference in the nearest lower precision is NOT correct ----


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_in_lower_precision_fails(monkeypatch, tmp_path, cell):
    config, limits = CELLS[cell]
    seen = {}

    def spy(self):
        ref = self.reference_readings()
        from harness import compare

        lim = self.traffic["limits"]
        seen["control"] = compare.judge(self.gaps(self.reference_readings(quant="fp8"), ref), lim, self.log)
        return compare.judge(self.gaps(self.program_readings(), ref), lim, self.log)

    real_load = tiny.bench_run.load_module

    def load(kind, name):
        mod = real_load(kind, name)
        if kind == "drivers":
            mod.Driver.check = spy
        return mod

    monkeypatch.setattr(tiny.bench_run, "load_module", load)
    rc, last, _ = run(monkeypatch, tmp_path, cell)
    assert rc == 0 and last["correct"] is True
    failed = [k for k, c in seen["control"].items() if c["value"] > c["limit"]]
    assert failed, seen["control"]


# -- faults planted under the timed path ------------------------------------------


def fault_grpo_state_unchanged(monkeypatch):
    from rl_tpu.trainers import GRPOTrainer

    def update(self, params, opt_state, batch, dm, poison=None):
        return params, opt_state, dm

    monkeypatch.setattr(GRPOTrainer, "_update_impl", update)


def fault_grpo_half_batch(monkeypatch):
    from rl_tpu.trainers import GRPOTrainer

    real = GRPOTrainer._update_impl

    def update(self, params, opt_state, batch, dm, poison=None):
        half = batch["tokens"].shape[0] // 2
        return real(self, params, opt_state, batch[:half], dm, poison)

    monkeypatch.setattr(GRPOTrainer, "_update_impl", update)


def fault_engine_token_altered(monkeypatch):
    from rl_tpu.models.serving import ContinuousBatchingEngine

    real = ContinuousBatchingEngine.harvest

    def harvest(self):
        out = real(self)
        for f in out.values():
            f.tokens = (np.asarray(f.tokens) + 1) % 200
        return out

    monkeypatch.setattr(ContinuousBatchingEngine, "harvest", harvest)


FAULTS = [
    ("gpt2-medium.grpo", fault_grpo_state_unchanged),
    ("gpt2-medium.grpo", fault_grpo_half_batch),
    ("gpt2-medium.grpo", fault_engine_token_altered),
    ("gpt2-medium.rollout", fault_engine_token_altered),
]


@pytest.mark.parametrize("cell,fault", FAULTS, ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_broken_timed_path_reads_not_correct(monkeypatch, tmp_path, cell, fault):
    fault(monkeypatch)
    rc, last, _ = run(monkeypatch, tmp_path, cell)
    assert rc == 0
    assert last["correct"] is False, last["compared"]
    assert any(c["value"] > c["limit"] for c in last["compared"].values())
