"""Drives whole GRPO steps through ``GRPOTrainer.step()``.

Set-up builds ONE trainer, gives it the benchmark's weights from the
seed, drives it through its first ``check_steps`` steps by the window's
own call (they warm every program up and are what ``correct`` compares),
and hands the same object to the window. The reward is the benchmark's
own: with random weights the arithmetic scorer gives 0 to every sample
and the group advantage would be rounding noise of the KL term, so the
job scores a hash of the response's tokens instead — advantages of order
one that the tokens alone decide, as a verifiable reward gives.
"""

from __future__ import annotations

import gc

import numpy as np

from harness import compare, work


def token_hash_reward(tokens: np.ndarray) -> float:
    """A stand-in verifiable reward in [0, 1]: a position-weighted sum of
    the response's token ids, modulo a prime."""
    t = np.asarray(tokens, np.int64)
    return float(int(np.sum(t * (np.arange(len(t)) + 1))) % 1009) / 1008.0


class Driver:
    span = "bench.trainer_step"

    def __init__(self, config, traffic, seed, reference, log):
        self.config, self.traffic, self.seed, self.ref, self.log = config, traffic, int(seed), reference, log
        self.job = traffic["job"]
        self.records: list[dict] = []
        self.steps = 0

    # -- set-up ---------------------------------------------------------------

    def model_config(self):
        import jax.numpy as jnp

        from rl_tpu.models import TransformerConfig

        c = self.config
        return TransformerConfig(
            vocab_size=c["vocab_size"], d_model=c["n_embd"], n_layers=c["n_layer"],
            n_heads=c["n_head"], d_ff=c["n_inner"], max_seq_len=c["n_positions"],
            dtype=jnp.bfloat16, attention_impl=self.job["attention_impl"],
        )

    def tokenizer(self, dataset):
        """The trainer's own tokenizer, naming no stop token: every
        completion runs to ``max_new_tokens`` and every step holds the same
        work (with the stop token, 1 run in 6 sampled its id among random
        weights' 50257 and read 10 % slower)."""
        from rl_tpu.data.llm import SimpleTokenizer

        class RunToBudget(SimpleTokenizer):
            eos_token_id = None

        return RunToBudget(dataset.corpus())

    def setup(self):
        import jax
        import jax.numpy as jnp

        from rl_tpu.envs.llm import arithmetic_dataset
        from rl_tpu.trainers import GRPOTrainer

        j, seed31 = self.job, self.seed % (2**31 - 2)
        dataset = arithmetic_dataset(j["dataset_size"], seed=seed31)
        t = GRPOTrainer(
            dataset, model_config=self.model_config(), tokenizer=self.tokenizer(dataset),
            scorer=lambda history, toks: token_hash_reward(toks),
            num_prompts=j["num_prompts"], group_repeats=j["group_repeats"],
            max_prompt_len=j["max_prompt_len"], max_new_tokens=j["max_new_tokens"],
            temperature=j["temperature"], learning_rate=j["learning_rate"],
            kl_coeff=j["kl_coeff"], clip_epsilon=j["clip_epsilon"],
            continuous_batching=True, microbatch_size=j["microbatch_size"], seed=seed31,
        )
        # the benchmark's weights take the place of the trainer's own init
        w = self.ref.make_weights(self.config, self.seed)
        assert jax.tree.structure(w) == jax.tree.structure(t.params), "weight tree differs from the program's"
        t.params = w
        t.ref_params = jax.tree.map(jnp.copy, w)
        t.collector.ref_params = t.ref_params
        t.opt_state = t.opt.init(w)
        t.scheme.push(w)
        self.trainer = t

        collect = t.collector.collect

        def recording_collect(params, key):
            batch = collect(params, key)
            self.records.append({k: np.asarray(batch[k]) for k in (
                "tokens", "attention_mask", "assistant_mask", "sample_log_prob", "group_id", "reward")})
            return batch

        t.collector.collect = recording_collect
        n_check = int(self.traffic["check_steps"])
        for i in range(n_check):
            t.step()
            jax.block_until_ready(t.params)
            self.steps += 1
            self.records[i]["loss"] = float(t.metrics_snapshot()["loss"])
            if i == 0:
                # Adam's first moment after one step is (1 - b1) g
                mu = t.opt_state[0].mu
                self.grad_norms = {k: v / (1 - self.ref.ADAM_B1) for k, v in self.ref.leaf_norms(mu).items()}
                eng = t.collector._engine
                eng.aot_warmup()  # the rest of the ladder, as chip_smoke.py does
                self.n_slots = eng.n_slots
        del t.collector.collect  # the window drives the collector's own method
        self.dparam_norms = self.ref.diff_norms(t.params, t.ref_params)
        self.records = self.records[:n_check]

    # -- the window -----------------------------------------------------------

    def counters(self) -> dict:
        eng = self.trainer.metrics_snapshot()["engine"]
        return {"steps": self.steps, **{k: eng[k] for k in (
            "tokens_generated", "decode_steps", "decode_launches", "admissions", "host_transfers")}}

    def open_window(self, t0):
        pass

    def unit(self):
        self.trainer.step()
        self.steps += 1

    def close_window(self):
        import jax

        jax.block_until_ready(self.trainer.params)

    def end_to_end(self, c0, c1, elapsed) -> dict:
        bad = int(self.trainer.metrics_snapshot()["bad_steps"])
        return {
            "grpo_tokens_per_s": (c1["tokens_generated"] - c0["tokens_generated"]) / elapsed,
            "attempted": c1["steps"] - c0["steps"],
            "failed": bad,
        }

    def step_flops(self, steps: int) -> float:
        """FLOPs that ``steps`` steps of this job require (from the shapes
        of the recorded steps: prompts and responses as they came)."""
        rec = self.records[0]
        am, rm = rec["attention_mask"] > 0, rec["assistant_mask"] > 0
        prompts, news = (am & ~rm).sum(1), rm.sum(1)
        return steps * work.grpo_step_flops(self.config, prompts, news)

    # -- correct --------------------------------------------------------------

    def release(self):
        self.trainer = None
        gc.collect()

    def program_readings(self) -> dict:
        """What the timed path produced in its first steps."""
        return {
            "lp": [r["sample_log_prob"] for r in self.records],
            "loss": [r["loss"] for r in self.records],
            "grad_norms": self.grad_norms,
            "dparam_norms": self.dparam_norms,
        }

    def reference_readings(self, quant=None, fault=None) -> dict:
        """The same readings from the plain reference, which follows the
        recorded steps on the tokens the program sampled. ``quant`` and
        ``fault`` make it the control or plant a fault (calibration only)."""
        import jax
        import jax.numpy as jnp

        ref, cfg = self.ref, self.config
        w0 = ref.make_weights(cfg, self.seed)
        params, opt = jax.tree.map(jnp.copy, w0), ref.adam_init(w0)
        P = self.job["max_prompt_len"]
        out = {"lp": [], "loss": []}
        for k, rec in enumerate(self.records):
            rm = rec["assistant_mask"] > 0
            task = [token_hash_reward(rec["tokens"][i, P:][rm[i, P:]]) for i in range(len(rm))]
            params, opt, step = ref.grpo_step(cfg, self.job, params, opt, w0, rec, task, quant=quant,
                                              block=self.traffic.get("check_block", 4), fault=fault)
            out["lp"].append(np.asarray(step["behav"]))
            out["loss"].append(step["loss"])
            if k == 0:
                out["grad_norms"] = step["grad_norms"]
        out["dparam_norms"] = ref.diff_norms(params, w0)
        return out

    def gaps(self, got: dict, ref: dict) -> dict:
        masks = [r["assistant_mask"] > 0 for r in self.records]
        lp = [float(np.abs(g - r)[m].mean()) for g, r, m in zip(got["lp"], ref["lp"], masks)]
        loss = [abs(g - r) for g, r in zip(got["loss"], ref["loss"])]
        for k in range(len(lp)):
            self.log(f"step {k + 1}: loss {got['loss'][k]:.6g} reference {ref['loss'][k]:.6g} lp_gap {lp[k]:.6g}")
        skip = compare.negligible_leaves(ref["grad_norms"])
        if skip:
            self.log(f"leaves left out of the change (reference gradient negligible): {sorted(skip)}")
        g, d = (got["grad_norms"], ref["grad_norms"]), (got["dparam_norms"], ref["dparam_norms"], skip)
        return {
            "lp_gap_step1": lp[0],
            "lp_gap_last": lp[-1],
            "loss_gap": max(loss),
            "grad_norm_gap": compare.worst_leaf_gap(*g),
            "grad_norm_gap_median": compare.median_leaf_gap(*g),
            "dparam_norm_gap": compare.worst_leaf_gap(*d),
            "dparam_norm_gap_median": compare.median_leaf_gap(*d),
        }

    def check(self) -> dict:
        values = self.gaps(self.program_readings(), self.reference_readings())
        return compare.judge(values, self.traffic["limits"], self.log)
