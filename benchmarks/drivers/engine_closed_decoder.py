"""``engine_closed`` for a decoder whose configuration file carries the
published (Hugging Face) keys and whose block is not GPT-2's: RMSNorm
before and after each branch, rotary positions, a gated-SiLU FFN, an
untied head, an explicit head width, the layer stack looped
``total_ut_steps`` times. The closed loop, the window, the counters' tally
and ``correct`` are ``drivers/engine_closed.py``'s, loaded by path; this
file only builds the engine (bfloat16 weights, the scanned stack, the
table's length from the traffic file), reads three more counters, and
names the fault a loop can have.
"""

from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_drivers_engine_closed", os.path.join(os.path.dirname(os.path.abspath(__file__)), "engine_closed.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

# what the engine counts beyond the base driver's: blocks held summed over
# decode steps, admission rounds the pool deferred, loops run on the device
EXTRA_COUNTERS = ("kv_block_steps", "admissions_deferred_kv", "loop_steps_run")


class Driver(_base.Driver):
    def __init__(self, config, traffic, seed, reference, log):
        # the base driver sizes its requests and its reference rows by
        # ``n_positions``: here the engine's table, not the published 65,536
        super().__init__({**config, "n_positions": int(traffic["engine"]["max_seq_len"])},
                         traffic, seed, reference, log)

    def build_engine(self):
        import jax.numpy as jnp

        from rl_tpu.models import ContinuousBatchingEngine, TransformerConfig, TransformerLM

        c, e = self.config, self.traffic["engine"]
        # first, so that a program without these options fails before the weights are made
        model = TransformerLM(TransformerConfig(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
            n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"],
            d_ff=c["intermediate_size"], max_seq_len=c["n_positions"], dtype=jnp.bfloat16,
            norm="rmsnorm", norm_eps=c["rms_norm_eps"], norm_placement="sandwich",
            position="rotary", rope_theta=float(c["rope_theta"]), ffn="swiglu",
            tie_embeddings=bool(c["tie_word_embeddings"]), loop_steps=c["total_ut_steps"],
            early_exit_threshold=float(c["early_exit_threshold"]), scan_layers=True,
        ))
        params = self.ref.make_weights(c, self.seed)
        return ContinuousBatchingEngine(
            model, params, n_slots=e["n_slots"], block_size=e["block_size"], n_blocks=e["n_blocks"],
            max_seq_len=e["max_seq_len"], prompt_buckets=tuple(e["prompt_buckets"]), greedy=e["greedy"],
            temperature=e["temperature"], decode_chunk=e["decode_chunk"], prefix_cache=e["prefix_cache"],
            seed=self.seed % (2**31 - 2),
        )

    def close_window(self):
        super().close_window()
        snap = self.eng.metrics_snapshot()
        self.log(f"pool: {snap['kv_blocks_used']} of {snap['kv_blocks_total']} blocks held, "
                 f"{snap['kv_reserved_blocks']} more reserved; {snap['admissions_deferred_kv']} admission rounds "
                 f"so far left a free slot empty for want of blocks")

    def counters(self) -> dict:
        out = super().counters()
        snap = self.eng.metrics_snapshot()
        return {**out, **{k: snap[k] for k in EXTRA_COUNTERS if k in snap}}

    def reference_readings(self, quant=None, fault=None) -> dict:
        """Any fault asked for is this driver's own: the reference run with
        one loop fewer (``calibrate.py`` names only the GRPO job's)."""
        import jax.numpy as jnp
        import numpy as np

        toks, mask, _resp = self.layout
        w = self.ref.make_weights(self.config, self.seed)
        lp = self.ref.score_rows(
            self.config, w, jnp.asarray(toks), jnp.asarray(mask), self.traffic["engine"]["temperature"],
            quant, block=4, fault="loops_minus_one" if fault else None)
        return {"lp": np.asarray(lp), "budget_gap": 0.0}
