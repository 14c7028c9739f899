"""Drives ``ContinuousBatchingEngine`` in a closed loop: ``submit`` keeps
at least ``outstanding`` requests in the engine, the loop is ``step()`` /
``harvest()``. Set-up warms the engine's whole program ladder and runs
the traffic through it untimed for ``warm_seconds``, so the chunk tuner has
settled and nothing compiles in the window.

``correct``: a sample of the requests the window finished, drawn from the
seed with the longest in it, is scored once by the plain reference; the
traffic samples its tokens, so what is compared is each served token's
behaviour log-prob against the reference's log-prob of that token, and
that every request got exactly its budget.
"""

from __future__ import annotations

import functools
import gc
import time

import numpy as np

from harness import compare, requests, work


class Driver:
    span = "bench.engine_step"

    def __init__(self, config, traffic, seed, reference, log):
        self.config, self.traffic, self.seed, self.ref, self.log = config, traffic, int(seed), reference, log
        self.pool = requests.make_pool(traffic["requests"], config["vocab_size"], self.seed, config["n_positions"])
        self.next = 0
        self.want: dict[int, dict] = {}  # rid -> request
        self.done: list = []  # (request, FinishedRequest) finished inside the window
        self.in_window = False
        self.finished = 0
        self.submitted = 0
        self.work = {"prompt_tokens": 0.0, "prompt_pairs": 0.0, "kv_token_steps": 0.0}
        self._last_steps = 0

    def build_engine(self):
        import jax.numpy as jnp

        from rl_tpu.models import ContinuousBatchingEngine, TransformerConfig, TransformerLM

        c, e = self.config, self.traffic["engine"]
        model = TransformerLM(TransformerConfig(
            vocab_size=c["vocab_size"], d_model=c["n_embd"], n_layers=c["n_layer"], n_heads=c["n_head"],
            d_ff=c["n_inner"], max_seq_len=c["n_positions"], dtype=jnp.bfloat16,
        ))
        params = self.ref.make_weights(c, self.seed)
        return ContinuousBatchingEngine(
            model, params, n_slots=e["n_slots"], block_size=e["block_size"], n_blocks=e["n_blocks"],
            prompt_buckets=tuple(e["prompt_buckets"]), greedy=e["greedy"], temperature=e["temperature"],
            decode_chunk=e["decode_chunk"], seed=self.seed % (2**31 - 2),
        )

    def _on_admit(self, rid):
        p = len(self.want[rid]["prompt"])
        self.work["prompt_tokens"] += p
        self.work["prompt_pairs"] += work.causal_pairs(p)

    def submit_next(self) -> int:
        req = self.pool[self.next % len(self.pool)]
        self.next += 1
        rid = self.eng.submit(req["prompt"], req["new"])
        self.want[rid] = req
        self.submitted += 1
        return rid

    def step_and_harvest(self) -> dict:
        """One ``step()``, the tally of the keys and values it had to read,
        one ``harvest()``; returns what finished."""
        from jax.profiler import TraceAnnotation

        eng = self.eng
        eng.step()
        steps = eng.decode_steps
        live = float(eng.lens[eng.slot_rid >= 0].sum())
        self.work["kv_token_steps"] += live * (steps - self._last_steps)
        self._last_steps = steps
        with TraceAnnotation("bench.harvest"):
            out = eng.harvest()
            for rid, fin in out.items():
                req = self.want.pop(rid)
                self.finished += 1
                if self.in_window:
                    self.done.append((req, fin))
        return out

    # -- set-up and the window ----------------------------------------------

    def setup(self):
        self.eng = self.build_engine()
        self.eng.on_admit = self._on_admit
        self.eng.aot_warmup()
        self.warm_table_ops()
        t_end = time.perf_counter() + float(self.traffic["warm_seconds"])
        while time.perf_counter() < t_end:
            self.unit()
        self.log(f"warm-up: {self.finished} requests finished, chunk tuner at "
                 f"{self.eng.metrics_snapshot()['tuner_k']}")

    def warm_table_ops(self):
        """The engine writes and reads its device block table by eager
        ``jax.numpy`` calls whose shapes follow how many entries changed
        (padded to a power of two) and how many rows a prefill admits.
        ``aot_warmup`` does not cover them, so a window compiled whichever
        counts the warm-up traffic had not met: 9 s of a checkout's first
        run, 1.4 s of its second (PERF.md, section 7, fault 6). The same two
        expressions, on the engine's table, for every count."""
        import jax.numpy as jnp

        table = self.eng.dev_table
        n = 1
        while n <= table.size:
            z = np.zeros(n, np.int32)
            table.at[z, z].set(jnp.asarray(z))
            if n <= table.shape[0]:
                table[jnp.asarray(np.zeros(n, np.int64))]
            n *= 2

    def unit(self):
        from jax.profiler import TraceAnnotation

        low = int(self.traffic["outstanding"])
        if self.eng.pending() < low:
            with TraceAnnotation("bench.submit"):
                while self.eng.pending() < low:
                    self.submit_next()
        self.step_and_harvest()

    def open_window(self, t0):
        self.eng.metrics_snapshot()  # the window opens on a drained device, as it closes on one
        self.in_window = True
        self.log(f"window opens with {self.eng.pending()} requests outstanding")

    def close_window(self):
        self.eng.metrics_snapshot()  # reads the device counter: waits for the launched chunk
        self.in_window = False
        self.log(f"window closes with {self.eng.pending()} requests outstanding, "
                 f"{len(self.done)} finished inside it")

    def end_to_end(self, c0, c1, elapsed) -> dict:
        return {
            "gen_tokens_per_s": (c1["tokens_generated"] - c0["tokens_generated"]) / elapsed,
            "attempted": c1["finished"] - c0["finished"],
            "failed": 0,
        }

    def counters(self) -> dict:
        snap = self.eng.metrics_snapshot()  # reads the device counter: waits for launched chunks
        out = {k: snap[k] for k in ("tokens_generated", "decode_steps", "decode_launches", "admissions",
                                    "host_transfers", "pending")}
        return {**out, **self.work, "finished": self.finished, "submitted": self.submitted}

    # -- correct --------------------------------------------------------------

    def release(self):
        self.eng = None
        gc.collect()

    @functools.cached_property
    def picked(self) -> list:
        """Requests to check: the longest, and others drawn from the seed."""
        n = min(int(self.traffic["check_requests"]), len(self.done))
        if n == 0:
            return []
        longest = max(range(len(self.done)), key=lambda i: len(self.done[i][1].tokens))
        rest = [i for i in range(len(self.done)) if i != longest]
        pick = np.random.default_rng(self.seed).permutation(rest)[: n - 1]
        return [self.done[longest]] + [self.done[i] for i in pick]

    @functools.cached_property
    def layout(self):
        """The picked requests as padded rows: tokens, key mask, response mask."""
        picked, T = self.picked, self.config["n_positions"]
        toks = np.zeros((len(picked), T), np.int32)
        mask = np.zeros((len(picked), T), bool)
        resp = np.zeros((len(picked), T), bool)
        for i, (_req, fin) in enumerate(picked):
            p, n = len(fin.prompt), len(fin.tokens)
            toks[i, :p], toks[i, p:p + n] = fin.prompt, fin.tokens
            mask[i, :p + n] = True
            resp[i, p:p + n] = True
        return toks, mask, resp

    def program_readings(self) -> dict:
        """The served tokens' behaviour log-probs, and how far each request
        is from its budget."""
        toks, _mask, resp = self.layout
        served = np.zeros(toks.shape, np.float32)
        budget = 0
        for i, (req, fin) in enumerate(self.picked):
            p, n = len(fin.prompt), len(fin.tokens)
            served[i, p:p + n] = fin.log_probs
            budget += abs(n - req["new"])
        return {"lp": served, "budget_gap": float(budget)}

    def reference_readings(self, quant=None, fault=None) -> dict:
        import jax.numpy as jnp

        cfg = self.config
        toks, mask, _resp = self.layout
        w = self.ref.make_weights(cfg, self.seed)
        temp = self.traffic["engine"]["temperature"]
        lp = self.ref.score_rows(cfg, w, jnp.asarray(toks), jnp.asarray(mask), temp, quant, block=4)
        return {"lp": np.asarray(lp), "budget_gap": 0.0}

    def gaps(self, got: dict, ref: dict) -> dict:
        if not self.picked:
            return {"lp_gap_mean": float("nan"), "lp_gap_max": float("nan"), "budget_gap": got["budget_gap"]}
        resp = self.layout[2]
        gap = np.abs(got["lp"] - ref["lp"])[resp]
        self.log(f"checked {len(resp)} requests, {int(resp.sum())} served tokens, longest {int(resp.sum(1).max())}")
        return {"lp_gap_mean": float(gap.mean()), "lp_gap_max": float(gap.max()), "budget_gap": got["budget_gap"]}

    def check(self) -> dict:
        values = self.gaps(self.program_readings(), self.reference_readings())
        return compare.judge(values, self.traffic["limits"], self.log)
