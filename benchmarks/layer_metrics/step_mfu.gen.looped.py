"""Whole-step share of the chip's peak in generation, looped decoder: FLOPs
of the prefills admitted and the decode steps done in the traced window
(every loop of the layers a token, the live keys of every cache entry,
active slots only) over traced window x peak FLOP/s."""

from harness import work_decoder as work


def read(run):
    cfg = run["config"]
    d = {k: run["c1"][k] - run["c0"][k] for k in
         ("tokens_generated", "admissions", "prompt_tokens", "prompt_pairs", "kv_token_steps")}
    if d["tokens_generated"] <= 0:
        return None
    flops = (
        work.forward_flops(cfg, d["prompt_tokens"], d["admissions"], d["prompt_pairs"])
        + work.forward_flops(cfg, d["tokens_generated"], d["tokens_generated"], d["kv_token_steps"])
    )
    return 100.0 * flops / (run["trace"].window_s * run["peaks"]["flops"])
