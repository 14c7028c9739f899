"""Whole-step share of the chip's peak in generation: FLOPs of the
prefills admitted and the decode steps done in the traced window (live
keys only, active slots only) over traced window x peak FLOP/s."""

from harness import work


def read(run):
    cfg = run["config"]
    d = {k: run["c1"][k] - run["c0"][k] for k in
         ("tokens_generated", "admissions", "prompt_tokens", "prompt_pairs", "kv_token_steps")}
    if d["tokens_generated"] <= 0:
        return None
    decoded = d["tokens_generated"] - d["admissions"]
    flops = (
        work.lm_forward_flops(cfg, d["prompt_tokens"], d["admissions"], d["prompt_pairs"])
        + work.lm_forward_flops(cfg, decoded, decoded, d["kv_token_steps"])
    )
    return 100.0 * flops / (run["trace"].window_s * run["peaks"]["flops"])
