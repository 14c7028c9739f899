"""The flash-attention kernels' share of their roofline in the GRPO
update and scoring: forward and backward together, from the device trace
by kernel name."""

from harness import readers, work

NEEDLES = ("_fwd_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel")  # ops/attention.py's pallas_call names


def read(run):
    drv, cfg = run["driver"], run["config"]
    steps = run["c1"]["steps"] - run["c0"]["steps"]
    if steps <= 0:
        return None
    j = drv.job
    B, T = j["num_prompts"] * j["group_repeats"], j["max_prompt_len"] + j["max_new_tokens"]
    L = cfg["n_layer"]
    # per step: scoring forward + update forward (2) and one backward, every layer
    ff, fb = work.flash_attention_cost(cfg, B, T, False)
    bf, bb = work.flash_attention_cost(cfg, B, T, True)
    return readers.kernel_roofline(run, NEEDLES, steps * L * (2 * ff + bf), steps * L * (2 * fb + bb))
