"""The paged decode-attention kernel's share of its roofline: the live
keys and values each call had to read, over the kernel's summed device
time in the trace (by kernel name)."""

from harness import readers, work

NEEDLES = ("_paged_decode_kernel", "_paged_decode_int8_kernel")  # the pallas_call names


def read(run):
    cfg = run["config"]
    live = run["c1"]["kv_token_steps"] - run["c0"]["kv_token_steps"]
    steps = run["c1"]["decode_steps"] - run["c0"]["decode_steps"]
    if steps <= 0 or live <= 0:
        return None
    rows = steps * run["traffic"]["engine"]["n_slots"]
    flops, byts = work.decode_attention_cost(cfg, live, rows)
    return readers.kernel_roofline(run, NEEDLES, cfg["n_layer"] * flops, cfg["n_layer"] * byts)
