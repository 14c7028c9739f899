"""The paged decode-attention kernel's share of its roofline in a looped
decoder: one call a cache entry (loops x layers a decode step), the live
keys and values each call had to read, over the kernel's summed device
time in the trace (by kernel name)."""

from harness import readers
from harness import work_decoder as work

NEEDLES = ("_paged_decode_kernel",)  # the pallas_call name


def read(run):
    cfg = run["config"]
    live = run["c1"]["kv_token_steps"] - run["c0"]["kv_token_steps"]
    steps = run["c1"]["decode_steps"] - run["c0"]["decode_steps"]
    if steps <= 0 or live <= 0:
        return None
    rows = steps * run["traffic"]["engine"]["n_slots"]
    flops, byts = work.decode_attention_cost(cfg, live, rows)
    entries = work.cache_entries(cfg)
    return readers.kernel_roofline(run, NEEDLES, entries * flops, entries * byts)
