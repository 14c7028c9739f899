"""The fused sampling kernel's share of its roofline: one read of the
[rows, vocabulary] float32 logits a call, over the kernel's
summed device time in the trace (by kernel name)."""

from harness import readers, work

NEEDLES = ("_fused_sample_kernel",)  # kernels/sampling.py's pallas_call name


def read(run):
    steps = run["c1"]["decode_steps"] - run["c0"]["decode_steps"]
    if steps <= 0:
        return None
    # a decode step samples a row for every slot, a prefill one for every request it admits
    rows = steps * run["traffic"]["engine"]["n_slots"] + run["c1"]["admissions"] - run["c0"]["admissions"]
    flops, byts = work.sampling_cost(run["config"], rows)
    return readers.kernel_roofline(run, NEEDLES, flops, byts)
