"""Arithmetic several per-layer readers share."""

from __future__ import annotations


def slot_occupancy(run, n_slots: int):
    """Decoded tokens over decode steps x slots, from the engine's counters
    (a prefill samples each request's first token, so admissions are not
    decode work)."""
    d = {k: run["c1"][k] - run["c0"][k] for k in ("tokens_generated", "admissions", "decode_steps")}
    if d["decode_steps"] <= 0:
        return None
    return 100.0 * (d["tokens_generated"] - d["admissions"]) / (d["decode_steps"] * n_slots)


def kernel_roofline(run, needles, flops: float, byts: float):
    """Least time for (flops, bytes) over the kernel's summed device time."""
    from harness import work

    sec, n = run["trace"].op_seconds(*needles)
    if n == 0 or sec <= 0:
        run["log"](f"no device op named like {needles} in the trace")
        return None
    least, bound = work.roofline_seconds(flops, byts, run["peaks"])
    run["log"](f"kernel {needles}: {n} calls, {sec:.6f} s on the device, least {least:.6f} s ({bound}-bound)")
    return 100.0 * least / sec
