"""Whole-step share of the chip's peak: FLOPs the traced window's GRPO
steps require (generation, reference scoring, update forward + backward;
no recomputation, no padding) over traced window x peak FLOP/s."""


def read(run):
    steps = run["c1"]["steps"] - run["c0"]["steps"]
    if steps <= 0:
        return None
    flops = run["driver"].step_flops(steps)
    return 100.0 * flops / (run["trace"].window_s * run["peaks"]["flops"])
