"""Share of the chip's memory bandwidth the decode steps need: every
weight once a step (float32, as the program keeps them) and the live keys
and values of the active slots, over traced window x peak bytes/s."""

from harness import work


def read(run):
    steps = run["c1"]["decode_steps"] - run["c0"]["decode_steps"]
    if steps <= 0:
        return None
    live = run["c1"]["kv_token_steps"] - run["c0"]["kv_token_steps"]
    byts = steps * work.decode_step_bytes(run["config"], 0.0) + work.kv_bytes_per_token(run["config"]) * live
    return 100.0 * byts / (run["trace"].window_s * run["peaks"]["bytes_per_s"])
