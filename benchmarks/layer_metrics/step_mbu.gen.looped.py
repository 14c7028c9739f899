"""Share of the chip's memory bandwidth the decode steps need, looped
decoder: the layers' weights once a loop and the head once a step, and
the live keys and values of the active slots in every cache entry
(bfloat16), over traced window x peak bytes/s."""

from harness import work_decoder as work


def read(run):
    steps = run["c1"]["decode_steps"] - run["c0"]["decode_steps"]
    if steps <= 0:
        return None
    live = run["c1"]["kv_token_steps"] - run["c0"]["kv_token_steps"]
    byts = steps * work.decode_weight_bytes(run["config"]) + work.kv_bytes_per_token(run["config"]) * live
    return 100.0 * byts / (run["trace"].window_s * run["peaks"]["bytes_per_s"])
