"""Share of the KV pool's usable blocks the slots' tables held, mean over
the window's decode steps: the engine's ``kv_block_steps`` over decode
steps x usable blocks. A program without the counter leaves it out."""


def read(run):
    if "kv_block_steps" not in run["c1"]:
        return None
    steps = run["c1"]["decode_steps"] - run["c0"]["decode_steps"]
    if steps <= 0:
        return None
    usable = run["traffic"]["engine"]["n_blocks"] - 1  # block 0 is scratch
    return 100.0 * (run["c1"]["kv_block_steps"] - run["c0"]["kv_block_steps"]) / (steps * usable)
