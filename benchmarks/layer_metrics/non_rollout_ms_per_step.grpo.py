"""What a GRPO step spends outside its rollout (ms): the program's
``grpo.step`` span less the ``collector.rollout`` inside it — prompts,
reward, reference scoring, assembly, placement, update dispatch, push,
metrics drain — mean over the traced window's steps."""

from harness import spans


def read(run):
    return spans.non_rollout_ms_per_step(run)
