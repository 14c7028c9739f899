"""Full GRPO/RLHF recipe: local tokenizer + arithmetic task dataset →
DatasetChatEnv → KV-cache generation → KL-shaped rewards → GRPO updates →
DevicePut weight push → greedy eval (reference analog:
sota-implementations/grpo/grpo-sync.py, engine-free and hub-free).

Run:  python examples/grpo_full.py [steps]
With >1 devices (e.g. the 8-dev CPU mesh:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/grpo_full.py)
the training forward runs ring attention over a "context" mesh axis.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from rl_tpu.envs.llm import arithmetic_dataset  # noqa: E402
from rl_tpu.trainers.grpo import GRPOTrainer  # noqa: E402


def main(steps: int = 60):
    mesh = None
    if len(jax.devices()) > 1:
        from rl_tpu.parallel import make_mesh

        n = len(jax.devices())
        mesh = make_mesh(data=1, context=n)
        print(f"ring attention over {n}-way context axis")

    ds = arithmetic_dataset(n=256, max_operand=4)
    trainer = GRPOTrainer(ds, mesh=mesh, num_prompts=8, group_repeats=8,
                          kl_coeff=0.02)
    print(f"vocab={trainer.tokenizer.vocab_size} "
          f"eval@init={trainer.evaluate():.3f}")
    for i in range(steps):
        m = trainer.step()
        if i % 10 == 0:
            print(f"step {i:4d} reward {m['reward']:.3f} loss {m['loss']:.4f}")
    print(f"eval@end={trainer.evaluate():.3f} "
          f"(policy v{trainer.policy_version.version})")


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 60)
