"""Config system: component registry + recursive instantiation from dicts/YAML.

Redesign of the reference's hydra/omegaconf ConfigStore
(reference: torchrl/trainers/algorithms/configs/ — a ``*Config`` dataclass
with ``_target_`` per component, registered in groups; YAML recipes compose
object graphs). Same recipe shape without the hydra dependency:

- a config node is a mapping with ``_target_`` naming either a registered
  component (``"env/cartpole"``) or a dotted import path
  (``"rl_tpu.envs.CartPoleEnv"``);
- nested mappings/sequences instantiate depth-first;
- ``_partial_: true`` returns a ``functools.partial`` instead of calling.

>>> cfg = load_yaml("recipe.yaml")
>>> env = instantiate(cfg["env"])
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Any, Callable, Mapping, Sequence

__all__ = [
    "register", "get_component", "instantiate", "load_yaml", "to_dict",
    "REGISTRY", "compile_cache_dir", "enable_compile_cache",
]

# -- persistent compilation cache -------------------------------------------
#
# ONE rule places every cache the program keeps (XLA compile cache here,
# the executable store under it — rl_tpu.compile.store): the directory
# JAX_COMPILATION_CACHE_DIR names, which jax reads by itself; otherwise
# <checkout>/.jax_cache. The path is part of the cache key, so it is
# never built from a tmpdir, a pid or the time. bench.py, chip_smoke.py
# and tests/conftest.py call enable_compile_cache(); so does the first
# ProgramRegistry (any registered trainer or serving engine). Opt out with
# RL_TPU_NO_COMPILE_CACHE=1.

_ENV_NO_CACHE = "RL_TPU_NO_COMPILE_CACHE"


def compile_cache_dir() -> str:
    """Where the caches live: what jax is already configured with (the
    ``JAX_COMPILATION_CACHE_DIR`` variable, or an earlier call), else
    ``.jax_cache`` beside the ``rl_tpu`` package."""
    import os

    import jax

    return jax.config.jax_compilation_cache_dir or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
    )


def enable_compile_cache() -> str | None:
    """Idempotently enable JAX's persistent compilation cache at
    :func:`compile_cache_dir`. Returns the dir in use, or None when opted
    out. A dir jax already has (from the environment) is left as it is:
    nothing is set in code then."""
    import os

    if os.environ.get(_ENV_NO_CACHE, "") not in ("", "0"):
        return None
    import jax

    path = compile_cache_dir()
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


REGISTRY: dict[str, Callable] = {}


def register(name: str, target: Callable | None = None):
    """Register a component constructor; usable as decorator."""

    def deco(t):
        if name in REGISTRY and REGISTRY[name] is not t:
            raise ValueError(f"config component {name!r} already registered")
        REGISTRY[name] = t
        return t

    return deco(target) if target is not None else deco


def _resolve_dotted(path: str) -> Callable:
    mod, _, attr = path.rpartition(".")
    return getattr(importlib.import_module(mod), attr)


def get_component(target: str) -> Callable:
    entry = REGISTRY.get(target, _BUILTINS.get(target))
    if entry is not None:
        # builtin entries are dotted-path strings, resolved lazily so that
        # importing rl_tpu.config alone stays cheap
        return _resolve_dotted(entry) if isinstance(entry, str) else entry
    if "." in target:
        return _resolve_dotted(target)
    raise KeyError(f"unknown component {target!r} (not registered, not importable)")


def instantiate(node: Any) -> Any:
    """Depth-first instantiation of a config tree."""
    if isinstance(node, Mapping):
        out = {k: instantiate(v) for k, v in node.items() if not k.startswith("_")}
        if "_target_" in node:
            fn = get_component(node["_target_"])
            if node.get("_partial_", False):
                return functools.partial(fn, **out)
            return fn(**out)
        return out
    if isinstance(node, str):
        return node
    if isinstance(node, Sequence):
        return [instantiate(v) for v in node]
    return node


def load_yaml(path: str) -> dict:
    import yaml

    with open(path) as f:
        return yaml.safe_load(f)


def to_dict(obj: Any) -> Any:
    """Dataclass tree -> plain dict (for hparam logging / YAML dump)."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {f.name: to_dict(getattr(obj, f.name)) for f in dataclasses.fields(obj)}
    if isinstance(obj, Mapping):
        return {k: to_dict(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [to_dict(v) for v in obj]
    return obj


# Standard component registry (the reference's config groups,
# trainers/algorithms/configs/__init__.py registers a *Config per component).
# Values are dotted import paths resolved lazily by get_component, built from
# per-group tables below so importing rl_tpu.config stays import-cheap.
_BUILTINS: dict[str, str] = {}


def _snake(name: str) -> str:
    import re

    # lower→Upper, UPPER→Upper-lower and digit→Upper-lower boundaries
    # (A2C→a2c, TD3→td3, DreamerV3Actor→dreamer_v3_actor)
    return re.sub(
        r"(?<=[a-z])(?=[A-Z])|(?<=[A-Z0-9])(?=[A-Z][a-z])", "_", name
    ).lower()


def _add_group(group: str, module: str, names: Sequence[str], strip: str = "") -> None:
    for n in names:
        short = n[: -len(strip)] if strip and n.endswith(strip) and n != strip else n
        _BUILTINS.setdefault(f"{group}/{_snake(short)}", f"{module}.{n}")


_add_group("env", "rl_tpu.envs", [
    "CartPoleEnv", "PendulumEnv", "MountainCarEnv", "MountainCarContinuousEnv",
    "AcrobotEnv", "TicTacToeEnv", "TradingEnv", "NavigationEnv",
    "VmapEnv", "TransformedEnv", "ModelBasedEnv",
    "FrameSkipEnv", "NoopResetEnv", "ConditionalSkipEnv", "MultiActionEnv",
], strip="Env")
_add_group("env", "rl_tpu.envs.llm", ["ChatEnv", "DatasetChatEnv"], strip="Env")
_add_group("env", "rl_tpu.envs.libs.gym", ["GymEnv"], strip="Env")
_add_group("transform", "rl_tpu.envs", [
    "Compose", "RewardSum", "RewardScaling", "RewardClipping", "StepCounter",
    "InitTracker", "CatFrames", "CatTensors", "ObservationNorm", "VecNorm",
    "DoubleToFloat", "DTypeCast", "FlattenObservation", "UnsqueezeTransform",
    "SqueezeTransform", "RenameTransform", "ActionScaling", "TimeMaxPool",
    "GrayScale", "Resize", "CenterCrop", "ToFloatImage",
    "ActionMask", "ActionDiscretizer", "BinarizeReward", "ClipTransform",
    "EndOfLifeTransform", "ExcludeTransform", "SelectTransform", "FiniteCheck",
    "Hash", "LineariseRewards", "ModuleTransform", "PermuteTransform",
    "SignTransform", "StackTransform", "TensorDictPrimer", "Timer",
    "TrajCounter", "TargetReturn", "Crop", "DiscreteActionProjection",
    "UnaryTransform", "RandomTruncationTransform",
], strip="Transform")
_add_group("network", "rl_tpu.modules", [
    "MLP", "ConcatMLP", "ConvNet", "DuelingMLP", "TanhPolicy", "NoisyDense",
    "MultiAgentMLP", "QMixer", "VDNMixer", "NormalParamExtractor",
])
_add_group("module", "rl_tpu.modules", ["TDModule", "TDSequential"], strip="Module")
_add_group("actor", "rl_tpu.modules", [
    "ProbabilisticActor", "QValueActor", "RandomPolicy", "MultiStepActorWrapper",
], strip="Actor")
_add_group("operator", "rl_tpu.modules", ["ValueOperator", "ActorValueOperator"], strip="Operator")
_add_group("exploration", "rl_tpu.modules", [
    "EGreedyModule", "AdditiveGaussianModule", "OrnsteinUhlenbeckModule",
    "GSDEModule", "ConsistentDropout",
], strip="Module")
_add_group("dist", "rl_tpu.modules", [
    "Normal", "TanhNormal", "TruncatedNormal", "Delta", "TanhDelta",
    "Categorical", "OneHotCategorical", "MaskedCategorical", "Ordinal",
    "OneHotOrdinal",
])
_add_group("planner", "rl_tpu.modules", ["CEMPlanner", "MPPIPlanner"], strip="Planner")
_add_group("loss", "rl_tpu.objectives", [
    "PPOLoss", "ClipPPOLoss", "KLPENPPOLoss", "A2CLoss", "ReinforceLoss",
    "SACLoss", "DiscreteSACLoss", "DQNLoss", "DistributionalDQNLoss",
    "DDPGLoss", "TD3Loss", "TD3BCLoss", "CQLLoss", "DiscreteCQLLoss",
    "IQLLoss", "REDQLoss", "CrossQLoss", "BCLoss", "GAILLoss", "ACTLoss",
    "IPPOLoss", "MAPPOLoss", "QMixerLoss", "DreamerActorLoss",
    "DreamerValueLoss", "DreamerV3ModelLoss", "DreamerV3ActorLoss",
    "DreamerV3ValueLoss",
], strip="Loss")
_add_group("estimator", "rl_tpu.objectives", [
    "GAE", "MultiAgentGAE", "TD0Estimator", "TD1Estimator",
    "TDLambdaEstimator", "VTrace",
], strip="Estimator")
_add_group("updater", "rl_tpu.objectives", ["SoftUpdate", "HardUpdate"], strip="Update")
_add_group("storage", "rl_tpu.data.replay", [
    "DeviceStorage", "ListStorage", "MemmapStorage", "CompressedListStorage",
    "StorageEnsemble",
], strip="Storage")
_add_group("sampler", "rl_tpu.data.replay", [
    "RandomSampler", "SamplerWithoutReplacement", "PrioritizedSampler",
    "HostPrioritizedSampler", "SliceSampler", "SliceSamplerWithoutReplacement",
    "PrioritizedSliceSampler", "StalenessAwareSampler",
], strip="Sampler")
_add_group("writer", "rl_tpu.data.replay", [
    "RoundRobinWriter", "MaxValueWriter", "ImmutableDatasetWriter",
], strip="Writer")
_add_group("buffer", "rl_tpu.data.replay", ["ReplayBuffer", "ReplayBufferEnsemble"], strip="Buffer")
_add_group("postproc", "rl_tpu.data", [
    "MultiStep", "DensifyReward", "Reward2GoTransform", "BurnInTransform",
], strip="Transform")
_add_group("model", "rl_tpu.models", [
    "RSSM", "RSSMv3", "TransformerLM", "DecisionTransformer", "ACTModel",
], strip="Model")
_add_group("collector", "rl_tpu.collectors", [
    "Collector", "HostCollector", "LLMCollector",
], strip="Collector")
_add_group("pool", "rl_tpu.collectors", ["ThreadedEnvPool", "ProcessEnvPool"], strip="EnvPool")
_add_group("serve", "rl_tpu.modules", ["InferenceServer"])
_add_group("comm", "rl_tpu.comm", [
    "Watchdog", "Interruptor", "ServiceRegistry", "TCPServiceRegistry",
])
_add_group("storage", "rl_tpu.data", ["VideoCodecStorage"], strip="Storage")
_add_group("postproc", "rl_tpu.data", ["AddActionChunks"])
_add_group("logger", "rl_tpu.record.loggers", [
    "CSVLogger", "TensorboardLogger", "WandbLogger", "MLFlowLogger",
    "NullLogger", "MultiLogger",
], strip="Logger")
_add_group("scheme", "rl_tpu.weight_update.schemes", [
    "SharedProgramScheme", "DevicePutScheme", "DoubleBufferScheme",
], strip="Scheme")
_add_group("trainer", "rl_tpu.trainers", ["Trainer"])
_add_group("program", "rl_tpu.trainers", [
    "OnPolicyProgram", "OffPolicyProgram", "OnPolicyConfig", "OffPolicyConfig",
], strip="Program")
_BUILTINS.update({
    # aliases kept from the round-1 registry + builder entry points
    "env/cartpole": "rl_tpu.envs.CartPoleEnv",
    "env/hopper": "rl_tpu.envs.HopperEnv",
    "env/team_counting": "rl_tpu.testing.MultiAgentCountingEnv",
    "env/walker2d": "rl_tpu.envs.Walker2dEnv",
    "env/mountaincar": "rl_tpu.envs.MountainCarEnv",
    "env/tictactoe": "rl_tpu.envs.TicTacToeEnv",
    "actor/qvalue": "rl_tpu.modules.QValueActor",
    "transform/obs_norm": "rl_tpu.envs.ObservationNorm",
    "loss/td3_bc": "rl_tpu.objectives.TD3BCLoss",
    "loss/c51": "rl_tpu.objectives.DistributionalDQNLoss",
    "loss/kl_pen_ppo": "rl_tpu.objectives.KLPENPPOLoss",
    "model/rssm_v3": "rl_tpu.models.RSSMv3",
    "postproc/reward2go": "rl_tpu.data.Reward2GoTransform",
    "sampler/without_replacement": "rl_tpu.data.SamplerWithoutReplacement",
    "buffer/replay": "rl_tpu.data.ReplayBuffer",
    "env/gym": "rl_tpu.envs.libs.gym.GymEnv",
    "env/brax": "rl_tpu.envs.libs.brax.BraxEnv",
    "env/jumanji": "rl_tpu.envs.libs.jumanji.JumanjiEnv",
    "env/pettingzoo": "rl_tpu.envs.libs.pettingzoo.PettingZooEnv",
    "loss/ppo_clip": "rl_tpu.objectives.ClipPPOLoss",
    "network/conv": "rl_tpu.modules.ConvNet",
    "network/dueling": "rl_tpu.modules.DuelingMLP",
    "module/td": "rl_tpu.modules.TDModule",
    "program/on_policy_config": "rl_tpu.trainers.OnPolicyConfig",
    "program/off_policy_config": "rl_tpu.trainers.OffPolicyConfig",
    "trainer/ppo": "rl_tpu.trainers.make_ppo_trainer",
    "trainer/a2c": "rl_tpu.trainers.make_a2c_trainer",
    "trainer/impala": "rl_tpu.trainers.make_impala_trainer",
    "trainer/mappo": "rl_tpu.trainers.make_mappo_trainer",
    "trainer/sac": "rl_tpu.trainers.make_sac_trainer",
    "trainer/dqn": "rl_tpu.trainers.make_dqn_trainer",
    "trainer/td3": "rl_tpu.trainers.make_td3_trainer",
    "trainer/ddpg": "rl_tpu.trainers.make_ddpg_trainer",
    "trainer/redq": "rl_tpu.trainers.make_redq_trainer",
    "trainer/crossq": "rl_tpu.trainers.make_crossq_trainer",
    "trainer/qmix": "rl_tpu.trainers.make_qmix_trainer",
    "trainer/iql_offline": "rl_tpu.trainers.train_iql",
    "trainer/cql_offline": "rl_tpu.trainers.train_cql",
    "trainer/grpo": "rl_tpu.trainers.GRPOTrainer",
    "tokenizer/simple": "rl_tpu.data.llm.SimpleTokenizer",
    "dataset/arithmetic": "rl_tpu.envs.llm.arithmetic_dataset",
    "dataset/copy": "rl_tpu.envs.llm.copy_dataset",
    "scorer/exact_match": "rl_tpu.envs.llm.ExactMatchScorer",
    "scorer/sum": "rl_tpu.envs.llm.SumScorer",
    "scorer/format": "rl_tpu.envs.llm.FormatScorer",
    "llm_transform/kl_reward": "rl_tpu.envs.llm.KLRewardTransform",
    "llm_transform/policy_version": "rl_tpu.envs.llm.PolicyVersion",
    "llm_transform/python_tool": "rl_tpu.envs.llm.PythonToolTransform",
    # round-4 components
    "env/chess": "rl_tpu.envs.ChessEnv",
    "env/toy_vla": "rl_tpu.envs.ToyVLAEnv",
    "env/dm_control": "rl_tpu.envs.libs.dm_control.DMControlEnv",
    "actor/diffusion": "rl_tpu.modules.DiffusionActor",
    "actor/tiny_vla": "rl_tpu.modules.TinyVLA",
    "model/gp_world": "rl_tpu.modules.GPWorldModel",
    "loss/diffusion_bc": "rl_tpu.objectives.DiffusionBCLoss",
    "loss/pilco_cost": "rl_tpu.objectives.ExponentialQuadraticCost",
    "loss/dpo": "rl_tpu.objectives.llm.DPOLoss",
    "loss/pairwise_reward": "rl_tpu.objectives.llm.PairwiseRewardLoss",
    "dataset/gsm8k": "rl_tpu.envs.llm.gsm8k_dataset",
    "dataset/countdown": "rl_tpu.envs.llm.countdown_dataset",
    "dataset/ifeval": "rl_tpu.envs.llm.ifeval_dataset",
    "dataset/math_expression": "rl_tpu.envs.llm.math_expression_dataset",
    "dataset/minari_h5": "rl_tpu.data.MinariH5Dataset",
    "dataset/atari_dqn": "rl_tpu.data.AtariDQNDataset",
    "dataset/lerobot": "rl_tpu.data.LeRobotDataset",
    "scorer/gsm8k": "rl_tpu.envs.llm.GSM8KScorer",
    "scorer/countdown": "rl_tpu.envs.llm.CountdownScorer",
    "scorer/ifeval": "rl_tpu.envs.llm.IFEvalScorer",
    "tokenizer/action_uniform": "rl_tpu.data.UniformActionTokenizer",
    "tokenizer/action_vocab_tail": "rl_tpu.data.VocabTailActionTokenizer",
    "collector/mesh": "rl_tpu.collectors.MeshCollector",
})
