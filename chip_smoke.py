"""The quickest proof that rl_tpu still starts on the chip.

    python chip_smoke.py             # one TPU chip: serve, grpo, anakin, per
    python chip_smoke.py --chips 4   # four chips: the FSDP GRPO update only

One process, no platform forcing, no subprocess: it drives the system's
main paths through the entry points a user calls, at the full width of
the 110M ``TransformerLM`` (vocab 32768, d_model 768, 12 layers, 12 heads
x 64, d_ff 3072, ctx 1024, bf16; weights random from ``SEED``) and the
full shapes of the pixel Anakin program and the PER bench, a few steps
each, and checks what comes out against the repo's own plain paths:

- ``serve``: two ``ContinuousBatchingEngine``s (greedy, and sampled at
  temperature 0.8; ``decode_chunk="auto"``, paged KV, 16 slots) answer 8
  requests of mixed lengths; every request gets its token budget, one
  greedy request's log-probs agree with teacher-forced scoring by
  ``models/generate.py``, and the decode program's HLO holds a
  ``tpu_custom_call`` for every kernel the registry reports native there.
- ``grpo``: ``GRPOTrainer(continuous_batching=True)`` takes 3 steps
  through ``LLMCollector`` and the engine; finite loss, parameters
  changed, no compile on step 3, donated optimizer state consumed.
- ``anakin``: the fused on-policy program (Nature-CNN over device-
  rendered 84x84x4 frames, 256 envs x 16 steps), 3 donated dispatches;
  finite metrics, no recompile after the first.
- ``per``: ``PrioritizedSampler.sample_and_update`` at capacity 2**20;
  the fused sum-tree kernel's state is bit-equal to the stock scatter
  path from the same seed, the block sums add up to ``sum(priorities)``.
- ``--chips 4`` runs ONLY the sharded update over
  ``make_fsdp_mesh(fsdp=4)`` and the single-device update it is compared
  with: loss parity, four distinct quarter shards per large leaf, and
  per-device memory within 25% of each other.

Every phase prints one JSON line; the LAST line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
and carries nothing else. Without an accelerator, with a device that is
not in the peaks table, or when any phase raises or fails a check, the
last line says ``"ok": false`` and the exit code is 1. Times printed here
are observations of one cold run, not metrics.

The compile cache is placed by ``rl_tpu.config.enable_compile_cache``:
``JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache`` in the checkout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from unittest import mock

SEED = 0
LP_ATOL = 0.1  # nats: ~6 bf16 ulps of a logit in [2, 4), two independent roundings
# the same update on the same batch; XLA fuses a quarter batch per device
# differently from the whole one, so bf16 activations round at other points
FSDP_LOSS_ATOL = 2e-3
MEM_SPREAD = 0.25


def model_110m(**overrides):
    import jax.numpy as jnp

    from rl_tpu.models import TransformerConfig

    kw = dict(
        vocab_size=32768, d_model=768, n_layers=12, n_heads=12, d_ff=3072,
        max_seq_len=1024, dtype=jnp.bfloat16,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def _emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


def _native_kernels() -> list[str]:
    from rl_tpu.kernels import registry as kreg

    return sorted(n for n, st in kreg.status().items() if st["mode"] == "native")


def kernels_in_hlo(hlo: str) -> set[str]:
    """Registered kernels that are in ``hlo`` as a ``tpu_custom_call``."""
    from rl_tpu.kernels import registry as kreg

    calls = [ln for ln in hlo.splitlines() if 'custom_call_target="tpu_custom_call"' in ln]
    return {
        name
        for name in kreg.registered_kernels()
        if any(t in ln for ln in calls for t in kreg.kernel_targets(name))
    }


def _all_finite(tree) -> bool:
    import jax
    import numpy as np

    return all(
        bool(np.isfinite(np.asarray(x, np.float32)).all())
        for x in jax.tree.leaves(tree)
        if hasattr(x, "dtype") and np.issubdtype(np.asarray(x).dtype, np.floating)
    )


# -- serve --------------------------------------------------------------------


def serve_phase(cfg=None, *, n_slots=16, prompt_buckets=(32, 128), lengths=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from rl_tpu.compile import get_program_registry
    from rl_tpu.models import ContinuousBatchingEngine, TransformerLM, token_log_probs

    cfg = cfg or model_110m()
    # (prompt length, tokens to generate): short and long of each
    lengths = lengths or [(12, 24), (90, 8), (30, 48), (5, 64)]
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(SEED), jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(SEED)
    block = 16
    out: dict = {"requests": 0, "kernels_native": _native_kernels()}
    budgets_met = finite = True
    greedy_done = None
    engines = []  # the registry holds their programs weakly
    for greedy in (True, False):
        eng = ContinuousBatchingEngine(
            model, params, n_slots=n_slots, block_size=block,
            n_blocks=n_slots * (cfg.max_seq_len // block) + 1,
            prompt_buckets=prompt_buckets, greedy=greedy,
            temperature=1.0 if greedy else 0.8, decode_chunk="auto", seed=SEED,
        )
        want = {}
        for p_len, n_new in lengths:
            prompt = rng.integers(0, cfg.vocab_size, p_len).astype(np.int32)
            want[eng.submit(prompt, n_new)] = n_new
        done = eng.run()
        out["requests"] += len(done)
        budgets_met &= set(done) == set(want) and all(
            len(done[r].tokens) == n and done[r].finished_reason == "length"
            for r, n in want.items()
        )
        finite &= all(bool(np.isfinite(f.log_probs).all()) for f in done.values())
        if greedy:
            greedy_done = done[max(want, key=want.get)]  # the longest answer
        engines.append(eng)
    decode_progs = [
        p for p in get_program_registry().programs() if p.name.startswith("serving.decode.")
    ]
    out["decode_programs"] = sorted({p.name for p in decode_progs})
    hlo_kernels = {k for p in decode_progs for hlo in p.hlo_texts() for k in kernels_in_hlo(hlo)}

    # the engine's log-probs vs teacher-forced scoring of the same tokens
    # by the plain no-cache path (random-init logits are nearly flat, so
    # argmax tokens say little; the log-probs are the comparable quantity)
    seq = np.concatenate([greedy_done.prompt, greedy_done.tokens])[None]
    score = jax.jit(lambda p, t: token_log_probs(model, p, t))
    ref = np.asarray(score(params, jnp.asarray(seq)))[0]
    ref = ref[len(greedy_done.prompt):]
    lp_diff = float(np.max(np.abs(ref - greedy_done.log_probs)))

    # every kernel the decode path can reach (this engine's KV cache is
    # not int8, the sum-tree belongs to PER) must be in its HLO
    expected = set(out["kernels_native"]) & {"paged_attention", "sampling"}
    out.update(
        budgets_met=bool(budgets_met),
        log_probs_finite=bool(finite),
        lp_max_abs_diff_vs_plain=lp_diff,
        lp_atol=LP_ATOL,
        lp_agree=lp_diff <= LP_ATOL,
        kernels_in_decode_hlo=sorted(hlo_kernels),
        kernels_lowered=expected <= hlo_kernels,
    )
    return out


# -- grpo ---------------------------------------------------------------------


def grpo_phase(cfg=None, *, steps=3, num_prompts=4, group_repeats=8,
               max_prompt_len=32, max_new_tokens=96, microbatch_size=8):
    import jax
    import numpy as np

    from rl_tpu.compile import CompileDelta
    from rl_tpu.envs.llm import arithmetic_dataset
    from rl_tpu.trainers import GRPOTrainer

    # the training forward takes the flash kernels, as it does in
    # BENCH_MODE=rlhf on the chip; generation and scoring share the params
    cfg = cfg or model_110m(attention_impl="flash")
    trainer = GRPOTrainer(
        arithmetic_dataset(64, seed=SEED), model_config=cfg,
        num_prompts=num_prompts, group_repeats=group_repeats,
        max_prompt_len=max_prompt_len, max_new_tokens=max_new_tokens,
        learning_rate=1e-4, seed=SEED, continuous_batching=True,
        microbatch_size=microbatch_size,
    )
    before = jax.tree.map(np.asarray, trainer.params)
    losses = []
    donated = True
    for i in range(steps):
        opt_in = jax.tree.leaves(trainer.opt_state)
        with CompileDelta() as d:
            losses.append(trainer.step()["loss"])
        jax.block_until_ready(trainer.params)
        # the update donates the optimizer state: a backend that took the
        # donation has deleted every array leaf it was handed
        donated &= all(x.is_deleted() for x in opt_in if x.ndim)
        if i == 0:
            # the rollout engine exists now: build the rest of its ladder
            # (a request that samples eos early frees its slot, and the
            # next admission is a smaller prefill), so that the steady
            # state compiles nothing whatever the sampler draws
            trainer.collector._engine.aot_warmup()
    changed = sum(
        float(np.abs(np.asarray(a, np.float32) - b).max()) > 0
        for a, b in zip(jax.tree.leaves(trainer.params), jax.tree.leaves(before))
    )
    snap = trainer.metrics_snapshot()
    return dict(
        losses=losses,
        loss_finite=bool(np.isfinite(losses).all()),
        params_finite=_all_finite(trainer.params),
        param_leaves_changed=int(changed),
        params_changed=changed > 0,
        bad_steps=snap["bad_steps"],
        no_bad_steps=snap["bad_steps"] == 0,
        compile_delta_last_step=d.delta,
        no_compile_on_last_step=d.delta == 0,
        compile_delta_explain=d.explain(),
        donation_accepted=bool(donated),
        engine_used="engine" in snap,
        kernels_native=_native_kernels(),
    )


# -- anakin -------------------------------------------------------------------


def anakin_phase(*, n_envs=256, unroll=16, dispatches=3):
    import jax
    import numpy as np

    from rl_tpu.compile import CompileDelta
    from rl_tpu.envs import CartPoleEnv, PixelRender, TransformedEnv, VmapEnv, cartpole_pixels
    from rl_tpu.modules import (
        MLP,
        Categorical,
        ConvNet,
        ProbabilisticActor,
        TDModule,
        TDSequential,
        ValueOperator,
    )
    from rl_tpu.objectives import ClipPPOLoss
    from rl_tpu.trainers import AnakinConfig, AnakinProgram

    # bench_pixel's program: Nature-CNN actor and critic over frames the
    # env renders on the device
    env = TransformedEnv(
        VmapEnv(CartPoleEnv(), n_envs),
        PixelRender(cartpole_pixels, shape=(84, 84, 4), keep_obs=False),
    )
    actor = ProbabilisticActor(
        TDSequential(
            TDModule(ConvNet(), ["pixels"], ["feat"]),
            TDModule(MLP(out_features=2, num_cells=(512,)), ["feat"], ["logits"]),
        ),
        Categorical,
        dist_keys=("logits",),
    )
    critic = TDSequential(
        TDModule(ConvNet(), ["pixels"], ["vfeat"]),
        ValueOperator(MLP(out_features=1, num_cells=(512,)), in_keys=["vfeat"]),
    )
    loss = ClipPPOLoss(actor, critic, normalize_advantage=True)
    loss.make_value_estimator(gamma=0.99, lmbda=0.95)
    frames = n_envs * unroll
    program = AnakinProgram(
        env, lambda p, td, k: actor(p["actor"], td, k), loss,
        AnakinConfig(
            num_envs=n_envs, unroll_length=unroll, num_epochs=4,
            minibatch_size=min(frames, max(32, frames // 4)),
        ),
    )
    ts = program.init(jax.random.key(SEED))
    dm = program.init_metrics()
    finite = donated = True
    recompiles = 0
    metrics = None
    for i in range(dispatches):
        ts_in = jax.tree.leaves(ts)
        with CompileDelta() as d:
            ts, dm, metrics = program.dispatch(ts, dm)
            jax.block_until_ready(metrics)
        finite &= _all_finite(metrics)
        donated &= all(x.is_deleted() for x in ts_in if hasattr(x, "is_deleted"))
        if i:
            recompiles += d.delta
    snap = program.device_metrics.to_flat(program.device_metrics.drain(dm))
    return dict(
        loss=float(np.asarray(metrics["loss"])),
        metrics_finite=bool(finite),
        params_finite=_all_finite(ts["params"]),
        env_steps=snap["env_steps"],
        env_steps_counted=snap["env_steps"] == float(frames * dispatches),
        recompiles_after_first=int(recompiles),
        no_recompile=recompiles == 0,
        donation_accepted=bool(donated),
    )


# -- per ----------------------------------------------------------------------


def per_phase(*, capacity=2**20, batch=256, cycles=4):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from rl_tpu.data.replay.samplers import PrioritizedSampler
    from rl_tpu.kernels import registry as kreg

    sampler = PrioritizedSampler(alpha=0.6, beta=0.4)
    size = jnp.asarray(capacity, jnp.int32)

    def priority_fn(idx, info):
        return (idx % 97).astype(jnp.float32) * 0.01 + 0.05

    def cycle(st, key):
        return sampler.sample_and_update(st, key, batch, size, capacity, priority_fn)

    def run():
        """The same seeded cycles; a fresh jit so the kernel selection in
        force at trace time is the one that runs."""
        st = sampler.init(capacity)
        st = sampler.on_write(st, jnp.arange(capacity), None)
        # spread the leaves so the tree is not uniform
        st = sampler.update_priority(
            st, jnp.arange(0, capacity, 7),
            jax.random.uniform(jax.random.key(SEED), (len(range(0, capacity, 7)),)) + 0.1,
        )
        step = jax.jit(cycle, donate_argnums=0)
        hlo = step.lower(st, jax.random.key(1)).compile().as_text()
        idx = None
        for i in range(cycles):
            idx, _info, st = step(st, jax.random.fold_in(jax.random.key(SEED), i))
        return jax.tree.map(np.asarray, dict(st)), np.asarray(idx), hlo

    mode = kreg.selection("sumtree")
    st_k, idx, hlo = run()
    # the stock scatter-add path from the same seed: the registry's own
    # opt-out, set only around this trace and restored after it
    with mock.patch.dict(os.environ, {kreg.ENV_NO_KERNELS: "sumtree"}):
        st_ref, idx_ref, _ = run()

    def bits(a):
        return np.asarray(a, np.float32).view(np.uint32)

    exactness = kreg.registered_kernels()["sumtree"].exactness
    pr = st_k["priorities"].astype(np.float64)
    total, root = float(pr.sum()), float(st_k["esum"].astype(np.float64).sum())
    return dict(
        sumtree_mode=mode or "stock",
        exactness_tier=exactness,
        state_bit_equal_to_stock=bool(
            np.array_equal(bits(st_k["priorities"]), bits(st_ref["priorities"]))
            and np.array_equal(bits(st_k["esum"]), bits(st_ref["esum"]))
        ),
        indices_equal_to_stock=bool(np.array_equal(idx, idx_ref)),
        root=root,
        sum_priorities=total,
        root_matches_sum=abs(root - total) <= 1e-4 * total,
        indices_in_range=bool((idx >= 0).all() and (idx < capacity).all()),
        kernel_lowered=(mode != "native") or ("sumtree" in kernels_in_hlo(hlo)),
    )


# -- four chips: the FSDP update and its single-device comparison --------------


def _grpo_batch(B, T, prompt_len, vocab, num_prompts):
    """A synthetic rollout batch with every field the update reads."""
    import jax.numpy as jnp
    import numpy as np

    from rl_tpu.data import ArrayDict

    rng = np.random.default_rng(SEED)
    resp = np.arange(T)[None, :] >= prompt_len
    return ArrayDict(
        advantage=jnp.asarray(rng.standard_normal(B), jnp.float32),
        reward=jnp.asarray(rng.random(B), jnp.float32),
        tokens=jnp.asarray(rng.integers(0, vocab, (B, T)), jnp.int32),
        attention_mask=jnp.ones((B, T), jnp.float32),
        assistant_mask=jnp.asarray(np.broadcast_to(resp, (B, T))),
        # near what a random-init model assigns (-log V), so ratios sit
        # around the clip range instead of saturating it
        sample_log_prob=jnp.asarray(
            -np.log(vocab) + 0.1 * rng.standard_normal((B, T)), jnp.float32
        ),
        group_id=jnp.asarray(np.arange(B) % num_prompts, jnp.int32),
        policy_version=jnp.zeros((B,), jnp.int32),
        ref_log_prob=jnp.full((B, T), -np.log(vocab), jnp.float32),
    )


def fsdp_phase(cfg=None, *, devices=None, num_prompts=4, group_repeats=8,
               max_prompt_len=32, max_new_tokens=96, microbatch_size=16,
               fsdp_min_size_mb=4.0):
    import gc

    import jax
    import numpy as np

    from rl_tpu.envs.llm import arithmetic_dataset
    from rl_tpu.parallel import make_fsdp_mesh
    from rl_tpu.trainers import GRPOTrainer

    cfg = cfg or model_110m()
    devices = list(devices if devices is not None else jax.devices())[:4]
    B, T = num_prompts * group_repeats, max_prompt_len + max_new_tokens
    batch = _grpo_batch(B, T, max_prompt_len, cfg.vocab_size, num_prompts)

    def trainer(mesh):
        return GRPOTrainer(
            arithmetic_dataset(64, seed=SEED), model_config=cfg, mesh=mesh,
            num_prompts=num_prompts, group_repeats=group_repeats,
            max_prompt_len=max_prompt_len, max_new_tokens=max_new_tokens,
            learning_rate=1e-4, seed=SEED, microbatch_size=microbatch_size,
            fsdp_min_size_mb=fsdp_min_size_mb,
        )

    sharded = trainer(make_fsdp_mesh(fsdp=4, devices=devices))
    placed = jax.device_put(batch, sharded._batch_placement)
    loss_sharded = sharded._consume(placed)["loss"]
    jax.block_until_ready(sharded.params)

    # every leaf above the cutoff that four divides: four distinct shards
    # of a quarter each
    cutoff = fsdp_min_size_mb * 2**20
    big = bad = 0
    for leaf in jax.tree.leaves((sharded.params, sharded.opt_state)):
        if not hasattr(leaf, "addressable_shards") or leaf.ndim == 0:
            continue
        if leaf.size * leaf.dtype.itemsize < cutoff or not any(s % 4 == 0 for s in leaf.shape):
            continue
        big += 1
        shards = leaf.addressable_shards
        ok = (
            len(shards) == 4
            and len({s.device for s in shards}) == 4
            and len({str(s.index) for s in shards}) == 4
            and all(s.data.size * 4 == leaf.size for s in shards)
        )
        bad += not ok
    gc.collect()
    stats = [d.memory_stats() for d in devices]
    in_use = [s["bytes_in_use"] for s in stats] if all(stats) else None

    # the single-device update it is compared with: same seed, same batch
    single = trainer(None)
    loss_single = single._consume(batch)["loss"]
    diff = abs(loss_sharded - loss_single)
    out = dict(
        loss_sharded=loss_sharded,
        loss_single=loss_single,
        loss_abs_diff=diff,
        loss_atol=FSDP_LOSS_ATOL,
        loss_parity=bool(np.isfinite(diff) and diff <= FSDP_LOSS_ATOL),
        large_leaves=big,
        large_leaves_sharded_four_ways=big > 0 and bad == 0,
        bytes_in_use_per_device=in_use,
    )
    if in_use is not None:  # the CPU backend reports no memory statistics
        out["memory_balanced"] = (max(in_use) - min(in_use)) <= MEM_SPREAD * max(in_use)
    return out


# -- driver -------------------------------------------------------------------


def _run_phase(name, fn) -> bool:
    """Run one phase, print its line; True iff every boolean check held."""
    from rl_tpu.compile import compile_seconds_total

    t0, c0 = time.perf_counter(), compile_seconds_total()
    line: dict = {"phase": name}
    try:
        checks = fn()
        line["ok"] = all(v for v in checks.values() if isinstance(v, bool))
        line["checks"] = checks
    except Exception:
        line["ok"] = False
        line["error"] = traceback.format_exc(limit=8)
    line["wall_s"] = round(time.perf_counter() - t0, 2)
    line["compile_s"] = round(compile_seconds_total() - c0, 2)
    _emit(line)
    return line["ok"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    from rl_tpu.compile import install_compile_listener
    from rl_tpu.config import enable_compile_cache
    from rl_tpu.utils.peaks import device_peaks

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    problem = None
    if device["platform"] != "tpu":
        problem = f"no accelerator: jax found platform {device['platform']!r}"
    elif device["count"] != args.chips:
        problem = f"--chips {args.chips} on a machine with {device['count']} devices"
    else:
        try:
            device_peaks(device["kind"])
        except KeyError as e:
            problem = str(e)
    if problem:
        _emit({"phase": "device", "ok": False, "error": problem})
        _emit({"ok": False, "device": device})
        return 1

    t0 = time.perf_counter()
    cache_dir = enable_compile_cache()
    install_compile_listener()
    phases = (
        {"fsdp": fsdp_phase}
        if args.chips == 4
        else {"serve": serve_phase, "grpo": grpo_phase, "anakin": anakin_phase, "per": per_phase}
    )
    ok = True
    for name, fn in phases.items():
        ok &= _run_phase(name, fn)
    _emit({
        "phase": "summary", "ok": ok, "wall_s": round(time.perf_counter() - t0, 2),
        "compile_cache_dir": cache_dir, "claim": None,
    })
    _emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
