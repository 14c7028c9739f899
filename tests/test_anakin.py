"""Anakin fused-program tests: fleet factory, per-env PRNG stream
independence (the batched-reset key fix), fused-vs-Collector parity from
the same seed, autoreset boundary exactness, donation/transfer-guard
safety, and 1-vs-4-device sharded parity on the PR-7 forced-host topology."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rl_tpu.collectors import Collector
from rl_tpu.envs import (
    CartPoleEnv,
    RewardSum,
    StepCounter,
    TransformedEnv,
    VmapEnv,
    check_vmap_autoreset,
    fleet_env_names,
    make_fleet,
)
from rl_tpu.modules import MLP, Categorical, ProbabilisticActor, TDModule, ValueOperator
from rl_tpu.objectives import ClipPPOLoss
from rl_tpu.trainers import (
    AnakinConfig,
    AnakinProgram,
    OnPolicyConfig,
    OnPolicyProgram,
)

KEY = jax.random.key(0)


def make_actor_critic():
    actor = ProbabilisticActor(
        TDModule(MLP(out_features=2, num_cells=(32, 32)), ["observation"], ["logits"]),
        Categorical,
        dist_keys=("logits",),
    )
    critic = ValueOperator(MLP(out_features=1, num_cells=(32, 32)))
    loss = ClipPPOLoss(actor, critic)
    loss.make_value_estimator(gamma=0.99, lmbda=0.95)
    policy = lambda p, td, k: actor(p["actor"], td, k)  # noqa: E731
    return policy, loss


def make_program(num_envs=8, unroll=8, steps_per_dispatch=1, mesh=None,
                 device_metrics=True, max_episode_steps=20):
    policy, loss = make_actor_critic()
    cfg = AnakinConfig(
        num_envs=num_envs,
        unroll_length=unroll,
        steps_per_dispatch=steps_per_dispatch,
        num_epochs=2,
        minibatch_size=num_envs * unroll // 2,
    )
    return AnakinProgram(
        "cartpole", policy, loss, cfg, mesh=mesh,
        device_metrics=device_metrics, max_episode_steps=max_episode_steps,
    )


class TestMakeFleet:
    def test_registry(self):
        names = fleet_env_names()
        for n in ("cartpole", "pendulum", "chess", "trading", "hopper"):
            assert n in names
        with pytest.raises(KeyError):
            make_fleet("not_an_env", 4)

    def test_name_and_kwargs(self):
        env = make_fleet("cartpole", 4, max_episode_steps=7)
        assert env.batch_shape == (4,)
        _, td = env.reset(KEY)
        assert "episode_reward" in td  # RewardSum attached

    def test_instance(self):
        env = make_fleet(CartPoleEnv(), 3, episode_return=False)
        assert isinstance(env, VmapEnv)
        with pytest.raises(TypeError):
            make_fleet(CartPoleEnv(), 3, max_episode_steps=5)

    def test_batched_instance_rejected(self):
        with pytest.raises(ValueError):
            make_fleet(VmapEnv(CartPoleEnv(), 2), 4)


# keep heavyweight envs tractable: tiny fleets, short episodes
_FLEET_KWARGS = {
    "chess": {"max_halfmoves": 6},
    "hopper": {"max_episode_steps": 10},
    "walker2d": {"max_episode_steps": 10},
    "trading": {"max_episode_steps": 10},
}


@pytest.mark.parametrize("name", fleet_env_names())
def test_vmap_autoreset_every_fleet_env(name):
    """Every registered fleet env passes the vmap-autoreset conformance
    pass: structure/dtype equivalence with the scalar path and distinct
    per-env PRNG streams across the masked reset merge."""
    env = make_fleet(name, 1, episode_return=False, **_FLEET_KWARGS.get(name, {}))
    check_vmap_autoreset(env.env, KEY, num_envs=3)


class TestPerEnvResetStreams:
    """The batched-key fix: each sub-env re-seeds from its OWN stream."""

    def _fleet_state(self, num_envs=4):
        # max_episode_steps=1 -> every env is done after one step, so a
        # single step_and_reset exercises the batched reset branch for all
        env = make_fleet("cartpole", num_envs, max_episode_steps=1)
        state, td = env.reset(KEY)
        td = td.set("action", jnp.zeros((num_envs,), jnp.int32))
        return env, state, td

    def test_perturbing_one_stream_leaves_others_unchanged(self):
        env, state_a, td = self._fleet_state()
        rng_path = env._rng_path
        rng = state_a[rng_path]
        state_b = state_a.set(rng_path, rng.at[0].set(jax.random.fold_in(rng[0], 7)))

        _, _, carry_a = env.step_and_reset(state_a, td)
        _, _, carry_b = env.step_and_reset(state_b, td)
        obs_a, obs_b = np.asarray(carry_a["observation"]), np.asarray(carry_b["observation"])
        # env 0's post-done reset draw changes with its stream...
        assert not np.array_equal(obs_a[0], obs_b[0])
        # ...and every other env's reset is untouched (the old shared-key
        # scheme derived ALL resets from env 0's stream)
        np.testing.assert_array_equal(obs_a[1:], obs_b[1:])

    def test_reset_draws_distinct_across_fleet(self):
        env, state, td = self._fleet_state()
        _, _, carry = env.step_and_reset(state, td)
        obs = np.asarray(carry["observation"])
        assert len({o.tobytes() for o in obs}) == obs.shape[0]

    def test_carry_streams_stay_distinct(self):
        env, state, td = self._fleet_state()
        new_state, _, _ = env.step_and_reset(state, td)
        raw = np.asarray(jax.random.key_data(new_state[env._rng_path]))
        assert len({r.tobytes() for r in raw.reshape(raw.shape[0], -1)}) == raw.shape[0]


class TestAutoresetBoundary:
    def test_return_and_length_reset_exactly_at_done(self):
        num_envs, horizon = 4, 5
        env = TransformedEnv(
            VmapEnv(CartPoleEnv(max_episode_steps=horizon), num_envs),
            [RewardSum(), StepCounter()],
        )
        coll = Collector(env, frames_per_batch=num_envs * 12)
        batch, _ = jax.jit(coll.collect)({}, coll.init(KEY))
        done = np.asarray(batch["next", "done"])
        er_root = np.asarray(batch["episode_reward"])
        er_next = np.asarray(batch["next", "episode_reward"])
        sc_root = np.asarray(batch["step_count"])
        sc_next = np.asarray(batch["next", "step_count"])
        reward = np.asarray(batch["next", "reward"])

        for t in range(done.shape[0] - 1):
            d = done[t]
            # where done: the NEXT step starts a fresh episode (return and
            # length restart from zero exactly at the boundary)...
            np.testing.assert_array_equal(er_root[t + 1][d], 0.0)
            np.testing.assert_array_equal(sc_root[t + 1][d], 0)
            # ...where alive: accumulation carries over unbroken
            np.testing.assert_array_equal(er_root[t + 1][~d], er_next[t][~d])
            np.testing.assert_array_equal(sc_root[t + 1][~d], sc_next[t][~d])
        # within a step the sum/count advance by exactly this transition
        np.testing.assert_allclose(er_next, er_root + reward, rtol=1e-6)
        np.testing.assert_array_equal(sc_next, sc_root + 1)
        # cartpole with a fixed horizon: every done is at step_count == horizon
        np.testing.assert_array_equal(sc_next[done], horizon)


class TestFusedParity:
    def test_bitwise_matches_on_policy_program(self):
        """Fused dispatch == the host Collector+OnPolicyProgram path, same
        seed: identical composition, so params match exactly."""
        policy, loss = make_actor_critic()
        env = make_fleet("cartpole", 8, max_episode_steps=20)
        coll = Collector(env, policy, frames_per_batch=64)
        ref = OnPolicyProgram(
            coll, loss, OnPolicyConfig(num_epochs=2, minibatch_size=32)
        )
        ts_ref = ref.init(KEY)
        step = jax.jit(ref.train_step)
        for _ in range(3):
            ts_ref, m_ref = step(ts_ref)

        prog = make_program(num_envs=8, unroll=8, device_metrics=False)
        ts = prog.init(KEY)
        for _ in range(3):
            ts, _, m = prog.dispatch(ts)

        for a, b in zip(jax.tree.leaves(ts_ref["params"]), jax.tree.leaves(ts["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(m_ref["loss"]) == pytest.approx(float(m["loss"]), abs=1e-6)

    def test_steps_per_dispatch_equivalent(self):
        """4 dispatches of 1 step == 1 dispatch of 4 scanned steps."""
        p1 = make_program(device_metrics=False, steps_per_dispatch=1)
        p4 = make_program(device_metrics=False, steps_per_dispatch=4)
        ts1, ts4 = p1.init(KEY), p4.init(KEY)
        for _ in range(4):
            ts1, _, _ = p1.dispatch(ts1)
        ts4, _, _ = p4.dispatch(ts4)
        for a, b in zip(jax.tree.leaves(ts1["params"]), jax.tree.leaves(ts4["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    def test_metrics_accumulation(self):
        prog = make_program(steps_per_dispatch=2)
        ts = prog.init(KEY)
        ts, snap = prog.run(ts, 3)
        flat = prog.device_metrics.to_flat(snap)
        assert flat["env_steps"] == prog.env_steps_per_dispatch * 3
        assert flat["updates"] == 6.0
        assert flat["episodes"] > 0
        assert np.isfinite(flat["loss"])


class TestDonationSafety:
    def test_dispatch_no_implicit_transfers(self):
        """The fused step makes ZERO implicit host transfers; the only
        host<->device traffic per dispatch is the explicit metrics drain."""
        prog = make_program()
        ts = prog.init(KEY)
        dm = prog.init_metrics()
        ts, dm, _ = prog.dispatch(ts, dm)  # compile outside the guard
        with jax.transfer_guard("disallow"):
            for _ in range(2):
                ts, dm, _ = prog.dispatch(ts, dm)
                prog.device_metrics.drain_async(dm)
            snap = prog.device_metrics.drain(dm)  # explicit device_get: legal
        assert prog.device_metrics.to_flat(snap)["env_steps"] == 3 * prog.env_steps_per_dispatch

    def test_lagged_snapshot_survives_donation(self):
        """dm is NOT donated: the previous dispatch's snapshot must stay
        readable while the next dispatch is in flight (the lagged drain)."""
        prog = make_program()
        ts = prog.init(KEY)
        dm = prog.init_metrics()
        ts, dm1, _ = prog.dispatch(ts, dm)
        prog.device_metrics.drain_async(dm1)
        ts, dm2, _ = prog.dispatch(ts, dm1)  # donates ts, must not clobber dm1
        snap1 = prog.device_metrics.drain(dm1)
        assert prog.device_metrics.to_flat(snap1)["env_steps"] == prog.env_steps_per_dispatch
        snap2 = prog.device_metrics.drain(dm2)
        assert prog.device_metrics.to_flat(snap2)["env_steps"] == 2 * prog.env_steps_per_dispatch

    def test_run_loop(self):
        prog = make_program()
        ts = prog.init(KEY)
        ts, snap = prog.run(ts, 2)
        assert prog.device_metrics.to_flat(snap)["env_steps"] == 2 * prog.env_steps_per_dispatch


@pytest.mark.mesh
class TestShardedAnakin:
    def test_1_vs_4_device_parity(self):
        """Same seed on 1 device vs a (batch=4) mesh: params agree to
        within reduction-reorder noise (PR-7 tolerance reasoning: Adam's
        first-step normalization amplifies f32 reassociation toward
        O(lr); lr/3 with lr=3e-4 gives 5x headroom over observed)."""
        from rl_tpu.parallel import make_fsdp_mesh

        p0 = make_program(device_metrics=False)
        mesh = make_fsdp_mesh(fsdp=1, batch=4, devices=jax.devices()[:4])
        p4 = make_program(device_metrics=False, mesh=mesh)
        ts0, ts4 = p0.init(KEY), p4.init(KEY)
        for _ in range(2):
            ts0, _, _ = p0.dispatch(ts0)
            ts4, _, _ = p4.dispatch(ts4)
        maxdiff = max(
            float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
            for a, b in zip(jax.tree.leaves(ts0["params"]), jax.tree.leaves(ts4["params"]))
        )
        assert maxdiff < 1e-4, f"sharded fused program diverged: {maxdiff}"

    def test_fsdp_mesh_runs_and_keeps_layout(self):
        from rl_tpu.parallel import make_fsdp_mesh

        mesh = make_fsdp_mesh(fsdp=2, batch=2, devices=jax.devices()[:4])
        prog = make_program(mesh=mesh)
        prog.config.fsdp_min_size_mb = 0.0
        ts = prog.init(KEY)
        env_rng = ts["collector"]["env"][prog.env._rng_path]
        assert not env_rng.sharding.is_fully_replicated  # per-env streams shard
        assert ts["rng"].sharding.is_fully_replicated  # program key replicates
        ts, snap = prog.run(ts, 2)
        post = ts["collector"]["env"][prog.env._rng_path]
        assert post.sharding == env_rng.sharding  # pinned layout, no reshard
        assert prog.device_metrics.to_flat(snap)["env_steps"] == 2 * prog.env_steps_per_dispatch


@pytest.mark.mesh
class TestTrainStateShardings:
    def test_batched_env_keys_shard_scalar_keys_replicate(self):
        from rl_tpu.parallel import make_fsdp_mesh, shard_train_state, train_state_shardings

        mesh = make_fsdp_mesh(fsdp=2, batch=4)
        num_envs = 8
        ts = {
            "collector": {
                "obs": jnp.ones((num_envs, 3)),
                "rng": jax.random.split(jax.random.key(2), num_envs),
                "scalar_rng": jax.random.key(3),
            },
            "rng": jax.random.key(1),
        }
        sh = train_state_shardings(ts, mesh, num_envs)
        assert sh["collector"]["obs"].spec == sh["collector"]["rng"].spec
        out = shard_train_state(ts, mesh, num_envs)
        assert not out["collector"]["rng"].sharding.is_fully_replicated
        assert out["collector"]["scalar_rng"].sharding.is_fully_replicated
        assert out["rng"].sharding.is_fully_replicated
