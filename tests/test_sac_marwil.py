"""SAC (discrete) + MARWIL (offline): learning-progress tests on CartPole.

SAC and an offline algorithm on the existing env-runner/learner split.
Mirrors the reference's learning tests (rllib/algorithms/sac/tests,
rllib/algorithms/marwil/tests): train a small number of iterations on the
CPU mesh and assert a reward threshold — not convergence to optimal, which
would be flaky on one core.
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rllib.marwil import MARWILConfig, collect_episodes
from ray_tpu.rllib.sac import SACConfig

pytest.importorskip("gymnasium")


def _angle_policy(obs: np.ndarray) -> int:
    """Near-expert scripted CartPole controller: push toward the pole's
    fall direction (reaches ~200 return) — the offline 'expert'."""
    angle, ang_vel = obs[2], obs[3]
    return 1 if angle + 0.5 * ang_vel > 0 else 0


def test_sac_learns_cartpole(ray_cluster):
    cfg = SACConfig(num_env_runners=2, num_envs_per_runner=2,
                    rollout_fragment_length=64, learning_starts=256,
                    train_batch_size=128, num_updates_per_iter=24,
                    seed=0)
    algo = cfg.build()
    try:
        best = 0.0
        for _ in range(45):
            result = algo.train()
            if result["episode_return_mean"]:
                best = max(best, result["episode_return_mean"])
            if best >= 50.0:
                break
        # untrained CartPole policies average ~10-20; 50 demonstrates
        # learning within a 1-CPU-budget number of iterations
        assert best >= 50.0, f"SAC failed to learn: best return {best}"
        assert result["alpha"] > 0.0  # temperature stayed positive
    finally:
        algo.stop()


def test_sac_checkpoint_roundtrip(ray_cluster, tmp_path):
    cfg = SACConfig(num_env_runners=1, num_envs_per_runner=1,
                    rollout_fragment_length=16, learning_starts=16,
                    train_batch_size=16, num_updates_per_iter=2, seed=1)
    algo = cfg.build()
    try:
        algo.train()
        path = str(tmp_path / "ck")
        algo.save(path)
        steps = algo._env_steps
        algo2 = SACConfig(num_env_runners=1, num_envs_per_runner=1,
                          seed=2).build()
        try:
            algo2.restore(path)
            assert algo2._env_steps == steps
            import jax

            a = jax.tree.leaves(algo.pi_params)[0]
            b = jax.tree.leaves(algo2.pi_params)[0]
            assert np.allclose(np.asarray(a), np.asarray(b))
        finally:
            algo2.stop()
    finally:
        algo.stop()


def test_marwil_learns_from_offline_expert():
    episodes = collect_episodes("CartPole-v1", _angle_policy,
                                n_episodes=30, seed=7, max_steps=300)
    mean_behavior = float(np.mean(
        [ep["rewards"].sum() for ep in episodes]))
    assert mean_behavior > 100  # the scripted expert is genuinely good
    algo = MARWILConfig(episodes=episodes, beta=1.0, seed=0,
                        num_updates_per_iter=64).build()
    for _ in range(12):
        result = algo.train()
    assert result["loss"] is not None
    score = algo.evaluate(n_episodes=5)
    # advantage-weighted cloning of a >100-return expert must beat random
    # (~20) by a wide margin
    assert score >= 80.0, f"MARWIL eval return {score}"


def test_bc_degenerate_beta_zero():
    """beta=0 is plain behavior cloning (the reference's BC subclasses
    MARWIL exactly this way)."""
    episodes = collect_episodes("CartPole-v1", _angle_policy,
                                n_episodes=20, seed=11, max_steps=300)
    algo = MARWILConfig(episodes=episodes, beta=0.0, seed=0,
                        num_updates_per_iter=64).build()
    for _ in range(8):
        algo.train()
    score = algo.evaluate(n_episodes=3)
    assert score >= 60.0, f"BC eval return {score}"


def test_marwil_requires_offline_data():
    with pytest.raises(ValueError, match="offline"):
        MARWILConfig(episodes=None).build()


def test_cql_learns_from_offline_expert():
    """CQL (reference: rllib/algorithms/cql/): conservative offline
    Q-learning on the same expert episodes MARWIL uses — policy beats
    random by a wide margin without ever touching the live env, and the
    conservative gap shrinks as OOD actions get pushed down."""
    from ray_tpu.rllib.cql import CQLConfig

    episodes = collect_episodes("CartPole-v1", _angle_policy,
                                n_episodes=30, seed=5, max_steps=300)
    algo = CQLConfig(episodes=episodes, cql_alpha=1.0, seed=0,
                     num_updates_per_iter=64).build()
    first_gap = None
    for _ in range(12):
        result = algo.train()
        if first_gap is None:
            first_gap = result["cql_gap"]
    assert result["cql_gap"] < first_gap  # conservatism takes hold
    score = algo.evaluate(n_episodes=4)
    assert score >= 80.0, f"CQL eval return {score}"


def test_cql_requires_offline_data():
    from ray_tpu.rllib.cql import CQLConfig

    with pytest.raises(ValueError, match="offline"):
        CQLConfig(episodes=None).build()
