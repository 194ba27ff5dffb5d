"""Learning-QUALITY tests: losses that go down is not enough — reward must
go up, so a silently-broken loss (sign flip, detached grad, wrong target)
fails the suite.

Reference test model: rllib/tuned_examples/ (CI runs algorithms to a reward
threshold); scaled to the 1-core dev box with fixed seeds and bounded
iteration counts, asserting improvement over the untrained/behavior policy
rather than full convergence.
"""

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def cluster(cpu_jax):
    ray_tpu.init(num_cpus=3)
    yield
    ray_tpu.shutdown()


def _mean_tail(history, k=3):
    return float(np.mean(history[-k:]))


def test_ppo_improves_cartpole(cluster):
    """PPO lifts CartPole return well above the random-policy baseline
    (~20) within a bounded budget (rllib/tuned_examples/ppo analog)."""
    from ray_tpu.rl.algorithm import PPO
    from ray_tpu.rl.ppo import PPOConfig

    algo = PPO(PPOConfig(num_env_runners=2, envs_per_runner=4,
                         rollout_length=128, minibatches=4, epochs=4))
    try:
        history = []
        for _ in range(20):
            r = algo.train()
            if r["episode_return_mean"]:
                history.append(r["episode_return_mean"])
        early = float(np.mean(history[:3]))
        late = _mean_tail(history)
        assert late > early + 15, (early, late, history)
        assert late > 45, (late, history)  # random policy: ~20
    finally:
        algo.stop()


def test_dqn_improves_cartpole(cluster):
    from ray_tpu.rl.dqn import DQN, DQNConfig

    algo = DQN(DQNConfig(num_env_runners=2, envs_per_runner=4,
                         rollout_length=64, learning_starts=256,
                         train_batch_size=128, updates_per_iteration=48,
                         epsilon_decay_steps=3_000, lr=2e-3,
                         target_update_tau=0.05))
    try:
        history = []
        for _ in range(24):
            r = algo.train()
            if r["episode_return_mean"]:
                history.append(r["episode_return_mean"])
        early = float(np.mean(history[:3]))
        late = _mean_tail(history)
        assert late > early + 10, (early, late, history)
        assert late > 40, (late, history)
    finally:
        algo.stop()


def test_cql_beats_behavior_policy(cpu_jax, tmp_path):
    """CQL trained on a RANDOM-policy dataset must act better than the
    behavior policy that produced the data (the whole point of offline
    RL), evaluated greedily in the live env
    (rllib/algorithms/cql analog on the discrete critic)."""
    from ray_tpu.rl.cql import CQL, CQLConfig
    from ray_tpu.rl.env import make_env
    from ray_tpu.rl.offline import collect_episodes, read_episodes

    path = collect_episodes("CartPole-v1", str(tmp_path / "data"),
                            n_steps=8_192, seed=0)
    data = read_episodes(path)
    assert "next_obs" in data  # transition-complete shards

    # Behavior (random) policy baseline: mean episode length in the data.
    dones = data["dones"]
    behavior_return = len(dones) / max(1.0, float(dones.sum()))

    algo = CQL(CQLConfig(alpha=1.0, epochs=30, batch_size=512,
                         lr=3e-4), path, seed=0)
    algo.train()

    env = make_env("CartPole-v1", 8, seed=123)
    obs = env.reset()
    done_count, step_count = 0.0, 0
    for _ in range(400):
        obs, _r, done = env.step(algo.greedy_actions(obs))
        done_count += float(done.sum())
        step_count += len(done)
    eval_return = step_count / max(1.0, done_count)
    assert eval_return > behavior_return * 1.5, \
        (behavior_return, eval_return)
    assert eval_return > 40, (behavior_return, eval_return)


def test_cql_conservatism_vs_dqn_offline(cpu_jax, tmp_path):
    """The conservative term must actually bite: on the same offline data,
    CQL's Q-values for out-of-distribution (greedy) actions stay below
    plain offline double-DQN's (alpha=0), the over-estimation CQL exists
    to fix."""
    from ray_tpu.rl.cql import CQL, CQLConfig
    from ray_tpu.rl.offline import collect_episodes

    path = collect_episodes("CartPole-v1", str(tmp_path / "data"),
                            n_steps=4_096, seed=1)
    conservative = CQL(CQLConfig(alpha=2.0, epochs=15, batch_size=512), path)
    plain = CQL(CQLConfig(alpha=0.0, epochs=15, batch_size=512), path)
    conservative.train()
    plain.train()
    obs = conservative.batch["obs"][:512]
    q_cons = conservative.q_values(obs).max(-1).mean()
    q_plain = plain.q_values(obs).max(-1).mean()
    assert q_cons < q_plain, (q_cons, q_plain)


def test_dreamerv3_improves_cartpole(cpu_jax):
    """DreamerV3's imagination-trained policy lifts CartPole return above
    the random baseline (~20) within a bounded budget
    (rllib/algorithms/dreamerv3 tuned-example analog). Smoke + learning:
    the world model, imagination rollout, and actor-critic all engage."""
    from ray_tpu.rl.dreamerv3 import DreamerV3, DreamerV3Config

    algo = DreamerV3(DreamerV3Config(
        envs=8, rollout_length=64, batch_size=8, seq_len=16, horizon=8,
        learning_starts=512, updates_per_iteration=8), seed=0)
    history = []
    for _ in range(30):
        r = algo.train()
        if r["episode_return_mean"]:
            history.append(r["episode_return_mean"])
    assert r["episodes_total"] > 10
    assert np.isfinite(r["wm_loss"])
    final = _mean_tail(history)
    assert final > 60.0, (
        f"no learning: final={final:.1f} "
        f"history={[round(h, 1) for h in history]}")


# ---- multi-agent (reference: rllib/env/multi_agent_env.py) ---------------

def test_multi_agent_env_protocol():
    from ray_tpu.rl.multi_agent import CooperativeReach

    env = CooperativeReach(n_envs=4, grid=5, seed=0)
    obs = env.reset()
    assert set(obs) == {"a0", "a1"}
    assert obs["a0"].shape == (4, 10)
    acts = {"a0": np.full(4, 2), "a1": np.zeros(4, dtype=int)}
    obs2, rewards, done = env.step(acts)
    assert set(rewards) == {"a0", "a1"}
    assert rewards["a0"].shape == (4,) and done.shape == (4,)
    # Team reward is shared (cooperative).
    np.testing.assert_array_equal(rewards["a0"], rewards["a1"])


def test_multi_agent_two_policy_cooperative_learning():
    """Review item 8 'done': a 2-policy cooperative gridworld LEARNS —
    mean team return improves significantly over training."""
    from ray_tpu.rl.multi_agent import (CooperativeReach, MultiAgentConfig,
                                        MultiAgentPPO)

    env = CooperativeReach(n_envs=16, grid=5, max_steps=32, seed=1)
    config = MultiAgentConfig.from_env(
        env, shared=False, rollout_length=32, n_envs=16,
        hidden=(32, 32), lr=3e-3, epochs=4, minibatches=2)
    assert len(config.policies) == 2  # independent policy per agent
    algo = MultiAgentPPO(env, config, seed=1)

    first = [algo.train()["episode_return_mean"] for _ in range(3)]
    for _ in range(35):
        last = algo.train()
    baseline = np.mean(first)
    trained = last["episode_return_mean"]
    # Random walk hovers deeply negative (distance penalties, rare joint
    # arrivals); trained agents coordinate to the goals fast.
    assert trained > baseline + 0.3, (baseline, trained)
    assert trained > 0.0, trained
    # Per-policy learner metrics flowed through.
    assert any(k.startswith("p_a0/") for k in last)
    assert any(k.startswith("p_a1/") for k in last)


def test_multi_agent_shared_policy_learning():
    """Shared mapping: both agents drive ONE policy (homogeneous spaces),
    and the task still learns."""
    from ray_tpu.rl.multi_agent import (CooperativeReach, MultiAgentConfig,
                                        MultiAgentPPO)

    env = CooperativeReach(n_envs=16, grid=5, max_steps=32, seed=2)
    config = MultiAgentConfig.from_env(
        env, shared=True, rollout_length=32, n_envs=16,
        hidden=(32, 32), lr=3e-3, epochs=4, minibatches=2)
    assert list(config.policies) == ["shared"]
    algo = MultiAgentPPO(env, config, seed=2)
    first = algo.train()["episode_return_mean"]
    for _ in range(35):
        last = algo.train()
    assert last["episode_return_mean"] > max(first, -1.0) + 0.3


def test_td3_improves_pendulum(cluster):
    """TD3 (continuous control) lifts Pendulum return far above the
    random-policy baseline (~-1400) within a bounded budget
    (rllib/algorithms/td3 analog; Fujimoto 2018 fixes are all on the
    jitted update path)."""
    from ray_tpu.rl import TD3, TD3Config

    algo = TD3(TD3Config(num_env_runners=2, envs_per_runner=4,
                         rollout_length=64))
    try:
        history = []
        for _ in range(40):
            r = algo.train()
            if r["episode_return_mean"]:
                history.append(r["episode_return_mean"])
        early = float(np.mean(history[:3]))
        late = _mean_tail(history)
        # `early` is measured after ~768 warm-start updates and can
        # already be above random on a fast seed — anchor the improvement
        # bar at the random-policy level (~-1400) so fast early learning
        # can't fail the relative check.
        assert late > min(early, -1100) + 300, (early, late, history)
        assert late > -950, (late, history)  # random policy: ~-1400
    finally:
        algo.stop()


def test_sac_continuous_improves_pendulum(cluster):
    """Continuous SAC (reparameterized tanh-gaussian actor, learned
    temperature) lifts Pendulum return far above the random baseline
    (rllib/algorithms/sac analog — the reference's primary SAC form;
    the discrete variant is covered separately)."""
    from ray_tpu.rl import SACContinuous, SACContinuousConfig

    algo = SACContinuous(SACContinuousConfig(
        num_env_runners=2, envs_per_runner=4, rollout_length=64))
    try:
        history = []
        for _ in range(30):
            r = algo.train()
            if r["episode_return_mean"]:
                history.append(r["episode_return_mean"])
        early = float(np.mean(history[:3]))
        late = _mean_tail(history)
        assert late > min(early, -1100) + 300, (early, late, history)
        assert late > -750, (late, history)  # random policy: ~-1400
        assert 0.0 < r["alpha"] < 2.0  # temperature adapted, not stuck
    finally:
        algo.stop()
