"""On-device rollout generation (the PolyBeast->TPU adaptation).

Instead of gRPC environment servers feeding C++ actor threads, the
environments are pure JAX and the whole actor loop — policy evaluation,
action sampling, env step — runs inside one compiled ``lax.scan``
(Podracer/Anakin style). Batched over B envs with vmap; distributed over
the mesh data axis by the launcher.

The rollout layout matches the paper's learner-input dict (§2): time-major
(T+1 obs; T actions/rewards/dones/behavior outputs), so the learner code is
identical for host-loop and on-device actors.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def make_unroll(env, agent_apply, unroll_length: int):
    """Build unroll(params, carry, key) -> (carry, rollout).

    carry = (env_state, obs) batched over B. rollout dict:
      obs             (T+1, B, *obs_shape)
      action          (T, B) int32
      behavior_logits (T, B, A) float32
      reward, done    (T, B)
    """
    v_step = jax.vmap(env.step, in_axes=(0, 0, 0))

    def unroll(params, carry, key):
        def one_step(carry, key):
            env_state, obs = carry
            with jax.named_scope("actor_forward"):
                out = agent_apply(params, obs)
                action = jax.random.categorical(key, out.policy_logits)
            with jax.named_scope("env_step"):
                keys = jax.random.split(jax.random.fold_in(key, 1),
                                        obs.shape[0])
                env_state, next_obs, reward, done = v_step(env_state,
                                                           action, keys)
            step_data = {
                "obs": obs,
                "action": action.astype(jnp.int32),
                "behavior_logits": out.policy_logits,
                "reward": reward,
                "done": done,
            }
            return (env_state, next_obs), step_data

        # "rollout" holds the scan itself: stacking each step's outputs
        # and the T+1 observations (the body's ops keep their own scopes)
        with jax.named_scope("rollout"):
            keys = jax.random.split(key, unroll_length)
            carry, traj = jax.lax.scan(one_step, carry, keys)
            rollout = {
                "obs": jnp.concatenate([traj["obs"], carry[1][None]],
                                       axis=0),
                "action": traj["action"],
                "behavior_logits": traj["behavior_logits"],
                "reward": traj["reward"],
                "done": traj["done"],
            }
        return carry, rollout

    return unroll


def env_reset_batch(env, key, batch: int):
    keys = jax.random.split(key, batch)
    state, obs = jax.vmap(env.reset)(keys)
    return state, obs


def episode_returns(rollout) -> Dict[str, jnp.ndarray]:
    """Diagnostics: per-batch mean reward and episode termination count."""
    return {
        "reward_per_step": rollout["reward"].mean(),
        "episodes_ended": rollout["done"].sum(),
    }


def make_recurrent_unroll(env, agent_apply, agent_initial_state,
                          unroll_length: int):
    """Recurrent-agent unroll (TorchBeast core_state contract): the actor
    threads the LSTM state through the episode, resets it on done, and the
    rollout records the INITIAL core_state so the learner can re-run the
    recurrence from the same point.

    carry = (env_state, obs, core_state); rollout adds "core_state" (the
    state at the start of the unroll) and "done" is consumed by the agent
    to zero its state mid-unroll.
    """
    v_step = jax.vmap(env.step, in_axes=(0, 0, 0))

    def initial_carry(env_state, obs, batch):
        return (env_state, obs, agent_initial_state(batch),
                jnp.zeros((batch,), bool))

    def unroll(params, carry, key):
        env_state, obs, core_state, done0 = carry
        initial_core = core_state

        def one_step(c, key):
            env_state, obs, core_state, pre_done = c
            with jax.named_scope("actor_forward"):
                out = agent_apply(params, obs, core_state, pre_done)
                action = jax.random.categorical(key, out.policy_logits)
            with jax.named_scope("env_step"):
                keys = jax.random.split(jax.random.fold_in(key, 1),
                                        obs.shape[0])
                env_state, next_obs, reward, next_done = v_step(
                    env_state, action, keys)
            step_data = {
                "obs": obs,
                "pre_done": pre_done,  # obs[t] starts a fresh episode
                "action": action.astype(jnp.int32),
                "behavior_logits": out.policy_logits,
                "reward": reward,
                "done": next_done,     # episode ended on this transition
            }
            return (env_state, next_obs, out.core_state, next_done), \
                step_data

        with jax.named_scope("rollout"):
            keys = jax.random.split(key, unroll_length)
            (env_state, obs, core_state, done), traj = jax.lax.scan(
                one_step, (env_state, obs, core_state, done0), keys)
            rollout = {
                "obs": jnp.concatenate([traj["obs"], obs[None]], axis=0),
                "pre_done": jnp.concatenate([traj["pre_done"], done[None]],
                                            axis=0),
                "action": traj["action"],
                "behavior_logits": traj["behavior_logits"],
                "reward": traj["reward"],
                "done": traj["done"],
                "core_state": initial_core,
            }
        return (env_state, obs, core_state, done), rollout

    unroll.initial_carry = initial_carry
    return unroll
