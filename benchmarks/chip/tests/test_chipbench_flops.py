"""The chip benchmark's analytic counts and peaks table."""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path[:0] = [os.path.join(os.path.dirname(__file__), "..", "..")]

from chip import flops, weights  # noqa: E402


def test_convnet_forward_flops_match_cost_analysis():
    from repro.models.convnet import impala_deep
    obs_shape, channels, fc, actions = (20, 20, 4), (4, 8, 8), 32, 18
    _, apply_fn = impala_deep(obs_shape, actions, channels=channels, fc=fc)
    params = weights.impala_params(jax.random.PRNGKey(0),
                                   obs_shape=obs_shape, num_actions=actions,
                                   channels=channels, fc=fc)
    n = 6
    obs = jnp.zeros((n,) + obs_shape, jnp.float32)
    cost = jax.jit(apply_fn).lower(params, obs).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    want = n * flops.impala_forward_flops(obs_shape, channels, fc, actions)
    # XLA also counts the elementwise ops (bias, ReLU, residual adds,
    # pooling) that the analytic count leaves out: a few percent here
    assert want <= cost["flops"] <= 1.1 * want


def test_step_flops_are_actor_plus_learner():
    shape, ch, fc, a = (84, 84, 4), (16, 32, 32), 256, 18
    fwd = flops.impala_forward_flops(shape, ch, fc, a)
    first = flops.impala_conv_flops(shape, ch)[0][0]
    assert flops.impala_step_flops(shape, ch, fc, a, 80, 32) == \
        80 * 32 * fwd + 81 * 32 * (3 * fwd - first)


def test_unknown_device_kind_is_an_error():
    assert flops.peaks("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("cpu")
