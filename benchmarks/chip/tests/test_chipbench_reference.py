"""The plain reference agrees with the program at a reduced size on the
CPU, where both compute in float32 at "highest" precision; its fp8
control rounds as float8 e4m3 does."""

import copy
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, "..", "..", ".."))
sys.path[:0] = [os.path.join(ROOT, "benchmarks")]

from chip import weights  # noqa: E402
from chip.envs import atari_synth  # noqa: E402
from chip.reference import impala as ref_impala  # noqa: E402


def _config(name):
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           name + ".json")) as f:
        return json.load(f)


def tiny_impala():
    cfg = copy.deepcopy(_config("impala-deep-atari"))
    cfg["model"].update(obs_shape=[12, 12, 4], channels=[4, 8, 8], fc=16)
    cfg["env"].update(obs_shape=[12, 12, 4], sprite_half=1)
    return cfg


def test_impala_reference_matches_program_learner_step():
    from repro.configs.atari_impala import TRAIN
    from repro.core import learner, rollout
    from repro.models.convnet import impala_deep
    from repro.optim import make_optimizer
    cfg = tiny_impala()
    net = cfg["model"]
    t, b = 6, 4
    hp = dataclasses.replace(TRAIN, unroll_length=t, batch_size=b,
                             **cfg["train"])
    shape = dict(obs_shape=tuple(net["obs_shape"]),
                 num_actions=net["num_actions"],
                 channels=tuple(net["channels"]), fc=net["fc"])
    _, apply_fn = impala_deep(shape["obs_shape"], shape["num_actions"],
                              channels=shape["channels"], fc=shape["fc"])
    params = weights.impala_params(jax.random.PRNGKey(1), **shape)
    env = atari_synth.make(**cfg["env"])
    carry = rollout.env_reset_batch(env, jax.random.PRNGKey(2), b)
    _, batch = jax.jit(rollout.make_unroll(env, apply_fn, t))(
        params, carry, jax.random.PRNGKey(3))
    batch = dict(batch, reward=batch["reward"] + 0.5 * jax.random.normal(
        jax.random.PRNGKey(4), batch["reward"].shape))
    opt = make_optimizer(hp)
    with jax.default_matmul_precision("highest"):
        step = jax.jit(learner.make_train_step(apply_fn, opt, hp))
        p_prog, s_prog, metrics = step(params, opt.init(params),
                                       jnp.int32(5), batch)
    items = tuple(sorted(dict(
        discount=hp.discount, vtrace_rho_clip=hp.vtrace_rho_clip,
        vtrace_c_clip=hp.vtrace_c_clip, baseline_cost=hp.baseline_cost,
        entropy_cost=hp.entropy_cost, grad_clip=hp.grad_clip,
        total_steps=hp.total_steps, learning_rate=hp.learning_rate,
        rmsprop_decay=hp.rmsprop_decay, rmsprop_eps=hp.rmsprop_eps).items()))
    ms0 = jax.tree.map(jnp.zeros_like, params)
    p_ref, ms_ref, total, _, _ = ref_impala.train_step(
        params, ms0, jnp.int32(5), batch, hp_items=items, block=7 * b // 2)
    np.testing.assert_allclose(float(total), float(metrics["loss"]),
                               rtol=1e-5)
    for a, c in zip(jax.tree.leaves(s_prog["ms"]), jax.tree.leaves(ms_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-3,
                                   atol=1e-12)
    for a, c in zip(jax.tree.leaves(p_prog), jax.tree.leaves(p_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-5,
                                   atol=1e-7)


def test_impala_maxpool_and_conv_match_lax():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 21, 21, 3)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 3, 5)), jnp.float32)
    b = jnp.asarray(rng.standard_normal(5), jnp.float32)
    want = jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision="highest") + b
    np.testing.assert_allclose(ref_impala._conv3x3(x, w, b), want,
                               atol=1e-5)
    pool = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1),
                                 [(0, 0), (1, 1), (1, 1), (0, 0)])
    np.testing.assert_array_equal(ref_impala._maxpool3x3s2(x), pool)


def test_fp8_control_rounds_operands_and_gradients():
    x = jnp.asarray([448.0, 1.0, 1.0 + 2.0 ** -4, 1.0 + 2.0 ** -3, -3.3],
                    jnp.float32)
    q = np.asarray(ref_impala.fp8(x))
    # the largest magnitude maps to e4m3's largest; 3 mantissa bits below
    np.testing.assert_array_equal(q[:4], [448.0, 1.0, 1.0, 1.125])
    assert q[4] == -3.25
    # the backward products take fp8 operands too: x^T (fp8 ct) for w
    a = jnp.asarray([[448.0, 2.0], [1.0, 4.0]])      # exact in e4m3
    w = jnp.eye(2)
    ct = jnp.asarray([[448.0, 1.0 + 2.0 ** -4], [0.0, 1.0]])
    dw = jax.grad(lambda w: jnp.sum(ref_impala.fp8_dot(a, w) * ct))(w)
    q_ct = np.array([[448.0, 1.0], [0.0, 1.0]])
    np.testing.assert_array_equal(np.asarray(dw), np.asarray(a).T @ q_ct)
