"""Paper-faithful IMPALA agent networks.

``impala_deep``: the IMPALA "deep" ResNet (15 conv layers: 3 sections of
conv + maxpool + 2 residual blocks; FC 256; policy + baseline heads) — the
network TorchBeast trains on Atari (§4, without LSTM). Its pools'
gradient runs on the Pallas kernels of ``kernels/maxpool.py``.

``minatar_net``: the small ConvNet of the paper's MinAtar adaptation example
(Fig. 2): conv3x3x16 + FC 128 + heads.

Agents are (init, apply) pairs; apply(params, obs) -> AgentOutput. Obs is
(..., H, W, C) float32 (already scaled); leading dims are flattened and
restored so (T, B, ...) learner batches work directly.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import sharding as sharding_lib
from repro.kernels import ops as kernel_ops
from repro.models.common import param, split_params


class AgentOutput(NamedTuple):
    policy_logits: jnp.ndarray  # (..., num_actions)
    baseline: jnp.ndarray       # (...,)


class RecurrentAgentOutput(NamedTuple):
    policy_logits: jnp.ndarray
    baseline: jnp.ndarray
    core_state: tuple           # (h, c) LSTM state, threaded by the actor


def _conv_init(key, kh, kw, cin, cout):
    scale = 1.0 / np.sqrt(kh * kw * cin)
    return {
        "w": param(key, (kh, kw, cin, cout),
                   ("conv_h", "conv_w", "conv_in", "conv_out"), scale=scale),
        "b": param(None, (cout,), ("conv_out",), init="zeros"),
    }


def _conv(p, x, stride=1):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def _linear_init(key, din, dout, scale=None):
    return {
        "w": param(key, (din, dout), ("fc_in", "fc_out"), scale=scale),
        "b": param(None, (dout,), ("fc_out",), init="zeros"),
    }


def _linear(p, x):
    return x @ p["w"] + p["b"]


def _maxpool(x):
    """3x3 max-pool at stride 2 over NHWC. Differentiated, it runs the
    Pallas kernels of ``kernels/maxpool.py`` and keeps each window's
    winner index, not x, for the backward; under a learner's mesh
    (``sharding.use_rules``) each device pools its own batch rows."""
    state = sharding_lib.current_rules()
    with jax.named_scope("maxpool"):
        return _pool(x, x.shape[1:3], state[0] if state else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _pool(x, hw, mesh):
    del hw, mesh
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)])


def _pool_fwd(x, hw, mesh):
    def run(x):     # the kernels' (H, W, C, N) view is XLA's own layout
        out, idx = kernel_ops.maxpool_fwd(x.transpose(1, 2, 3, 0))
        # Without the barrier XLA fuses the next convolution into the
        # transpose back and saves its output beside x + y for the
        # backward: 0.7 ms a learner step by the v5e compiler's cost model.
        out = jax.lax.optimization_barrier(out.transpose(3, 0, 1, 2))
        return out, idx.transpose(3, 0, 1, 2)

    return kernel_ops.per_device(run, mesh, x.shape[0], dim=0)(x)


def _pool_bwd(hw, mesh, idx, dy):
    def run(idx, dy):
        dx = kernel_ops.maxpool_bwd(idx.transpose(1, 2, 3, 0),
                                    dy.transpose(1, 2, 3, 0), hw=hw)
        return dx.transpose(3, 0, 1, 2)

    return (kernel_ops.per_device(run, mesh, dy.shape[0], dim=0)(idx, dy),)


_pool.defvjp(_pool_fwd, _pool_bwd)


# ---------------------------------------------------------------------------
# IMPALA deep ResNet
# ---------------------------------------------------------------------------

def impala_deep(obs_shape, num_actions, channels=(16, 32, 32), fc=256):
    h, w, c_in = obs_shape

    def init(key):
        p = {"sections": []}
        cin = c_in
        sh, sw = h, w
        for ch in channels:
            key, k1, k2, k3, k4, k5 = jax.random.split(key, 6)
            p["sections"].append({
                "conv": _conv_init(k1, 3, 3, cin, ch),
                "res": [
                    {"c1": _conv_init(k2, 3, 3, ch, ch),
                     "c2": _conv_init(k3, 3, 3, ch, ch)},
                    {"c1": _conv_init(k4, 3, 3, ch, ch),
                     "c2": _conv_init(k5, 3, 3, ch, ch)},
                ],
            })
            cin = ch
            sh, sw = -(-sh // 2), -(-sw // 2)
        flat = sh * sw * channels[-1]
        key, k1, k2, k3 = jax.random.split(key, 4)
        p["fc"] = _linear_init(k1, flat, fc)
        p["policy"] = _linear_init(k2, fc, num_actions, scale=0.01)
        p["baseline"] = _linear_init(k3, fc, 1, scale=0.01)
        return p

    def apply(params, obs):
        lead = obs.shape[:-3]
        x = obs.reshape((-1,) + obs.shape[-3:]).astype(jnp.float32)
        for sec in params["sections"]:
            x = _conv(sec["conv"], x)
            x = _maxpool(x)
            for res in sec["res"]:
                y = _conv(res["c1"], jax.nn.relu(x))
                y = _conv(res["c2"], jax.nn.relu(y))
                x = x + y
        x = jax.nn.relu(x).reshape(x.shape[0], -1)
        x = jax.nn.relu(_linear(params["fc"], x))
        logits = _linear(params["policy"], x)
        baseline = _linear(params["baseline"], x)[..., 0]
        return AgentOutput(logits.reshape(lead + (num_actions,)),
                           baseline.reshape(lead))

    return init, apply


# ---------------------------------------------------------------------------
# MinAtar net (paper Fig. 2)
# ---------------------------------------------------------------------------

def minatar_net(obs_shape, num_actions, conv_ch=16, fc=128):
    h, w, c_in = obs_shape

    def init(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        flat = (h - 2) * (w - 2) * conv_ch
        return {
            "conv": _conv_init(k1, 3, 3, c_in, conv_ch),
            "core": _linear_init(k2, flat, fc),
            "policy": _linear_init(k3, fc, num_actions, scale=0.01),
            "baseline": _linear_init(k4, fc, 1, scale=0.01),
        }

    def apply(params, obs):
        lead = obs.shape[:-3]
        x = obs.reshape((-1,) + obs.shape[-3:]).astype(jnp.float32)
        y = jax.lax.conv_general_dilated(
            x, params["conv"]["w"], (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + params["conv"]["b"]
        y = jax.nn.relu(y).reshape(y.shape[0], -1)
        y = jax.nn.relu(_linear(params["core"], y))
        logits = _linear(params["policy"], y)
        baseline = _linear(params["baseline"], y)[..., 0]
        return AgentOutput(logits.reshape(lead + (num_actions,)),
                           baseline.reshape(lead))

    return init, apply


# ---------------------------------------------------------------------------
# recurrent agent: ConvNet torso + LSTM core (TorchBeast's core_state API)
# ---------------------------------------------------------------------------

def minatar_lstm_net(obs_shape, num_actions, conv_ch=16, core=128):
    """MinAtar ConvNet torso + LSTM core. apply(params, obs, core_state,
    done) -> RecurrentAgentOutput; obs is a single step (B, H, W, C) — the
    rollout threads core_state exactly like TorchBeast's agent interface,
    resetting it where done=True."""
    h, w, c_in = obs_shape

    def init(key):
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        flat = (h - 2) * (w - 2) * conv_ch
        return {
            "conv": _conv_init(k1, 3, 3, c_in, conv_ch),
            "torso": _linear_init(k2, flat, core),
            "lstm_x": _linear_init(k5, core, 4 * core,
                                   scale=core ** -0.5),
            "lstm_h": _linear_init(jax.random.fold_in(k5, 1), core,
                                   4 * core, scale=core ** -0.5),
            "policy": _linear_init(k3, core, num_actions, scale=0.01),
            "baseline": _linear_init(k4, core, 1, scale=0.01),
        }

    def initial_state(batch):
        z = jnp.zeros((batch, core), jnp.float32)
        return (z, z)

    def apply(params, obs, core_state, done=None):
        x = obs.astype(jnp.float32)
        y = jax.lax.conv_general_dilated(
            x, params["conv"]["w"], (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + params["conv"]["b"]
        y = jax.nn.relu(y).reshape(y.shape[0], -1)
        y = jax.nn.relu(_linear(params["torso"], y))
        hs, cs = core_state
        if done is not None:  # TorchBeast: zero the state at episode ends
            keep = (~done)[:, None].astype(hs.dtype)
            hs, cs = hs * keep, cs * keep
        gates = _linear(params["lstm_x"], y) + _linear(params["lstm_h"], hs)
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        cs = jax.nn.sigmoid(f + 1.0) * cs + jax.nn.sigmoid(i) * jnp.tanh(g)
        hs = jax.nn.sigmoid(o) * jnp.tanh(cs)
        logits = _linear(params["policy"], hs)
        baseline = _linear(params["baseline"], hs)[..., 0]
        return RecurrentAgentOutput(logits, baseline, (hs, cs))

    return init, apply, initial_state


def init_agent(init_fn, key):
    """Split an agent's AxisParam tree into (values, axes)."""
    return split_params(init_fn(key))
