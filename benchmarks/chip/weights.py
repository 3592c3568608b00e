"""Seeded weights in the program's parameter layout, made by the benchmark.

Both the system under test and the plain references take their weights
from here, so a reference never reads weights the program made. Each
generator is one jitted call from a key, on the device, in the dtype the
program keeps them in. Values are uniform with the variance of the
program's own initialisation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: str) -> jax.Array:
    """A PRNG key for one named stream of a run's seed (any size int)."""
    words = [int(x) for x in np.frombuffer(stream.encode(), np.uint8)]
    state = np.random.SeedSequence([int(seed) % 2**64] + words)
    return jax.random.PRNGKey(int(state.generate_state(1)[0]))


def _uniform(key, shape, std, dtype=jnp.float32):
    a = float(std) * np.sqrt(3.0)
    return jax.random.uniform(key, shape, dtype, -a, a)


# ---------------------------------------------------------------------------
# IMPALA deep ResNet (models/convnet.impala_deep layout)


def impala_shapes(obs_shape, num_actions, channels, fc):
    """{path: shape} of the deep ResNet, in the program's tree layout."""
    h, w, cin = obs_shape
    out = {}
    for i, ch in enumerate(channels):
        out[("sections", i, "conv")] = (3, 3, cin, ch)
        for r in range(2):
            for c in ("c1", "c2"):
                out[("sections", i, "res", r, c)] = (3, 3, ch, ch)
        cin = ch
        h, w = -(-h // 2), -(-w // 2)
    out[("fc",)] = (h * w * channels[-1], fc)
    out[("policy",)] = (fc, num_actions)
    out[("baseline",)] = (fc, 1)
    return out


@functools.partial(jax.jit, static_argnames=("obs_shape", "num_actions",
                                             "channels", "fc"))
def impala_params(key, *, obs_shape, num_actions, channels, fc):
    shapes = impala_shapes(obs_shape, num_actions, channels, fc)
    keys = jax.random.split(key, 2 * len(shapes))
    layers = {}
    for i, (path, shape) in enumerate(shapes.items()):
        fan_in = int(np.prod(shape[:-1]))
        std = 0.01 if path[0] in ("policy", "baseline") else fan_in ** -0.5
        layers[path] = {"w": _uniform(keys[2 * i], shape, std),
                        "b": _uniform(keys[2 * i + 1], shape[-1:], 0.01)}
    tree = {"sections": [
        {"conv": layers[("sections", i, "conv")],
         "res": [{c: layers[("sections", i, "res", r, c)]
                  for c in ("c1", "c2")} for r in range(2)]}
        for i in range(len(channels))]}
    for name in ("fc", "policy", "baseline"):
        tree[name] = layers[(name,)]
    return tree
