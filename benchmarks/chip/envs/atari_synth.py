"""Atari-shaped synthetic environment, pure JAX (vmap/scan-able).

The ALE is not available, so this stands in for an emulator at Atari's
shapes: 84x84 grey frames in a 4-frame stack, 18 actions. Each step moves
a few sprites (sprite 0 is steered by the action, the others bounce),
draws one new frame with seeded background noise, and shifts the stack.
Reward is sparse: +1 when the player's sprite overlaps the target sprite,
which then respawns. Episode lengths are heavy-tailed (lognormal). The
state keeps the stack as uint8, as an emulator does, and the observation
is that stack scaled to [0, 1] in float32: a buffer of its own, so that a
source which donates its carry never donates one buffer twice.

It costs little beside the network, as an emulator on the host would.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.envs.base import Env, auto_reset

# ALE's full action set as (dx, dy): NOOP, FIRE, UP, RIGHT, LEFT, DOWN,
# UPRIGHT, UPLEFT, DOWNRIGHT, DOWNLEFT, then the same eight with FIRE.
_MOVES = np.array([(0, 0), (0, 0), (0, -1), (1, 0), (-1, 0), (0, 1),
                   (1, -1), (-1, -1), (1, 1), (-1, 1),
                   (0, -1), (1, 0), (-1, 0), (0, 1),
                   (1, -1), (-1, -1), (1, 1), (-1, 1)], np.float32)


class SynthState(NamedTuple):
    frames: jnp.ndarray   # (H, W, stack) uint8
    pos: jnp.ndarray      # (K, 2) sprite centres (x, y); 0 = player, 1 = target
    vel: jnp.ndarray      # (K, 2)
    t: jnp.ndarray        # int32 step within the episode
    length: jnp.ndarray   # int32 episode length


def make(obs_shape=(84, 84, 4), num_actions=18, sprites=5, sprite_half=3,
         noise=0.1, speed=2.0, length_median=200.0,
         length_sigma=1.0, length_min=16, length_max=27000) -> Env:
    """Build the env. ``length_*`` set the lognormal episode lengths."""
    h, w, stack = obs_shape
    if num_actions != len(_MOVES):
        raise ValueError(f"{num_actions} actions; the ALE set has 18")
    moves = jnp.asarray(_MOVES) * speed
    lo = float(sprite_half + 1)
    hi = jnp.asarray([w - sprite_half - 2, h - sprite_half - 2], jnp.float32)
    yy = jnp.arange(h, dtype=jnp.float32)[:, None]
    xx = jnp.arange(w, dtype=jnp.float32)[None, :]
    shades = jnp.linspace(1.0, 0.5, sprites)

    def render(pos, key):
        frame = noise * jax.random.uniform(key, (h, w))
        for k in range(sprites):
            box = ((jnp.abs(xx - pos[k, 0]) <= sprite_half)
                   & (jnp.abs(yy - pos[k, 1]) <= sprite_half))
            frame = jnp.where(box, shades[k], frame)
        return jnp.floor(frame * 255.0).astype(jnp.uint8)

    def observe(frames):
        return frames.astype(jnp.float32) / 255.0

    def reset(key):
        k_pos, k_vel, k_len, k_frame = jax.random.split(key, 4)
        pos = jax.random.uniform(k_pos, (sprites, 2), minval=lo, maxval=hi)
        vel = jax.random.uniform(k_vel, (sprites, 2), minval=-speed,
                                 maxval=speed)
        z = jax.random.normal(k_len)
        length = jnp.clip(jnp.ceil(length_median * jnp.exp(length_sigma * z)),
                          length_min, length_max).astype(jnp.int32)
        frame = render(pos, k_frame)
        frames = jnp.repeat(frame[..., None], stack, axis=-1)
        return SynthState(frames, pos, vel, jnp.zeros((), jnp.int32),
                          length), observe(frames)

    def step(state, action, key):
        k_frame, k_target = jax.random.split(key)
        player = jnp.clip(state.pos[0] + moves[action], lo, hi)
        others = state.pos[1:] + state.vel[1:]
        bounce = (others < lo) | (others > hi)
        vel = state.vel.at[1:].set(jnp.where(bounce, -state.vel[1:],
                                             state.vel[1:]))
        others = jnp.clip(others, lo, hi)
        hit = jnp.all(jnp.abs(player - others[0]) <= 2 * sprite_half)
        respawn = jax.random.uniform(k_target, (2,), minval=lo, maxval=hi)
        others = others.at[0].set(jnp.where(hit, respawn, others[0]))
        pos = jnp.concatenate([player[None], others], axis=0)
        frame = render(pos, k_frame)
        frames = jnp.concatenate([state.frames[..., 1:], frame[..., None]],
                                 axis=-1)
        t = state.t + 1
        done = t >= state.length
        reward = hit.astype(jnp.float32)
        return SynthState(frames, pos, vel, t, state.length), \
            observe(frames), reward, done

    return Env(reset=reset, step=auto_reset(reset, step),
               num_actions=num_actions, obs_shape=tuple(obs_shape))
