"""Pallas TPU V-trace kernel — the paper's core algorithmic compute.

The V-trace backward recursion  acc_t = delta_t + (gamma_t c_t) acc_{t+1}
is a first-order linear recurrence over time. TPU adaptation: block the
batch dimension into 128-wide lanes (grid) and run the time recursion as an
on-chip fori_loop over sublane rows held entirely in VMEM — the whole
(T, 128) tile is resident, so the sequential dependency costs no HBM
traffic (memory-bound op: one read of deltas/dcs, one write of acc).

Inputs are precomputed by the ops.py wrapper from (log_rhos, discounts,
rewards, values, bootstrap): deltas (T, B) and dcs = discounts * cs (T, B).
Output: acc (T, B) with vs = values + acc.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(deltas_ref, dcs_ref, acc_ref, *, t_len):
    def body(i, carry):
        t = t_len - 1 - i
        acc = deltas_ref[t, :] + dcs_ref[t, :] * carry
        acc_ref[t, :] = acc
        return acc

    zero = jnp.zeros_like(deltas_ref[0, :])
    jax.lax.fori_loop(0, t_len, body, zero)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def vtrace_scan(deltas, dcs, *, block_b=128, interpret=False):
    """deltas, dcs: (T, B) float32 -> acc (T, B) float32."""
    t, b = deltas.shape
    bb = min(block_b, b)
    assert b % bb == 0, (b, bb)

    kernel = functools.partial(_kernel, t_len=t)
    return pl.pallas_call(
        kernel,
        grid=(b // bb,),
        in_specs=[
            pl.BlockSpec((t, bb), lambda bi: (0, bi)),
            pl.BlockSpec((t, bb), lambda bi: (0, bi)),
        ],
        out_specs=pl.BlockSpec((t, bb), lambda bi: (0, bi)),
        out_shape=jax.ShapeDtypeStruct((t, b), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="vtrace",
        interpret=interpret,
    )(deltas.astype(jnp.float32), dcs.astype(jnp.float32))
