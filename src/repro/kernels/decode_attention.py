"""Pallas TPU decode attention (flash-decode style).

One new query token per sequence attends to a KV cache of length S_cache.
Grid: (batch * kv_heads, num_kv_blocks); each instance processes all
``group`` = H/K query heads that share one kv head, so the q tile is
(group, hd) — MXU-friendly for GQA (group x bk matmuls) — and the KV cache
is read exactly once.

Supports position-validity masking (ring-buffer sliding-window caches pass
per-slot positions computed by the wrapper) and logit softcap.

Layout: q (B, H, hd); k, v (B, K, S, hd); slot_pos (S,) or (B, S) int32;
pos scalar or (B,). Per-row positions serve the continuous-batching
decode path, where every batch slot sits at its own sequence position;
scalar inputs are broadcast (the lockstep `generate` fast path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import NEG_INF


def _kernel(pos_ref, q_ref, k_ref, v_ref, slot_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale, softcap, window, bk,
            num_kv_blocks, kheads):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                   # (g, hd)
    k = k_ref[0].astype(jnp.float32)                   # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    pos = pos_ref[pl.program_id(0) // kheads]          # this row's position
    slot_pos = slot_ref[0]                             # (1, bk) int32

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    valid = jnp.logical_and(slot_pos >= 0, slot_pos <= pos)
    if window:
        valid = jnp.logical_and(valid, pos - slot_pos < window)
    s = jnp.where(valid, s, NEG_INF)                   # (g, bk) via broadcast

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "softcap", "window", "block_k", "interpret"))
def decode_attention(q, k, v, slot_pos, pos, *, scale=None, softcap=0.0,
                     window=0, block_k=128, interpret=False):
    """q: (B,H,hd); k,v: (B,K,S,hd); slot_pos: (S,) or (B,S) int32 position
    held by each cache slot (-1 = empty); pos: scalar or (B,) int32 current
    position per sequence. Returns (B,H,hd)."""
    b, h, hd = q.shape
    _, kheads, s, _ = k.shape
    assert h % kheads == 0
    group = h // kheads
    bk = min(block_k, s)
    assert s % bk == 0
    nk = s // bk
    if scale is None:
        scale = hd ** -0.5

    qf = q.reshape(b * kheads, group, hd)
    kf = k.reshape(b * kheads, s, hd)
    vf = v.reshape(b * kheads, s, hd)
    # (B, 1, S): a (1, 1, bk) block keeps bk on the lane axis and the
    # sublane dim equal to the array's, as the Mosaic tiling rule requires.
    slot3d = jnp.broadcast_to(
        jnp.asarray(slot_pos, jnp.int32).reshape(-1, 1, s), (b, 1, s))
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))

    kernel = functools.partial(_kernel, scale=scale, softcap=softcap,
                               window=window, bk=bk, num_kv_blocks=nk,
                               kheads=kheads)

    out = pl.pallas_call(
        kernel,
        grid=(b * kheads, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # pos
            pl.BlockSpec((1, group, hd), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, 1, bk), lambda bh, ki: (bh // kheads, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, group, hd), lambda bh, ki: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kheads, group, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="decode_attention",
        interpret=interpret,
    )(pos_arr, qf, kf, vf, slot3d)
    return out.reshape(b, h, hd)
