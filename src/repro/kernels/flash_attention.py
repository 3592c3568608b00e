"""Pallas TPU flash attention (prefill/train path).

Online-softmax attention with causal masking, optional sliding window,
optional logit softcap, and GQA (q heads grouped onto kv heads via the
BlockSpec index maps — no KV replication in HBM).

Grid: (batch * q_heads, num_q_blocks, num_kv_blocks), kv innermost so the
(m, l, acc) running state lives in VMEM scratch across kv iterations.
Fully-masked kv blocks (above the causal diagonal / outside the window) are
skipped with pl.when — the TPU-native equivalent of the CUDA early-exit.

Block sizes default to (128, 128): MXU-aligned (128x128 systolic array),
and the working set  bq*hd + 2*bk*hd + bq*bk  floats stays well under the
~16 MB v5e VMEM budget for hd <= 256.

Layout: q (B, H, S, hd); k, v (B, K, S, hd); out (B, H, S, hd).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import NEG_INF


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, causal, window, softcap, bq, bk, num_kv_blocks):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk

    # visit only blocks that can contain unmasked entries
    live = True
    if causal:
        live = k_start <= q_start + bq - 1
    if window:
        live = jnp.logical_and(live, q_start - (k_start + bk - 1) < window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                     # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                     # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "block_q",
                     "block_k", "interpret"))
def flash_attention(q, k, v, *, scale=None, causal=True, window=0,
                    softcap=0.0, block_q=128, block_k=128, interpret=False):
    """q: (B,H,S,hd); k,v: (B,K,S,hd) with H % K == 0. Returns (B,H,S,hd)."""
    b, h, s, hd = q.shape
    kheads = k.shape[1]
    assert h % kheads == 0, (h, kheads)
    group = h // kheads
    bq = min(block_q, s)
    bk = min(block_k, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    nq, nk = s // bq, s // bk
    if scale is None:
        scale = hd ** -0.5

    qf = q.reshape(b * h, s, hd)
    kf = k.reshape(b * kheads, s, hd)
    vf = v.reshape(b * kheads, s, hd)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        # bh indexes (b, h); the kv row is (b, h // group)
        return ((bh // h) * kheads + (bh % h) // group, ki, 0)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, num_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), q_map),
            pl.BlockSpec((1, bk, hd), kv_map),
            pl.BlockSpec((1, bk, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((b * h, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # m
            pltpu.VMEM((bq,), jnp.float32),       # l
            pltpu.VMEM((bq, hd), jnp.float32),    # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention",
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, s, hd)
