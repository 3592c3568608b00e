"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to compiled on TPU and interpret mode elsewhere
(one process-wide warning) via :func:`repro.kernels.compat.resolve_interpret`
— the kernels are written for the TPU target; interpret mode executes the
kernel body for correctness checking in this container (DESIGN.md §8.5).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# The shared fp32 mask constant for every masked-attention path — the model
# (models/attention.py) and the flash/decode kernels must agree on it or
# XLA-vs-kernel parity drifts on fully-masked rows. It MUST be defined
# before the kernel submodule imports below: the submodules import it back
# from this (then partially-initialised) module.
NEG_INF = -2.0e38

from repro.kernels import decode_attention as _dec  # noqa: E402
from repro.kernels import flash_attention as _fa  # noqa: E402
from repro.kernels import maxpool as _mp  # noqa: E402
from repro.kernels import ref as _ref  # noqa: E402
from repro.kernels import ssd_chunk as _ssd  # noqa: E402
from repro.kernels import vtrace as _vt  # noqa: E402
from repro.kernels.compat import resolve_interpret  # noqa: E402


def flash_attention(q, k, v, *, scale=None, causal=True, window=0,
                    softcap=0.0, block_q=128, block_k=128, interpret=None):
    return _fa.flash_attention(q, k, v, scale=scale, causal=causal,
                               window=window, softcap=softcap,
                               block_q=block_q, block_k=block_k,
                               interpret=resolve_interpret(interpret))


def decode_attention(q, k, v, slot_pos, pos, *, scale=None, softcap=0.0,
                     window=0, block_k=128, interpret=None):
    return _dec.decode_attention(q, k, v, slot_pos, pos, scale=scale,
                                 softcap=softcap, window=window,
                                 block_k=block_k,
                                 interpret=resolve_interpret(interpret))


def vtrace_acc(deltas, dcs, *, block_b=128, interpret=None):
    return _vt.vtrace_scan(deltas, dcs, block_b=block_b,
                           interpret=resolve_interpret(interpret))


def maxpool_fwd(x, *, interpret=None):
    return _mp.maxpool_fwd(x, interpret=resolve_interpret(interpret))


def maxpool_bwd(idx, dy, *, hw, interpret=None):
    return _mp.maxpool_bwd(idx, dy, hw=hw,
                           interpret=resolve_interpret(interpret))


def per_device(fn, mesh, size, dim):
    """``fn``, run by each device of ``mesh`` on its own slice of
    dimension ``dim`` (of ``size``) of every argument and result: XLA
    does not partition a Mosaic kernel, so a sharded caller must
    ``shard_map`` it. Replicated when ``size`` does not divide over the
    data axes; ``fn`` itself without a mesh."""
    if mesh is None:
        return fn
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import data_axes
    axes = data_axes(mesh)
    n = math.prod(mesh.shape[a] for a in axes)
    spec = P(*[None] * dim, axes) if axes and size % n == 0 else P()
    return jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)


def vtrace_from_importance_weights_kernel(
        log_rhos, discounts, rewards, values, bootstrap_value, *,
        clip_rho_threshold=1.0, clip_c_threshold=1.0,
        clip_pg_rho_threshold=1.0, mesh=None, interpret=None):
    """Full V-trace with the recursion on the Pallas kernel (drop-in for
    core.vtrace.vtrace_from_importance_weights). ``mesh``: the caller's
    device mesh, over whose data axes the recursion is split by column."""
    from repro.core.vtrace import VTraceReturns

    log_rhos = log_rhos.astype(jnp.float32)
    discounts = discounts.astype(jnp.float32)
    rewards = rewards.astype(jnp.float32)
    values = values.astype(jnp.float32)
    bootstrap_value = bootstrap_value.astype(jnp.float32)

    rhos = jnp.exp(log_rhos)
    clipped_rhos = jnp.minimum(clip_rho_threshold, rhos)
    cs = jnp.minimum(clip_c_threshold, rhos)
    values_tp1 = jnp.concatenate([values[1:], bootstrap_value[None]], 0)
    deltas = clipped_rhos * (rewards + discounts * values_tp1 - values)

    acc_fn = per_device(
        lambda d, dc: vtrace_acc(d, dc, interpret=interpret), mesh,
        deltas.shape[1], dim=1)      # split by batch column
    acc = acc_fn(deltas, discounts * cs)
    vs = values + acc
    vs_tp1 = jnp.concatenate([vs[1:], bootstrap_value[None]], 0)
    pg_rhos = jnp.minimum(clip_pg_rho_threshold, rhos)
    pg_adv = pg_rhos * (rewards + discounts * vs_tp1 - values)
    return VTraceReturns(jax.lax.stop_gradient(vs),
                         jax.lax.stop_gradient(pg_adv))


def ssd_chunk(c, b, xdt, da, h_prev, *, interpret=None):
    return _ssd.ssd_chunk(c, b, xdt, da, h_prev,
                          interpret=resolve_interpret(interpret))


def ssd_chunk_trainable(c, b, xdt, da, h_prev, *, interpret=None):
    """``ssd_chunk`` with a custom VJP: Pallas kernel on the forward, VJP
    of the jnp reference on the backward (Pallas TPU kernels are not
    reverse-mode differentiable; the reference recomputes the chunk —
    flash-style rematerialisation)."""

    @jax.custom_vjp
    def run(c, b, xdt, da, h_prev):
        return ssd_chunk(c, b, xdt, da, h_prev, interpret=interpret)

    def fwd(c, b, xdt, da, h_prev):
        return run(c, b, xdt, da, h_prev), (c, b, xdt, da, h_prev)

    def bwd(res, g):
        return jax.vjp(_ref.ref_ssd_chunk, *res)[1](g)

    run.defvjp(fwd, bwd)
    return run(c, b, xdt, da, h_prev)
