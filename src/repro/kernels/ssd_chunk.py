"""Pallas TPU kernel for one Mamba2 SSD chunk (the SSM hot loop).

Computes, for a single (batch, head) program instance over one chunk of
length L with state size N and head dim P:

  acs   = cumsum(da)                              (L,)
  Lmat  = exp(segsum(da))  (lower-tri)            (L, L)
  y     = ((C B^T) ∘ Lmat) X  +  (C h_prev) ∘ exp(acs)    (L, P)
  h_new = h_prev * exp(acs[-1]) + (B * exp(acs[-1]-acs))^T X   (P-major)

All three contractions are (L,N)x(N,L), (L,L)x(L,P), (L,N)x(N,P) matmuls —
MXU shaped for L in {128, 256}, N = P = 64/128. The inter-chunk recurrence
(h carry) stays outside (lax.scan in models/mamba.py); this kernel is the
body that dominates FLOPs. TPU adaptation of the Mamba2 CUDA kernel per
DESIGN.md §8 — matmul form, not a sequential scan.

Layouts: c, b (BH, L, N); xdt (BH, L, P); da (BH, L, 1); h_prev (BH, P, N).
Returns (y (BH, L, P), h_new (BH, P, N)).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(c_ref, b_ref, x_ref, da_ref, h_ref, y_ref, hnew_ref, *, l, n, p):
    c = c_ref[0].astype(jnp.float32)          # (L, N)
    b = b_ref[0].astype(jnp.float32)          # (L, N)
    x = x_ref[0].astype(jnp.float32)          # (L, P)
    da = da_ref[0].astype(jnp.float32)        # (L, 1)
    h_prev = h_ref[0].astype(jnp.float32)     # (P, N)

    ii = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    causal = jj <= ii
    # cumsum as a lower-triangular matmul (Mosaic has no cumsum); HIGHEST
    # keeps the decay exponents at full fp32 on the MXU.
    acs = jax.lax.dot_general(
        causal.astype(jnp.float32), da, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)[:, 0]   # (L,)
    # segsum: seg[i, j] = acs[i] - acs[j], masked lower-tri (incl diag)
    seg = acs[:, None] - acs[None, :]
    lmat = jnp.where(causal, jnp.exp(seg), 0.0)     # (L, L)

    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    scores = scores * lmat                           # (L, L)
    y_diag = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    # off-diagonal: contribution of the incoming state
    ch = jax.lax.dot_general(c, h_prev, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, P)
    y = y_diag + ch * jnp.exp(acs)[:, None]
    y_ref[0] = y.astype(y_ref.dtype)

    # state update: h_new = h_prev * exp(acs[-1]) + X^T (B * w),  w_l =
    # exp(acs[-1] - acs_l)
    w = jnp.exp(acs[l - 1] - acs)[:, None]           # (L, 1)
    bw = b * w                                        # (L, N)
    xtb = jax.lax.dot_general(x, bw, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, N)
    hnew_ref[0] = (h_prev * jnp.exp(acs[l - 1]) + xtb).astype(hnew_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk(c, b, xdt, da, h_prev, *, interpret=False):
    """c, b: (BH, L, N); xdt: (BH, L, P); da: (BH, L, 1) (<= 0);
    h_prev: (BH, P, N). Returns (y (BH, L, P), h_new (BH, P, N))."""
    bh, l, n = c.shape
    p = xdt.shape[-1]

    kernel = functools.partial(_kernel, l=l, n=n, p=p)
    return pl.pallas_call(
        kernel,
        grid=(bh,),
        in_specs=[
            pl.BlockSpec((1, l, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, l, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, l, p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, l, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, p, n), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, l, p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, p, n), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, l, p), xdt.dtype),
            jax.ShapeDtypeStruct((bh, p, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="ssd_chunk",
        interpret=interpret,
    )(c, b, xdt, da, h_prev)
