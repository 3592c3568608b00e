"""Pallas TPU max-pool with an index-based VJP: 3x3 windows at stride 2
with a one-pixel -inf edge (the IMPALA deep ResNet's pool).

``maxpool_fwd`` reads the input once and writes the pooled max and, per
output, the int8 index of the window's winner: the first maximum in
row-major window order (0..8), which is ``select_and_scatter``'s ``ge``
rule. ``maxpool_bwd`` builds the input gradient from that index and dy
alone and writes it once, so the backward never reads the input.

Both work on the (H, W, C, N) view of an NHWC activation: the batch in the
lanes, which is how XLA lays out the learner's convolution outputs on a
TPU ({0,3,2,1}), so the transposes to and from it are bitcasts. Output row
r covers input rows 2r-1..2r+1 and output column s input columns
2s-1..2s+1. The grid walks (column block, channel block, input row), one
input row a step, the row innermost: a VMEM carry holds what the row
before left for the next, so no row is read twice. Column blocks hold an
even number of input columns (all of them when W is odd) and take the one
column beside them through a second ``BlockSpec`` on the same operand.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_TARGET = 12 * 1024 * 1024   # blocks twice (double-buffered) + scratch


def pooled_size(n: int) -> int:
    return (n + 1) // 2


def _footprint(bw, k, cb, n, itemsize):
    """VMEM bytes of the larger of the two launches, counted as the kernel
    audit counts them: blocks twice, scratch once."""
    col, x_blk, o_blk = cb * n, bw * cb * n, k * cb * n
    fwd = 2 * itemsize * (x_blk + col + o_blk) + 2 * o_blk \
        + (itemsize + 4) * o_blk
    bwd = 2 * (itemsize * (o_blk + col + x_blk) + o_blk + col) \
        + itemsize * x_blk
    return max(fwd, bwd)


def blocks(h, w, c, n, itemsize=4):
    """(input columns, output columns, channels) of one block: the most
    work a step that fits ``VMEM_TARGET``, in the widest column block
    (the fewest neighbour columns read twice). Columns are tiled only
    when W is even; channels in multiples of 8 that divide C, or all."""
    del h
    wo = pooled_size(w)
    ks = [k for k in range(wo, 0, -1) if wo % k == 0] if w % 2 == 0 \
        else [wo]
    cbs = [cb for cb in range(c, 0, -1)
           if c % cb == 0 and (cb == c or cb % 8 == 0)]
    fits = [(k * cb, k, cb) for k in ks for cb in cbs
            if _footprint(min(2 * k, w), k, cb, n, itemsize) <= VMEM_TARGET]
    _, k, cb = max(fits) if fits else (0, ks[-1], cbs[-1])
    return min(2 * k, w), k, cb


def _fwd_kernel(x_ref, halo_ref, o_ref, idx_ref, best_ref, win_ref, *,
                height, bw):
    j, i = pl.program_id(0), pl.program_id(2)
    neg = jnp.full(best_ref.shape[1:], -jnp.inf, best_ref.dtype)

    @pl.when(i == 0)
    def _():    # the edge row above the first: every candidate loses
        best_ref[...] = jnp.full(best_ref.shape, -jnp.inf, best_ref.dtype)
        win_ref[...] = jnp.zeros(win_ref.shape, jnp.int32)

    def column(s, left, has_right, odd):
        # this row's three window columns, first maximum wins
        m, d = left, jnp.zeros(left.shape, jnp.int32)
        for dc, col in ((1, 2 * s), (2, 2 * s + 1))[:2 if has_right else 1]:
            v = x_ref[0, col]
            up = v > m
            m, d = jnp.where(up, v, m), jnp.where(up, dc, d)
        # then against the rows above it in the window
        best, win = best_ref[s], win_ref[s]
        up = m > best
        best = jnp.where(up, m, best)
        win = jnp.where(up, d + (6 if odd else 3), win)
        if odd:     # the window's last row: output row i // 2 is done
            o_ref[0, s] = best
            idx_ref[0, s] = win.astype(jnp.int8)
            best, win = m, d    # and this row opens the next window
        elif height % 2:        # an odd height's last row closes its window
            @pl.when(i == height - 1)
            def _():
                o_ref[0, s] = best
                idx_ref[0, s] = win.astype(jnp.int8)
        best_ref[s], win_ref[s] = best, win

    def row(odd):
        column(0, jnp.where(j > 0, halo_ref[0, 0], neg), bw > 1, odd)

        def body(s, carry):
            column(s, x_ref[0, 2 * s - 1], True, odd)
            return carry

        jax.lax.fori_loop(1, bw // 2, body, 0)
        if bw % 2 and bw > 1:   # odd width: the last window's edge column
            column(bw // 2, x_ref[0, bw - 2], False, odd)

    pl.when(i % 2 == 0)(lambda: row(False))
    pl.when(i % 2 == 1)(lambda: row(True))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def maxpool_fwd(x, *, block=None, interpret=False):
    """x: (H, W, C, N) -> (pooled (Ho, Wo, C, N) in x's dtype, winner index
    (Ho, Wo, C, N) int8). ``block``: (input columns, output columns,
    channels) of a step, as ``blocks`` picks by default."""
    h, w, c, n = x.shape
    ho, wo = pooled_size(h), pooled_size(w)
    bw, k, cb = block or blocks(h, w, c, n, x.dtype.itemsize)
    kernel = functools.partial(_fwd_kernel, height=h, bw=bw)
    out_spec = pl.BlockSpec((1, k, cb, n), lambda j, ci, i: (i // 2, j, ci, 0))
    return pl.pallas_call(
        kernel,
        grid=(wo // k, c // cb, h),
        in_specs=[
            pl.BlockSpec((1, bw, cb, n), lambda j, ci, i: (i, j, ci, 0)),
            pl.BlockSpec((1, 1, cb, n),
                         lambda j, ci, i: (i, jnp.maximum(j * bw - 1, 0),
                                           ci, 0)),
        ],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((ho, wo, c, n), x.dtype),
                   jax.ShapeDtypeStruct((ho, wo, c, n), jnp.int8)],
        scratch_shapes=[pltpu.VMEM((k, cb, n), x.dtype),
                        pltpu.VMEM((k, cb, n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="maxpool_fwd",
        interpret=interpret,
    )(x, x)


def _bwd_kernel(dy_ref, idx_ref, dyh_ref, idxh_ref, dx_ref, carry_ref, *,
                ho, wo, k, bw):
    j, i = pl.program_id(0), pl.program_id(2)
    zero = jnp.zeros(dx_ref.shape[2:], dx_ref.dtype)

    def load(s):
        return dy_ref[0, s], idx_ref[0, s].astype(jnp.int32)

    def parts(cur, nxt, dr):
        """One output row's gradient at window row dr in input columns 2s
        (window column 1 of output s, ``cur``) and 2s+1 (column 2 of s and
        column 0 of s+1, ``nxt``; None where 2s+1 is the edge)."""
        g, win = cur
        even = jnp.where(win == 3 * dr + 1, g, zero)
        if nxt is None:
            return even, None
        g1, win1 = nxt
        return even, (jnp.where(win == 3 * dr + 2, g, zero)
                      + jnp.where(win1 == 3 * dr, g1, zero))

    def put(ref, s, even, odd, base=None):     # refs of (1, bw, cb, n)
        ref[0, 2 * s] = even if base is None else base[0, 2 * s] + even
        if odd is not None:
            ref[0, 2 * s + 1] = odd if base is None \
                else base[0, 2 * s + 1] + odd

    def column(s, nxt, odd_row):
        cur = load(s)
        if odd_row:     # input row 2r+1: window row 0 of output r+1
            put(dx_ref, s, *parts(cur, nxt, 0), base=carry_ref)
        else:           # row 2r: window row 1 of output r; then row 2r+1's
            put(dx_ref, s, *parts(cur, nxt, 1))   # share of output r
            put(carry_ref, s, *parts(cur, nxt, 2))

    def row(odd_row):
        def body(s, carry):
            column(s, load(s + 1), odd_row)
            return carry

        jax.lax.fori_loop(0, k - 1, body, 0)
        if bw % 2:      # odd width: the last column has no right neighbour
            column(k - 1, None, odd_row)
        else:           # the column right of the block, zero past the edge
            g1, win1 = dyh_ref[0, 0], idxh_ref[0, 0].astype(jnp.int32)
            g1 = jnp.where((j + 1) * k < wo, g1, zero)
            column(k - 1, (g1, win1), odd_row)

    odd, below = i % 2 == 1, (i + 1) // 2 < ho
    pl.when(~odd)(lambda: row(False))
    pl.when(odd & below)(lambda: row(True))

    @pl.when(odd & ~below)
    def _():            # the last row of an even height: nothing below it
        dx_ref[...] = carry_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("hw", "block", "interpret"))
def maxpool_bwd(idx, dy, *, hw, block=None, interpret=False):
    """idx (Ho, Wo, C, N) int8 from ``maxpool_fwd``, dy (Ho, Wo, C, N) ->
    dx (H, W, C, N) in dy's dtype, ``hw`` = (H, W)."""
    ho, wo, c, n = dy.shape
    h, w = hw
    bw, k, cb = block or blocks(h, w, c, n, dy.dtype.itemsize)
    kernel = functools.partial(_bwd_kernel, ho=ho, wo=wo, k=k, bw=bw)

    def blk(j, ci, i):  # the output row whose windows reach row i last
        return jnp.minimum((i + 1) // 2, ho - 1), j, ci, 0

    def halo(j, ci, i):
        return blk(j, ci, i)[0], jnp.minimum((j + 1) * k, wo - 1), ci, 0

    return pl.pallas_call(
        kernel,
        grid=(wo // k, c // cb, h),
        in_specs=[pl.BlockSpec((1, k, cb, n), blk),
                  pl.BlockSpec((1, k, cb, n), blk),
                  pl.BlockSpec((1, 1, cb, n), halo),
                  pl.BlockSpec((1, 1, cb, n), halo)],
        out_specs=pl.BlockSpec((1, bw, cb, n),
                               lambda j, ci, i: (i, j, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((h, w, c, n), dy.dtype),
        scratch_shapes=[pltpu.VMEM((1, bw, cb, n), dy.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="maxpool_bwd",
        interpret=interpret,
    )(dy, idx, dy, idx)
