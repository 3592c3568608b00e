"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ops import NEG_INF


def ref_flash_attention(q, k, v, *, scale=None, causal=True, window=0,
                        softcap=0.0):
    """q: (B,H,S,hd); k,v: (B,K,S,hd). Dense-softmax reference."""
    b, h, s, hd = q.shape
    kheads = k.shape[1]
    group = h // kheads
    if scale is None:
        scale = hd ** -0.5
    qg = q.reshape(b, kheads, group, s, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bkgqh,bkth->bkgqt", qg, kf) * scale
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= qpos - kpos < window
    logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgqt,bkth->bkgqh", p, vf)
    return o.reshape(b, h, s, hd).astype(q.dtype)


def ref_decode_attention(q, k, v, slot_pos, pos, *, scale=None, softcap=0.0,
                         window=0):
    """q: (B,H,hd); k,v: (B,K,S,hd); slot_pos (S,) or (B,S); pos scalar
    or (B,) — per-row positions for the continuous-batching decode path."""
    b, h, hd = q.shape
    kheads, s = k.shape[1], k.shape[2]
    group = h // kheads
    if scale is None:
        scale = hd ** -0.5
    qg = q.reshape(b, kheads, group, hd).astype(jnp.float32)
    logits = jnp.einsum("bkgh,bkth->bkgt", qg, k.astype(jnp.float32)) * scale
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    slot_pos = jnp.broadcast_to(jnp.asarray(slot_pos).reshape(-1, s), (b, s))
    pos = jnp.broadcast_to(jnp.asarray(pos).reshape(-1), (b,))
    valid = (slot_pos >= 0) & (slot_pos <= pos[:, None])
    if window:
        valid &= pos[:, None] - slot_pos < window
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgt,bkth->bkgh", p, v.astype(jnp.float32))
    return o.reshape(b, h, hd).astype(q.dtype)


def ref_vtrace_scan(deltas, dcs):
    """Reverse first-order recurrence via lax.scan (matches core.vtrace)."""
    def body(acc, xs):
        d, dc = xs
        acc = d + dc * acc
        return acc, acc

    _, acc = jax.lax.scan(body, jnp.zeros_like(deltas[0]),
                          (deltas.astype(jnp.float32),
                           dcs.astype(jnp.float32)), reverse=True)
    return acc


def ref_ssd_chunk(c, b, xdt, da, h_prev):
    """Oracle for kernels/ssd_chunk.py — mirrors models/mamba.py chunk_step
    for a single (batch*head) slice set. Shapes as in ssd_chunk."""
    c = c.astype(jnp.float32)
    b = b.astype(jnp.float32)
    x = xdt.astype(jnp.float32)
    da = da.astype(jnp.float32)[..., 0]          # (BH, L)
    h = h_prev.astype(jnp.float32)
    acs = jnp.cumsum(da, axis=-1)                # (BH, L)
    seg = acs[:, :, None] - acs[:, None, :]
    l = c.shape[1]
    mask = jnp.tril(jnp.ones((l, l), bool))
    lmat = jnp.where(mask, jnp.exp(seg), 0.0)
    scores = jnp.einsum("gln,gsn->gls", c, b) * lmat
    y = jnp.einsum("gls,gsp->glp", scores, x)
    y = y + jnp.einsum("gln,gpn->glp", c, h) * jnp.exp(acs)[..., None]
    w = jnp.exp(acs[:, -1:] - acs)               # (BH, L)
    h_new = h * jnp.exp(acs[:, -1])[:, None, None] + \
        jnp.einsum("glp,gln,gl->gpn", x, b, w)
    return y.astype(xdt.dtype), h_new


def _pool_taps(h, w):
    """(window position, input row slice, input column slice) of the 3x3,
    stride-2 pool, on an input padded by one on each side."""
    ho, wo = (h + 1) // 2, (w + 1) // 2
    return [(3 * dr + dc, slice(dr, dr + 2 * ho - 1, 2),
             slice(dc, dc + 2 * wo - 1, 2))
            for dr in range(3) for dc in range(3)]


def ref_maxpool_fwd(x):
    """Oracle for kernels/maxpool.py on its (H, W, C, N) view: the window's
    max and the position (row-major, 0..8) of its first maximum."""
    h, w = x.shape[:2]
    xp = jnp.pad(x, ((1, 1), (1, 1), (0, 0), (0, 0)),
                 constant_values=-jnp.inf)
    taps = jnp.stack([xp[rows, cols] for _, rows, cols in _pool_taps(h, w)])
    return taps.max(0), jnp.argmax(taps, 0).astype(jnp.int8)


def ref_maxpool_bwd(idx, dy, hw):
    """dx (H, W, C, N): each output's dy added at its window's winner."""
    h, w = hw
    dxp = jnp.zeros((h + 2, w + 2) + dy.shape[2:], dy.dtype)
    for k, rows, cols in _pool_taps(h, w):
        dxp = dxp.at[rows, cols].add(jnp.where(idx == k, dy, 0))
    return dxp[1:h + 1, 1:w + 1]
