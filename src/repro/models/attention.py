"""Attention mixers: GQA self-attention (full / sliding-window / local),
cross-attention (VLM), with full-sequence, chunked (memory-bounded
online-softmax) and single-token decode paths.

Shape conventions:
  x          (B, S, d)
  q          (B, S, H, hd)      flat head axis (sharding-friendly; see
                                _project_qkv note)
  k, v       (B, S, K, hd)      GQA kv heads; expanded to H for the einsums
  cache k/v  (B, Scap, K, hd)   Scap = seq capacity or sliding window
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels.ops import NEG_INF  # shared fp32 mask constant
from repro.models.common import apply_rope, param, softcap


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def attn_init(key, cfg, kind):
    """Params for one attention layer. kind: attn|local_attn|swa_attn|xattn."""
    d, h, k_, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": param(ks[0], (d, h, hd), ("embed", "heads", "head_dim")),
        "wk": param(ks[1], (d, k_, hd), ("embed", "kv_heads", "head_dim")),
        "wv": param(ks[2], (d, k_, hd), ("embed", "kv_heads", "head_dim")),
        "wo": param(ks[3], (h, hd, d), ("heads", "head_dim", "embed"),
                    scale=float(1.0 / np.sqrt(h * hd))),
    }
    if cfg.use_qk_norm:
        p["q_norm"] = param(None, (hd,), ("head_dim",), init="zeros")
        p["k_norm"] = param(None, (hd,), ("head_dim",), init="zeros")
    return p


def _qk_norm(x, scale, eps):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return y.astype(dt)


def _project_qkv(params, cfg, x, kv_src):
    """Returns q (B,S,H,hd), k, v (B,Skv,K,hd).

    NOTE: q keeps the flat H head axis. A (K, G) reshape would make the
    16-way model-axis head sharding inexpressible whenever K < mesh model
    size (GSPMD maps one mesh axis to one tensor dim), silently replicating
    every attention intermediate. Full-sequence attention instead expands
    KV to H heads right before the einsum (_expand_kv) — a few hundred MB
    of transient bf16, fully sharded.
    """
    q = jnp.einsum("bsd,dhe->bshe", x, params["wq"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    k = jnp.einsum("bsd,dke->bske", kv_src, params["wk"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    v = jnp.einsum("bsd,dke->bske", kv_src, params["wv"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    if cfg.use_qk_norm:
        q = _qk_norm(q, params["q_norm"], cfg.norm_eps)
        k = _qk_norm(k, params["k_norm"], cfg.norm_eps)
    return q, k, v


def _expand_kv(k, group):
    """(B,S,K,hd) -> (B,S,K*group,hd); q head h reads kv head h // group."""
    if group == 1:
        return k
    return jnp.repeat(k, group, axis=2)


def _scale(cfg):
    return cfg.attn_scale if cfg.attn_scale is not None else cfg.resolved_head_dim ** -0.5


def _out_proj(params, cfg, o):
    """o: (B,S,H,hd) -> (B,S,d)."""
    return jnp.einsum("bshe,hed->bsd", o, params["wo"],
                      preferred_element_type=jnp.float32).astype(o.dtype)


def _window(cfg, kind):
    if kind in ("local_attn", "swa_attn"):
        return cfg.sliding_window
    return 0  # 0 = unbounded (full causal)


# ---------------------------------------------------------------------------
# full-sequence attention (train / prefill)
# ---------------------------------------------------------------------------

def _attend_dense(q, k, v, q_pos, k_pos, scale, window, cap, causal):
    """Plain (quadratic-memory) attention. q/k/v: (B,S,H,hd) (kv expanded)."""
    s = jnp.einsum("bqhe,bthe->bhqt", q, k,
                   preferred_element_type=jnp.float32) * scale
    if cap:
        s = softcap(s, cap)
    mask = jnp.ones(s.shape[-2:], dtype=bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    o = jnp.einsum("bhqt,bthe->bqhe", p.astype(q.dtype), v,
                   preferred_element_type=jnp.float32).astype(q.dtype)
    return o


def _attend_chunked(q, k, v, q_pos, k_pos, scale, window, cap, causal,
                    chunk, skip):
    """Memory-bounded online-softmax attention.

    Outer ``lax.scan`` over query chunks; inner loop over KV chunks. With
    ``skip=True`` the inner loop is a ``fori_loop`` with data-dependent
    bounds that *skips* fully-masked KV chunks (causal upper triangle /
    outside sliding window) — the beyond-paper compute optimization. With
    ``skip=False`` all KV chunks are visited and masked (fixed trip count:
    FLOPs fully visible to cost_analysis — the accounting baseline).
    """
    b, sq, heads, hd = q.shape
    skv = k.shape[1]
    cq = min(chunk, sq)
    ckv = min(chunk, skv)
    assert sq % cq == 0 and skv % ckv == 0, (sq, skv, chunk)
    nq, nkv = sq // cq, skv // ckv

    from repro.distributed.sharding import constrain_attention
    qc = q.reshape(b, nq, cq, heads, hd).transpose(1, 0, 2, 3, 4)
    # chunk-level constraint: heads->model when divisible, else the
    # WITHIN-chunk query dim (cq) — the nq scan dim must stay unsharded
    qc = constrain_attention(qc, seq_dim=2, head_dim=3, batch_dim=1)
    qpc = q_pos.reshape(nq, cq)
    kc = constrain_attention(k.reshape(b, nkv, ckv, heads, hd),
                             seq_dim=-1, head_dim=3)
    vc = constrain_attention(v.reshape(b, nkv, ckv, heads, hd),
                             seq_dim=-1, head_dim=3)
    kpc = k_pos.reshape(nkv, ckv)

    def kv_step(carry, j, q_i, qp_i):
        m, l, acc = carry
        kj = jax.lax.dynamic_index_in_dim(kc, j, axis=1, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(vc, j, axis=1, keepdims=False)
        kp = jax.lax.dynamic_index_in_dim(kpc, j, axis=0, keepdims=False)
        s = jnp.einsum("bqhe,bthe->bhqt", q_i, kj,
                       preferred_element_type=jnp.float32) * scale
        if cap:
            s = softcap(s, cap)
        mask = jnp.ones((cq, ckv), dtype=bool)
        if causal:
            mask &= kp[None, :] <= qp_i[:, None]
        if window:
            mask &= qp_i[:, None] - kp[None, :] < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqt,bthe->bhqe", p.astype(q.dtype), vj,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc)

    @jax.checkpoint
    def q_step(_, xs):
        # checkpointed: backward re-runs the inner online-softmax loop
        # instead of storing its per-iteration residuals (flash-style).
        i, q_i, qp_i = xs
        m0 = jnp.full((b, heads, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, heads, cq), jnp.float32)
        a0 = jnp.zeros((b, heads, cq, hd), jnp.float32)
        if skip and causal:
            # last kv chunk overlapping this q chunk (inclusive)
            hi = jnp.minimum((((i + 1) * cq - 1) // ckv) + 1, nkv)
            lo = jnp.maximum((i * cq - (window - 1)) // ckv, 0) if window else 0
            m, l, acc = jax.lax.fori_loop(
                lo, hi, lambda j, c: kv_step(c, j, q_i, qp_i), (m0, l0, a0))
        else:
            (m, l, acc), _ = jax.lax.scan(
                lambda c, j: (kv_step(c, j, q_i, qp_i), None),
                (m0, l0, a0), jnp.arange(nkv))
        l = jnp.maximum(l, 1e-30)
        o = (acc / l[..., None]).astype(q.dtype)  # (b,h,cq,hd)
        return None, o.transpose(0, 2, 1, 3)      # (b,cq,h,hd)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qc, qpc))
    # outs: (nq, b, cq, h, hd) -> (b, sq, h, hd)
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, heads, hd)


def _divisor_block(s, want, align):
    """Block size for a kernel axis of length ``s``: the largest divisor of
    ``s`` that is <= ``want`` and a multiple of ``align``, else ``s``
    itself. The grids need the axis to tile exactly, and Mosaic needs a
    block dim to be a multiple of its tile (8 sublanes, 128 lanes) or the
    whole axis; CI shapes are not always multiples of 128."""
    if s <= want:
        return s
    for b in range(want - want % align, 0, -align):
        if s % b == 0:
            return b
    return s


def _attend_flash_kernel(q, k, v, q_pos, k_pos, *, scale, window, cap,
                         chunk, group):
    """Causal attention on the Pallas flash kernel.

    Forward: kernels/flash_attention.py — GQA via the kernel's index maps
    (the unexpanded (B,S,K,hd) k/v go straight in), sliding window and
    softcap inside the kernel. Backward: VJP of the chunked
    online-softmax reference (``_attend_chunked``, skip=True) — Pallas
    TPU kernels are not reverse-mode differentiable, so the backward
    rematerialises flash-style from the saved inputs; the GQA expansion
    happens inside the differentiated reference so dk/dv sum back to K
    heads. Positions are integer primals and get float0 cotangents.
    """
    from repro.kernels import ops as kops
    bq = _divisor_block(q.shape[1], min(chunk, 128), 8)
    bk = _divisor_block(k.shape[1], min(chunk, 128), 8)

    @jax.custom_vjp
    def attend(q, k, v, q_pos, k_pos):
        o = kops.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), scale=scale, causal=True,
            window=window, softcap=cap or 0.0, block_q=bq, block_k=bk)
        return o.transpose(0, 2, 1, 3)

    def fwd(q, k, v, q_pos, k_pos):
        return attend(q, k, v, q_pos, k_pos), (q, k, v, q_pos, k_pos)

    def bwd(res, g):
        q, k, v, q_pos, k_pos = res

        def reference(q, k, v):
            # skip=False: the skip variant's data-dependent fori_loop is
            # not reverse-mode differentiable; the fixed-trip-count scan is.
            ke, ve = _expand_kv(k, group), _expand_kv(v, group)
            return _attend_chunked(q, ke, ve, q_pos, k_pos, scale, window,
                                   cap, True, chunk, skip=False)

        dq, dk, dv = jax.vjp(reference, q, k, v)[1](g)
        zero = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa: E731
        return dq, dk, dv, zero(q_pos), zero(k_pos)

    attend.defvjp(fwd, bwd)
    return attend(q, k, v, q_pos, k_pos)


def attn_apply(params, x, *, cfg, kind, positions, kv_src=None,
               impl=None):
    """Full-sequence attention (training / prefill).

    positions: (S,) int32 token positions. kv_src: (B,Sv,d) for xattn.
    Returns (out (B,S,d), kv) — kv returned so prefill can seed caches.
    """
    causal = kind != "xattn"
    src = x if kv_src is None else kv_src
    q, k, v = _project_qkv(params, cfg, x, src)
    if cfg.pos_emb == "rope" and kind != "xattn":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    window = _window(cfg, kind)
    kv_pos = positions if causal else jnp.arange(src.shape[1])
    impl = impl or cfg.attn_impl
    if impl == "auto":
        impl = "xla" if x.shape[1] <= 2048 else "xla_chunked_skip"
    group = cfg.num_heads // cfg.num_kv_heads
    ke, ve = _expand_kv(k, group), _expand_kv(v, group)
    from repro.distributed.sharding import constrain_attention
    q = constrain_attention(q)
    ke = constrain_attention(ke)
    ve = constrain_attention(ve)
    if impl == "xla":
        o = _attend_dense(q, ke, ve, positions, kv_pos, _scale(cfg), window,
                          cfg.attn_logit_softcap, causal)
    elif impl in ("kernel", "pallas") and causal:
        # the TPU flash-attention kernel (kernels/flash_attention.py) with
        # a reference-VJP backward; interpret-mode on CPU ("pallas" is the
        # legacy spelling of "kernel").
        o = _attend_flash_kernel(q, k, v, positions, kv_pos,
                                 scale=_scale(cfg), window=window,
                                 cap=cfg.attn_logit_softcap, group=group,
                                 chunk=cfg.attn_chunk)
    elif impl in ("xla_chunked", "xla_chunked_skip", "kernel", "pallas"):
        # non-causal kernel impl (xattn) falls back to the chunked path
        o = _attend_chunked(q, ke, ve, positions, kv_pos, _scale(cfg), window,
                            cfg.attn_logit_softcap, causal, cfg.attn_chunk,
                            skip=impl == "xla_chunked_skip")
    else:
        raise ValueError(f"unknown attn impl {impl}")
    return _out_proj(params, cfg, o), (k, v)


# ---------------------------------------------------------------------------
# decode (single token, KV cache)
# ---------------------------------------------------------------------------

def attn_cache_init(cfg, kind, batch, seq_len, dtype):
    """Cache arrays for one attention layer.

    Full attention: capacity = seq_len. Windowed: ring buffer of size window.
    xattn: static vision KV of length cfg.vision_seq.
    """
    k_, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    if kind == "xattn":
        cap = cfg.vision_seq
    else:
        window = _window(cfg, kind)
        cap = min(seq_len, window) if window else seq_len
    return {
        "k": jnp.zeros((batch, cap, k_, hd), dtype),
        "v": jnp.zeros((batch, cap, k_, hd), dtype),
    }


def attn_decode(params, x, cache, *, cfg, kind, pos, impl=None):
    """One-token decode. x: (B,1,d); pos: scalar int32 (lockstep decode,
    every row at the same position) or (B,) int32 (continuous batching,
    each slot at its own position — rope, cache writes and validity masks
    all become per-row).

    ``impl`` in ("kernel", "pallas") routes the score/softmax/value math
    to kernels/decode_attention.py (xattn keeps the dense path — static
    non-causal vision KV); anything else uses the grouped XLA einsum.
    Returns (out (B,1,d), new_cache).
    """
    group = cfg.num_heads // cfg.num_kv_heads
    if kind == "xattn":
        # static cross-attention against precomputed vision KV
        q, _, _ = _project_qkv(params, cfg, x, x)
        k = _expand_kv(cache["k"], group)
        v = _expand_kv(cache["v"], group)
        kv_pos = jnp.arange(k.shape[1])
        pos_arr = jnp.asarray(pos)
        o = _attend_dense(q, k, v, pos_arr[None], kv_pos, _scale(cfg), 0,
                          cfg.attn_logit_softcap, causal=False)
        return _out_proj(params, cfg, o), cache

    q, k_new, v_new = _project_qkv(params, cfg, x, x)
    pos = jnp.asarray(pos)
    vec = pos.ndim == 1                     # per-row positions
    if cfg.pos_emb == "rope":
        pos_arr = pos[:, None] if vec else pos[None]
        q = apply_rope(q, pos_arr, cfg.rope_theta)
        k_new = apply_rope(k_new, pos_arr, cfg.rope_theta)

    cap = cache["k"].shape[1]
    window = _window(cfg, kind)
    slot = jnp.mod(pos, cap) if window else pos
    if vec:
        upd = lambda c, n, s: jax.lax.dynamic_update_slice_in_dim(  # noqa: E731
            c, n, s, axis=0)
        k = jax.vmap(upd)(cache["k"], k_new, slot)
        v = jax.vmap(upd)(cache["v"], v_new, slot)
    else:
        k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot,
                                                axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot,
                                                axis=1)

    # position held by each cache slot (ring-buffer aware); with per-row
    # pos every quantity gains a leading batch axis
    idx = jnp.arange(cap)
    rpos = pos[:, None] if vec else pos
    if window:
        slot_pos = rpos - jnp.mod(rpos - idx, cap)
    else:
        slot_pos = jnp.broadcast_to(idx, (x.shape[0], cap)) if vec else idx
    valid = (slot_pos >= 0) & (slot_pos <= rpos)
    if window:
        valid &= rpos - slot_pos < window

    impl = impl or cfg.attn_impl
    if impl in ("kernel", "pallas"):
        # the TPU decode-attention kernel: one (B,H,hd) query against the
        # compact (B,K,cap,hd) cache, ring-buffer validity from slot_pos
        # inside the kernel (same semantics as `valid` above).
        from repro.kernels import ops as kops
        o = kops.decode_attention(
            q[:, 0], k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            slot_pos.astype(jnp.int32), pos.astype(jnp.int32),
            scale=_scale(cfg), softcap=cfg.attn_logit_softcap or 0.0,
            window=window, block_k=_divisor_block(cap, 128, 128))
        return _out_proj(params, cfg, o[:, None]), {"k": k, "v": v}

    # grouped GQA einsum directly against the compact (B,S,K,hd) cache:
    # expanding KV to H heads here would read+write `group`x the cache
    # bytes per token — decode is memory-bound, so that multiplies the
    # dominant roofline term (EXPERIMENTS.md §Perf H3). The tiny q is
    # reshaped to (K, G) instead; all big tensors keep the K axis.
    b = q.shape[0]
    hd = q.shape[-1]
    qg = q.reshape(b, 1, cfg.num_kv_heads, group, hd)
    s = jnp.einsum("bqkgh,btkh->bkgqt", qg, k,
                   preferred_element_type=jnp.float32) * _scale(cfg)
    if cfg.attn_logit_softcap:
        s = softcap(s, cfg.attn_logit_softcap)
    vmask = (valid[:, None, None, None, :] if vec
             else valid[None, None, None, None, :])
    s = jnp.where(vmask, s, NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    o = jnp.einsum("bkgqt,btkh->bqkgh", p.astype(q.dtype), v,
                   preferred_element_type=jnp.float32).astype(q.dtype)
    o = o.reshape(b, 1, cfg.num_heads, hd)
    return _out_proj(params, cfg, o), {"k": k, "v": v}


def attn_prefill_cache(cfg, kind, kv, seq_len, dtype):
    """Build a decode cache from prefill KV (k, v each (B,S,K,hd))."""
    k, v = kv
    b = k.shape[0]
    cache = attn_cache_init(cfg, kind, b, seq_len, dtype)
    window = _window(cfg, kind)
    cap = cache["k"].shape[1]
    s = k.shape[1]
    if window and s > cap:
        # keep the last `cap` positions, ring-aligned: slot = pos % cap
        keep_k, keep_v = k[:, s - cap:], v[:, s - cap:]
        pos0 = s - cap
        roll = jnp.mod(pos0, cap)
        keep_k = jnp.roll(keep_k, roll, axis=1)
        keep_v = jnp.roll(keep_v, roll, axis=1)
        return {"k": keep_k.astype(dtype), "v": keep_v.astype(dtype)}
    cache["k"] = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(dtype), 0, axis=1)
    cache["v"] = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(dtype), 0, axis=1)
    return cache
