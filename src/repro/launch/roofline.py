"""Trip-count-corrected roofline accounting.

XLA's HLO cost analysis counts while-loop bodies ONCE (verified in
EXPERIMENTS.md §Dry-run methodology), so a scanned-over-layers model reports
~1/num_groups of its true FLOPs. We correct with a two-program measurement:

  total ≈ cost(full program)            [scan bodies counted once]
        + (G-1) * cost(block program)   [one scan body, lowered standalone]
        + inner-scan corrections        [analytic, for loops *inside* a block
                                         or inside the loss: chunked
                                         attention, loss chunks, mamba/xLSTM
                                         chunk scans]

The block program is the same super-block computation (fwd for serve/prefill,
fwd+bwd-with-remat for train) lowered with the same mesh/rules, so its
collectives and bytes are measured, not modelled. The analytic corrections
use closed-form matmul FLOPs (documented per formula below) and are reported
separately so the measured/modelled split stays visible.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import INPUT_SHAPES, ModelConfig
from repro.distributed import sharding as shd
from repro.models import blocks as blocks_lib
from repro.models import model as model_lib
from repro.models.common import split_params


def _abstract_block_params(cfg, mesh, rules, pattern=None):
    box = {}

    def f():
        vals, axes = split_params(
            blocks_lib.block_init(jax.random.PRNGKey(0), cfg,
                                  pattern=pattern))
        box["axes"] = axes
        return vals

    shapes = jax.eval_shape(f)
    shardings = shd.param_shardings(box["axes"], mesh, rules, shapes)
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings)


def _act_spec(cfg, mesh, rules, b, s):
    spec = shd.spec_for(("act_batch", "act_seq", "act_embed"), mesh, rules,
                        (b, s, cfg.d_model))
    return jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.dtype(cfg.dtype),
                                sharding=NamedSharding(mesh, spec))


def build_block_program(cfg: ModelConfig, shape_name: str, mesh, rules):
    """One scan-body program matching model.forward/decode_step's body."""
    ishape = INPUT_SHAPES[shape_name]
    b, s = ishape.global_batch, ishape.seq_len
    kind = ishape.kind

    bp = _abstract_block_params(cfg, mesh, rules)
    shared = (_abstract_block_params(cfg, mesh, rules,
                                     pattern=model_lib.SHARED_PATTERN)
              if cfg.shared_attn_every else None)
    vis = None
    if cfg.vision_seq:
        from repro.launch.specs import _batch_spec, _sds
        vis = _sds((b, cfg.vision_seq, cfg.d_model), jnp.dtype(cfg.dtype),
                   mesh, P(_batch_spec(mesh, b), None, None))

    if kind == "decode":
        from jax.sharding import NamedSharding
        from repro.launch.specs import cache_specs, _sds

        cache = cache_specs(cfg, mesh, b, s)

        def strip_lead(sds):
            # keep the per-leaf sharding, minus the leading groups axis
            parts = list(sds.sharding.spec)
            parts = parts[1:] if parts else []
            return jax.ShapeDtypeStruct(
                sds.shape[1:], sds.dtype,
                sharding=NamedSharding(mesh, P(*parts)))

        cache_slice = jax.tree.map(strip_lead, cache)
        x = _act_spec(cfg, mesh, rules, b, 1)
        pos = jax.ShapeDtypeStruct((), jnp.int32)

        def fn(bp, shared, x, cache_slice, pos):
            with shd.use_rules(mesh, rules):
                x, nc = blocks_lib.block_decode(bp, x, cache_slice["block"],
                                                cfg=cfg, pos=pos)
                if shared is not None:
                    x, _ = blocks_lib.block_decode(
                        shared, x, cache_slice["shared"], cfg=cfg, pos=pos,
                        pattern=model_lib.SHARED_PATTERN)
            return x, nc

        args = (bp, shared, x, cache_slice, pos)
        return fn, args

    x = _act_spec(cfg, mesh, rules, b, s)

    if kind == "prefill":
        def fn(bp, shared, x, vis):
            positions = jnp.arange(s)
            with shd.use_rules(mesh, rules):
                y, aux, cache = blocks_lib.block_apply(
                    bp, x, cfg=cfg, positions=positions, vision=vis,
                    build_cache=True, seq_len=s, dtype=x.dtype)
                if shared is not None:
                    y, _, _ = blocks_lib.block_apply(
                        shared, y, cfg=cfg, positions=positions,
                        pattern=model_lib.SHARED_PATTERN, build_cache=True,
                        seq_len=s, dtype=x.dtype)
            return y, cache

        return fn, (bp, shared, x, vis)

    # train: fwd + remat-backward of one block (the scan body's true cost)
    # Weight grads carry the same ZeRO-2 sharding constraint as the full
    # program (specs.build_train), so the gradient reduction measures as a
    # reduce-scatter, not a full-weight all-reduce.
    def _axes_of(pattern):
        box = {}

        def f():
            vals, axes = split_params(blocks_lib.block_init(
                jax.random.PRNGKey(0), cfg, pattern=pattern))
            box["axes"] = axes
            return vals

        shapes = jax.eval_shape(f)
        return box["axes"], shapes

    bp_axes, bp_shapes = _axes_of(None)
    bp_gshard = shd.zero1_shardings(bp_axes, bp_shapes, mesh, rules)
    sh_gshard = None
    if cfg.shared_attn_every:
        sh_axes, sh_shapes = _axes_of(model_lib.SHARED_PATTERN)
        sh_gshard = shd.zero1_shardings(sh_axes, sh_shapes, mesh, rules)

    def fn(bp, shared, x, vis):
        positions = jnp.arange(s)

        @jax.checkpoint
        def apply(bp, shared, x):
            with shd.use_rules(mesh, rules):
                y, aux, _ = blocks_lib.block_apply(
                    bp, x, cfg=cfg, positions=positions, vision=vis)
                if shared is not None:
                    y, saux, _ = blocks_lib.block_apply(
                        shared, y, cfg=cfg, positions=positions,
                        pattern=model_lib.SHARED_PATTERN)
            return y

        def loss(bp_shared_x):
            bp_, shared_, x_ = bp_shared_x
            y = apply(bp_, shared_, x_)
            return jnp.sum(y.astype(jnp.float32)) * 1e-6

        gbp, gsh, gx = jax.grad(loss)((bp, shared, x))
        gbp = jax.tree.map(jax.lax.with_sharding_constraint, gbp, bp_gshard)
        if gsh is not None:
            gsh = jax.tree.map(jax.lax.with_sharding_constraint, gsh,
                               sh_gshard)
        return gbp, gsh, gx

    return fn, (bp, shared, x, vis)


# ---------------------------------------------------------------------------
# analytic inner-scan corrections (FLOPs; bytes where noted)
# ---------------------------------------------------------------------------

def inner_scan_corrections(cfg: ModelConfig, shape_name: str,
                           chips: int) -> Dict[str, float]:
    """Global FLOPs missing because loops *inside* one block / the loss are
    counted once. Returns extra FLOPs (global, all chips) per source.

    Formulas (per layer, global tokens N_tok = B*S, masked-chunk baseline):
      attn_chunked: kv_step ~ 4*B*H*cq*ckv*hd   -> x (nq*nkv - 1)
      loss_chunks:  chunk  ~ 6*B*c*d*V (fwd+recompute+bwd) -> x (nchunk-1)
      mamba_chunks: chunk  ~ B*L^2*H*(N+P) + 4*B*L*H*P*N   -> x (nc-1)
      mlstm_chunks: chunk  ~ 4*B*L^2*H*dh                  -> x (nc-1)
      slstm_steps:  step   ~ 8*B*H*dh^2                    -> x (S-1)
    """
    ishape = INPUT_SHAPES[shape_name]
    b, s = ishape.global_batch, ishape.seq_len
    kind = ishape.kind
    out = {k: 0.0 for k in ("attn_chunked", "loss_chunks", "mamba_chunks",
                            "mlstm_chunks", "slstm_steps")}
    if kind == "decode":
        return out  # no inner scans in the decode block

    hd = cfg.resolved_head_dim
    h = cfg.num_heads
    d = cfg.d_model

    n_attn = sum(1 for m, _ in cfg.block_pattern
                 if m in ("attn", "local_attn", "swa_attn")) * cfg.num_groups
    n_x = sum(1 for m, _ in cfg.block_pattern
              if m == "xattn") * cfg.num_groups
    if cfg.shared_attn_every:
        n_attn += cfg.num_groups

    if cfg.attn_impl in ("xla_chunked", "xla_chunked_skip", "kernel",
                         "pallas"):
        cq = min(cfg.attn_chunk, s)
        nq = s // cq
        nkv = nq
        per_step = 4.0 * b * h * cq * cq * hd
        out["attn_chunked"] += n_attn * (nq * nkv - 1) * per_step
        if n_x:
            sv = cfg.vision_seq
            ckv = min(cfg.attn_chunk, sv)
            nkv_x = sv // ckv
            out["attn_chunked"] += n_x * (nq * nkv_x - 1) * \
                4.0 * b * h * cq * ckv * hd

    if kind == "train":
        c = min(512, s)
        nchunk = s // c
        out["loss_chunks"] = (nchunk - 1) * 6.0 * b * c * d * cfg.vocab_size

    n_mamba = sum(1 for m, _ in cfg.block_pattern
                  if m == "mamba") * cfg.num_groups
    if n_mamba:
        d_in = cfg.ssm_expand * d
        nh = d_in // cfg.ssm_head_dim
        p_, n_ = cfg.ssm_head_dim, cfg.ssm_state
        L = min(cfg.ssm_chunk, s)
        nc = s // L
        per_chunk = b * L * L * nh * (n_ + p_) + 4.0 * b * L * nh * p_ * n_
        mult = 3.0 if kind == "train" else 1.0  # fwd+recompute+bwd
        out["mamba_chunks"] = n_mamba * (nc - 1) * per_chunk * mult

    n_mlstm = sum(1 for m, _ in cfg.block_pattern
                  if m == "mlstm") * cfg.num_groups
    if n_mlstm:
        dh = d // cfg.num_heads
        L = min(cfg.xlstm_chunk, s)
        nc = s // L
        per_chunk = 4.0 * b * L * L * cfg.num_heads * dh
        mult = 3.0 if kind == "train" else 1.0
        out["mlstm_chunks"] = n_mlstm * (nc - 1) * per_chunk * mult

    n_slstm = sum(1 for m, _ in cfg.block_pattern
                  if m == "slstm") * cfg.num_groups
    if n_slstm:
        dh = d // cfg.num_heads
        per_step = 8.0 * b * cfg.num_heads * dh * dh
        mult = 3.0 if kind == "train" else 1.0
        out["slstm_steps"] = n_slstm * (s - 1) * per_step * mult

    return out


# ---------------------------------------------------------------------------
# per-kernel rooflines (analytic FLOPs/bytes for one kernel invocation)
# ---------------------------------------------------------------------------

def kernel_roofline(kernel: str, *, dtype_bytes: int = 2,
                    **dims) -> Dict[str, float]:
    """Analytic single-chip roofline for ONE invocation of a Pallas kernel.

    FLOPs count the matmul terms (2 per multiply-add; softmax/exp
    elementwise terms are <3% and omitted); bytes are the MINIMAL HBM
    traffic — each operand read once, each output written once — i.e. the
    perfectly-blocked ideal the kernels aim for. ``roofline_s`` is the
    achievable lower bound on one v5e chip (mesh.PEAK_FLOPS_BF16 /
    mesh.HBM_BW); benchmarks/run.py --suite kernels reports
    measured_s / roofline_s as the achieved-vs-roofline ratio.

    Dims per kernel:
      flash_attention   b, h, kh, s, hd [, window, causal=True]
      decode_attention  b, h, kh, s, hd
      ssd_chunk         bh, l, n, p
      vtrace            t, b
      maxpool_fwd/_bwd  h, w, c, n (the pool's input; FLOPs here are the
                        window's 8 comparisons, or its 9 selects, per
                        output)
    """
    from repro.launch import mesh as mesh_lib
    if kernel == "flash_attention":
        b, h, kh = dims["b"], dims["h"], dims["kh"]
        s, hd = dims["s"], dims["hd"]
        window = dims.get("window", 0)
        # visited (q, kv) pairs: causal halves the square; a sliding
        # window caps each query's kv span
        s_eff = min(window, s) if window else (s + 1) / 2.0
        if not dims.get("causal", True):
            s_eff = s
        flops = 4.0 * b * h * s * s_eff * hd           # qk^T + pv
        bytes_ = dtype_bytes * (2 * b * h * s * hd      # q + o
                                + 2 * b * kh * s * hd)  # k + v (unexpanded)
    elif kernel == "decode_attention":
        b, h, kh = dims["b"], dims["h"], dims["kh"]
        s, hd = dims["s"], dims["hd"]
        flops = 4.0 * b * h * s * hd
        bytes_ = dtype_bytes * (2 * b * kh * s * hd     # streamed k + v
                                + 2 * b * h * hd)       # q + o
    elif kernel == "ssd_chunk":
        bh, L, n, p = dims["bh"], dims["l"], dims["n"], dims["p"]
        # G = C B^T (2L^2n); y_diag = (G.decay) X (2L^2p);
        # state update + y_off (2Lnp each)
        flops = bh * (2.0 * L * L * (n + p) + 4.0 * L * n * p)
        bytes_ = dtype_bytes * bh * (2 * L * n + 2 * L * p + 2 * p * n + L)
    elif kernel == "vtrace":
        t, b = dims["t"], dims["b"]
        flops = 3.0 * t * b                             # one fma + mul per cell
        bytes_ = 4 * 3 * t * b                          # deltas, dcs, out fp32
    elif kernel in ("maxpool_fwd", "maxpool_bwd"):
        h, w, c, n = dims["h"], dims["w"], dims["c"], dims["n"]
        outs = ((h + 1) // 2) * ((w + 1) // 2) * c * n
        flops = (8.0 if kernel == "maxpool_fwd" else 9.0) * outs
        # x (or dx) once, the pooled max (or dy) and the int8 index once
        bytes_ = dtype_bytes * (h * w * c * n + outs) + outs
    else:
        raise ValueError(f"unknown kernel {kernel}")
    compute_s = flops / mesh_lib.PEAK_FLOPS_BF16
    memory_s = bytes_ / mesh_lib.HBM_BW
    return {
        "flops": flops,
        "bytes": bytes_,
        "intensity": flops / bytes_ if bytes_ else 0.0,
        "roofline_s": max(compute_s, memory_s),
        "bound": "compute" if compute_s >= memory_s else "memory",
    }


def kernel_rooflines(cfg: ModelConfig, shape_name: str) -> Dict[str, Dict]:
    """Per-arch kernel roofline table: for every Pallas kernel with a hot
    path in this (cfg, input-shape), the analytic single-invocation
    roofline plus how many invocations one step performs
    (``calls_per_step`` = layers x inner chunks). Archs without the mixer
    simply omit the kernel."""
    ishape = INPUT_SHAPES[shape_name]
    b, s = ishape.global_batch, ishape.seq_len
    kind = ishape.kind
    dtype_bytes = jnp.dtype(cfg.dtype).itemsize
    hd = cfg.resolved_head_dim

    n_attn = sum(1 for m, _ in cfg.block_pattern
                 if m in ("attn", "local_attn", "swa_attn")) * cfg.num_groups
    if cfg.shared_attn_every:
        n_attn += cfg.num_groups
    n_mamba = sum(1 for m, _ in cfg.block_pattern
                  if m == "mamba") * cfg.num_groups

    out: Dict[str, Dict] = {}
    if n_attn:
        if kind == "decode":
            rl = kernel_roofline("decode_attention", dtype_bytes=dtype_bytes,
                                 b=b, h=cfg.num_heads, kh=cfg.num_kv_heads,
                                 s=s, hd=hd)
            rl["calls_per_step"] = n_attn
            out["decode_attention"] = rl
        else:
            rl = kernel_roofline("flash_attention", dtype_bytes=dtype_bytes,
                                 b=b, h=cfg.num_heads, kh=cfg.num_kv_heads,
                                 s=s, hd=hd,
                                 window=(cfg.sliding_window if all(
                                     m in ("swa_attn", "local_attn")
                                     for m, _ in cfg.block_pattern
                                     if m.endswith("attn")) else 0))
            rl["calls_per_step"] = n_attn * (3 if kind == "train" else 1)
            out["flash_attention"] = rl
    if n_mamba and kind != "decode":
        d_in = cfg.ssm_expand * cfg.d_model
        nh = d_in // cfg.ssm_head_dim
        L = min(cfg.ssm_chunk, s)
        rl = kernel_roofline("ssd_chunk", dtype_bytes=4,  # fp32 state math
                             bh=b * nh, l=L, n=cfg.ssm_state,
                             p=cfg.ssm_head_dim)
        rl["calls_per_step"] = n_mamba * (s // L) * (3 if kind == "train"
                                                     else 1)
        out["ssd_chunk"] = rl
    if kind == "train":
        rl = kernel_roofline("vtrace", t=s, b=b)
        rl["calls_per_step"] = 1
        out["vtrace"] = rl
    return out
