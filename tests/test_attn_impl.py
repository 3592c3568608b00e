"""Pallas-kernel impl parity: the ``--attn-impl kernel`` / ``--ssd-impl
kernel`` paths agree with xla to 1e-5 (fp32, interpret mode on CPU).

Layers of the pyramid:
  * ``attn_apply`` fwd/bwd vs xla across GQA / MQA / sliding-window /
    softcap, and ``attn_decode`` against the ring-buffer cache;
  * whole-model fwd/bwd for every zoo arch with an attention or mamba
    mixer (xlstm-125m has neither and is excluded);
  * prefill -> decode roundtrip: kernel-impl serve_step logits vs the
    xla decode path from the same kernel-built cache;
  * end-to-end: per-step LM pretrain losses (the acceptance criterion)
    in-process, and under a ("data","model") mesh with 2 forced host
    devices in a subprocess (conftest.run_forced).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_forced
from repro.configs import ARCHS, get_reduced_config
from repro.models import attention as A
from repro.models import model as model_lib

TOL = dict(rtol=1e-5, atol=1e-5)


def _has_kernel_mixer(cfg):
    mixers = {m for m, _ in cfg.block_pattern}
    return bool(mixers & {"attn", "local_attn", "swa_attn", "xattn",
                          "mamba"}) or cfg.shared_attn_every > 0


KERNEL_ARCHS = [a for a in ARCHS
                if _has_kernel_mixer(get_reduced_config(a))]


def _kernel_cfg(cfg):
    return dataclasses.replace(cfg, attn_impl="kernel", ssd_impl="kernel")


# ---------------------------------------------------------------------------
# attn_apply: kernel vs xla, forward and backward
# ---------------------------------------------------------------------------

def _attn_setup(cfg, b=2, s=96, key=0):
    k = jax.random.PRNGKey(key)
    params = jax.tree.map(
        lambda p: p.value if hasattr(p, "value") else p,
        A.attn_init(k, cfg, "attn"),
        is_leaf=lambda x: hasattr(x, "value"))
    x = jax.random.normal(jax.random.fold_in(k, 1), (b, s, cfg.d_model),
                          jnp.float32)
    return params, x


@pytest.mark.parametrize("kind,softcap,kv_heads", [
    ("attn", None, 2),        # GQA
    ("attn", None, 1),        # MQA
    ("attn", 30.0, 2),        # softcap inside the kernel
    ("swa_attn", None, 2),    # sliding window inside the kernel
])
def test_attn_apply_kernel_matches_xla(kind, softcap, kv_heads):
    cfg = dataclasses.replace(get_reduced_config("qwen3-32b"),
                              attn_logit_softcap=softcap, sliding_window=48,
                              attn_chunk=32, num_kv_heads=kv_heads)
    params, x = _attn_setup(cfg)
    pos = jnp.arange(x.shape[1])

    def run(impl):
        def f(x):
            o, _ = A.attn_apply(params, x, cfg=cfg, kind=kind,
                                positions=pos, impl=impl)
            return jnp.mean(jnp.square(o.astype(jnp.float32))), o

        (loss, o), g = jax.value_and_grad(f, has_aux=True)(x)
        return o, g

    o_ref, g_ref = run("xla")
    o_k, g_k = run("kernel")
    np.testing.assert_allclose(o_ref, o_k, **TOL)
    np.testing.assert_allclose(g_ref, g_k, **TOL)


def test_attn_decode_kernel_ring_buffer():
    """Kernel decode equals xla decode at every step, through the
    ring-buffer wrap of a window-sized cache."""
    cfg = dataclasses.replace(get_reduced_config("qwen3-32b"),
                              sliding_window=16, attn_chunk=16)
    params, x = _attn_setup(cfg, b=1, s=40)
    for kind in ("swa_attn", "attn"):
        caches = {"xla": A.attn_cache_init(cfg, kind, 1, 40, x.dtype),
                  "kernel": A.attn_cache_init(cfg, kind, 1, 40, x.dtype)}
        for t in range(40):
            outs = {}
            for impl in ("xla", "kernel"):
                outs[impl], caches[impl] = A.attn_decode(
                    params, x[:, t:t + 1], caches[impl], cfg=cfg,
                    kind=kind, pos=jnp.int32(t), impl=impl)
            np.testing.assert_allclose(outs["xla"], outs["kernel"], **TOL)


@pytest.mark.parametrize("s,want,align,block", [
    (37, 128, 8, 37),        # short axis: one whole block
    (256, 128, 8, 128),
    (200, 128, 8, 40),       # 100 divides 200 but is no multiple of 8
    (130, 128, 8, 130),      # no aligned divisor: the whole axis
    (512, 128, 128, 128),
    (200, 128, 128, 200),
])
def test_divisor_block_keeps_mosaic_tiling(s, want, align, block):
    """Kernel blocks tile the axis exactly and are a multiple of the tile
    or the whole axis (what Mosaic accepts on the chip)."""
    assert A._divisor_block(s, want, align) == block


# ---------------------------------------------------------------------------
# whole-model fwd/bwd parity for every arch with a kernel-served mixer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", KERNEL_ARCHS)
def test_model_fwd_bwd_kernel_parity(arch):
    cfg = get_reduced_config(arch)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 32)),
        jnp.int32)
    params, _ = model_lib.init(jax.random.PRNGKey(0), cfg)

    def run(cfg, impl):
        def loss(params):
            h, _, _ = model_lib.forward(params, tokens, cfg=cfg, impl=impl)
            return jnp.mean(jnp.square(h.astype(jnp.float32)))

        val, g = jax.value_and_grad(loss)(params)
        return val, g

    v_ref, g_ref = run(cfg, "xla")
    v_k, g_k = run(_kernel_cfg(cfg), "kernel")
    np.testing.assert_allclose(np.asarray(v_ref), np.asarray(v_k), **TOL)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), **TOL), g_ref, g_k)


# ---------------------------------------------------------------------------
# prefill -> decode roundtrip on the kernel path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen3-4b", "gemma2-27b", "mixtral-8x7b",
                                  "zamba2-2.7b"])
def test_prefill_decode_roundtrip_kernel(arch):
    """Kernel prefill builds the same caches as xla prefill, and kernel
    serve_step tracks xla serve_step token by token from that cache."""
    cfg = get_reduced_config(arch)
    cfg_k = _kernel_cfg(cfg)
    P, N = 16, 8
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (2, P + N)),
        jnp.int32)
    params, _ = model_lib.init(jax.random.PRNGKey(0), cfg)

    _, _, cache_ref = model_lib.prefill(params, tokens[:, :P], cfg=cfg,
                                        impl="xla", cache_seq_len=P + N)
    _, _, cache_k = model_lib.prefill(params, tokens[:, :P], cfg=cfg_k,
                                      impl="kernel", cache_seq_len=P + N)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), **TOL), cache_ref, cache_k)

    for t in range(P, P + N):
        lg_ref, _, cache_ref = model_lib.serve_step(
            params, tokens[:, t:t + 1], cache_ref, jnp.int32(t), cfg=cfg,
            impl="xla")
        lg_k, _, cache_k = model_lib.serve_step(
            params, tokens[:, t:t + 1], cache_k, jnp.int32(t), cfg=cfg_k,
            impl="kernel")
        np.testing.assert_allclose(np.asarray(lg_ref), np.asarray(lg_k),
                                   **TOL)


# ---------------------------------------------------------------------------
# end-to-end: per-step LM pretrain losses (the acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen3-4b", "zamba2-2.7b"])
def test_lm_pretrain_loss_parity_kernel(arch):
    from repro.configs.base import TrainConfig
    from repro.core import learner as L
    from repro.optim import make_optimizer

    cfg = get_reduced_config(arch)
    tc = TrainConfig(optimizer="adamw", learning_rate=1e-3, grad_clip=1.0,
                     lr_schedule="constant")
    params0, _ = model_lib.init(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer(tc)
    rng = np.random.default_rng(0)
    B, S = 4, 32
    batches = [{"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, S + 1)), jnp.int32)}
        for _ in range(3)]

    def losses(cfg):
        step = jax.jit(L.make_lm_pretrain_step(cfg, opt, loss_chunk=S))
        params, opt_state = params0, opt.init(params0)
        out = []
        for s, b in enumerate(batches):
            params, opt_state, m = step(params, opt_state, jnp.int32(s), b)
            out.append(float(m["loss"]))
        return out

    l_ref = losses(dataclasses.replace(cfg, attn_impl="xla"))
    l_k = losses(_kernel_cfg(cfg))
    np.testing.assert_allclose(l_ref, l_k, **TOL)


# ---------------------------------------------------------------------------
# sharded parity: kernel impl under a ("data","model") mesh, forced devices
# ---------------------------------------------------------------------------

_MESH_KERNEL_SCRIPT = r"""
import dataclasses

import jax, jax.numpy as jnp
import numpy as np

jax.config.update("jax_default_matmul_precision", "highest")

from repro.configs import get_reduced_config
from repro.configs.base import TrainConfig
from repro.core import learner as L
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh2d
from repro.models import model as M
from repro.optim import make_optimizer

B, S = 4, 32
cfg = get_reduced_config("qwen3-4b")
tc = TrainConfig(optimizer="adamw", learning_rate=1e-3, grad_clip=1.0,
                 lr_schedule="constant")
params0, axes = M.init(jax.random.PRNGKey(0), cfg)
opt = make_optimizer(tc)
rng = np.random.default_rng(0)
batches = [{"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                               (B, S + 1)), jnp.int32)}
           for _ in range(3)]


def losses(mesh, attn_impl, carry):
    icfg = dataclasses.replace(cfg, attn_impl=attn_impl)
    if mesh is None:
        params, gc, rules = params0, None, None
    else:
        rules = shd.MEGATRON_RULES
        pshard = shd.param_shardings(axes, mesh, rules, params0)
        gc = lambda g: jax.tree.map(jax.lax.with_sharding_constraint, g,
                                    pshard)
        params = jax.device_put(params0, pshard)
    step = jax.jit(L.make_lm_pretrain_step(icfg, opt, loss_chunk=S,
                                           grad_constraint=gc, mesh=mesh,
                                           rules=rules))
    opt_state0 = opt.init(params)
    opt_state, out = opt_state0, []
    for s, b in enumerate(batches):
        if carry:
            params, opt_state, m = step(params, opt_state, jnp.int32(s), b)
        else:
            _, _, m = step(params, opt_state0, jnp.int32(0), b)
        out.append(float(m["loss"]))
    return out


mesh = make_mesh2d(1, 2)  # --mesh-model 2
# per-step program parity from identical params: 1e-5
s_ref = losses(None, "xla", carry=False)
s_k = losses(mesh, "kernel", carry=False)
print("per-step xla unmeshed ", s_ref)
print("per-step kernel mesh12", s_k)
np.testing.assert_allclose(s_ref, s_k, rtol=1e-5, atol=1e-5)
# 3-step trajectory: reduction-order noise compounds through adamw
l_ref = losses(None, "xla", carry=True)
l_k = losses(mesh, "kernel", carry=True)
print("trajectory xla unmeshed ", l_ref)
print("trajectory kernel mesh12", l_k)
np.testing.assert_allclose(l_ref, l_k, rtol=1e-4, atol=1e-4)
print("KERNEL MESH PARITY OK")
"""


def test_kernel_mesh_model2_parity_subprocess():
    proc = run_forced(script=_MESH_KERNEL_SCRIPT, devices=2)
    assert "KERNEL MESH PARITY OK" in proc.stdout
