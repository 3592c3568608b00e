"""Each Pallas kernel compiles for a TPU v5e (``interpret=False``) at the
main path's real widths, against a described ``v5e:2x2`` topology — no chip
needed, nothing runs. Catches what interpret mode cannot: block shapes that
break Mosaic's tiling rule, primitives Mosaic does not lower, VMEM
overruns.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, so describing it while pytest-xdist
workers import this file would fail all but one of them.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as dec
from repro.kernels import flash_attention as fa
from repro.kernels import maxpool as mp
from repro.kernels import ops as kernel_ops
from repro.kernels import ssd_chunk as ssd
from repro.kernels import vtrace as vt
from repro.models import convnet

BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compilation cache off: a
    program compiled for a described chip is written to the cache but
    cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")  # else it logs under /tmp
            try:
                desc = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 — any failure: cannot
                pytest.skip(f"no v5e:2x2 topology can be described here: "
                            f"{e}")
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    # the kernels' own matmul precision, not the suite's "highest" default
    with jax.default_matmul_precision("default"):
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("t", [20, 80])
def test_vtrace_compiles(one_chip, t):
    """The learner's V-trace at the smoke's unroll and the paper's, B=32."""
    fn = functools.partial(vt.vtrace_scan, interpret=False)
    _compile(fn, one_chip, ((t, 32), F32), ((t, 32), F32))


@pytest.mark.parametrize("h,kh,hd,s", [
    (32, 32, 80, 37),      # zamba2-2.7b shared attention, odd prompt
    (32, 8, 128, 512),     # qwen3-4b GQA
], ids=["hd80-s37", "hd128-gqa-s512"])
def test_flash_attention_compiles(one_chip, h, kh, hd, s):
    blk = min(s, 128)
    fn = functools.partial(fa.flash_attention, block_q=blk, block_k=blk,
                           interpret=False)
    _compile(fn, one_chip, ((1, h, s, hd), BF16), ((1, kh, s, hd), BF16),
             ((1, kh, s, hd), BF16))


@pytest.mark.parametrize("cap", [31, 512])
def test_decode_attention_compiles(one_chip, cap):
    """zamba2-2.7b's shared-attention decode at batch 8, per-row slot
    positions; cap 31 is a block equal to the whole cache."""
    b, h, hd = 8, 32, 80
    fn = functools.partial(dec.decode_attention, block_k=min(cap, 128),
                           interpret=False)
    _compile(fn, one_chip, ((b, h, hd), BF16), ((b, h, cap, hd), BF16),
             ((b, h, cap, hd), BF16), ((b, cap), I32), ((b,), I32))


@pytest.mark.parametrize("length", [37, 256])
def test_ssd_chunk_compiles(one_chip, length):
    """One Mamba2 chunk of zamba2-2.7b (80 heads, N = P = 64): a short
    prompt's exact-length chunk and the full 256-token chunk."""
    bh, n, p = 80, 64, 64
    fn = functools.partial(ssd.ssd_chunk, interpret=False)
    _compile(fn, one_chip, ((bh, length, n), F32), ((bh, length, n), F32),
             ((bh, length, p), F32), ((bh, length, 1), F32),
             ((bh, p, n), F32))


@pytest.mark.parametrize("h,c", [(84, 16), (21, 32)],
                         ids=["section1-84", "section3-21"])
def test_maxpool_compiles(one_chip, h, c):
    """The deep ResNet's first and last pools over the learner's 2,592
    frames, on the (H, W, C, N) view: the forward and the backward."""
    n, ho = 81 * 32, mp.pooled_size(h)
    _compile(functools.partial(mp.maxpool_fwd, interpret=False), one_chip,
             ((h, h, c, n), F32))
    _compile(functools.partial(mp.maxpool_bwd, hw=(h, h), interpret=False),
             one_chip, ((ho, ho, c, n), I8), ((ho, ho, c, n), F32))


def _impala_hlo(one_chip, monkeypatch, grad, n):
    """The deep ResNet at Atari shapes over n frames, compiled for the
    chip: its parameter gradient, or the actors' forward."""
    monkeypatch.setattr(kernel_ops, "resolve_interpret",
                        lambda interpret=None: False)  # the chip's choice
    init_fn, apply_fn = convnet.impala_deep((84, 84, 4), 18)
    params = jax.eval_shape(lambda: convnet.init_agent(
        init_fn, jax.random.PRNGKey(0))[0])
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params)
    obs = jax.ShapeDtypeStruct((n, 84, 84, 4), F32, sharding=one_chip)

    def loss(p, o):
        out = apply_fn(p, o)
        return out.policy_logits.sum() + out.baseline.sum()

    fn = jax.grad(loss) if grad else apply_fn
    with jax.default_matmul_precision("default"):
        return jax.jit(fn).lower(params, obs).compile().as_text()


def test_impala_grad_pools_without_select_and_scatter(one_chip,
                                                      monkeypatch):
    """The learner's gradient at T=80, B=32 runs the pool kernels and no
    select-and-scatter, and neither copies nor transposes a section-1
    pool input: the kernels' view is XLA's own layout."""
    text = _impala_hlo(one_chip, monkeypatch, grad=True, n=81 * 32)
    assert "select-and-scatter" not in text
    calls = re.findall(r"%(maxpool_(?:fwd|bwd))\.\d+ = .*custom-call\(",
                       text)
    assert sorted(calls) == ["maxpool_bwd"] * 3 + ["maxpool_fwd"] * 3
    pool_sized = re.findall(r"= f32\[2592,84,84,16\]\{[^}]*\} "
                            r"(?:copy|transpose)\(", text)
    assert not pool_sized, pool_sized


def test_impala_actor_forward_pools_with_reduce_window(one_chip,
                                                       monkeypatch):
    """The actors' forward (batch 32, not differentiated) keeps the
    reduce-window pool and runs no kernel."""
    text = _impala_hlo(one_chip, monkeypatch, grad=False, n=32)
    assert text.count("reduce-window(") == 3
    assert "tpu_custom_call" not in text


def test_kernels_named_in_compiled_program(one_chip):
    """The compiled program names each kernel's custom call after its
    pallas_call name=, which is what a device trace shows."""
    cases = {
        "vtrace": (functools.partial(vt.vtrace_scan, interpret=False),
                   ((8, 128), F32), ((8, 128), F32)),
        "flash_attention": (
            functools.partial(fa.flash_attention, block_q=128, block_k=128,
                              interpret=False),
            ((1, 2, 128, 128), BF16), ((1, 2, 128, 128), BF16),
            ((1, 2, 128, 128), BF16)),
        "decode_attention": (
            functools.partial(dec.decode_attention, block_k=128,
                              interpret=False),
            ((8, 2, 128), BF16), ((8, 2, 128, 128), BF16),
            ((8, 2, 128, 128), BF16), ((8, 128), I32), ((8,), I32)),
        "ssd_chunk": (functools.partial(ssd.ssd_chunk, interpret=False),
                      ((2, 64, 64), F32), ((2, 64, 64), F32),
                      ((2, 64, 64), F32), ((2, 64, 1), F32),
                      ((2, 64, 64), F32)),
        "maxpool_fwd": (functools.partial(mp.maxpool_fwd, interpret=False),
                        ((8, 8, 8, 128), F32)),
        "maxpool_bwd": (functools.partial(mp.maxpool_bwd, hw=(8, 8),
                                          interpret=False),
                        ((4, 4, 8, 128), I8), ((4, 4, 8, 128), F32)),
    }
    for name, (fn, *shapes) in cases.items():
        text = _compile(fn, one_chip, *shapes)
        calls = re.findall(r"%([\w.]+) = .*? custom-call\(.*"
                           r'custom_call_target="tpu_custom_call"', text)
        assert calls and all(c.split(".")[0] == name for c in calls), \
            (name, calls)
