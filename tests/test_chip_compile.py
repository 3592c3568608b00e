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
from repro.kernels import ssd_chunk as ssd
from repro.kernels import vtrace as vt

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


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
    }
    for name, (fn, *shapes) in cases.items():
        text = _compile(fn, one_chip, *shapes)
        calls = re.findall(r"%([\w.]+) = .*? custom-call\(.*"
                           r'custom_call_target="tpu_custom_call"', text)
        assert calls and all(c.split(".")[0] == name for c in calls), \
            (name, calls)
