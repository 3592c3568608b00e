"""``chip_smoke.py`` and the compile-cache rule, off the chip: the smoke
refuses to run without a TPU, and its phases run at a tiny size on the CPU
(kernels in interpret mode), so the script cannot rot between chip runs."""

import os
import shutil
import subprocess
import sys

import jax
import pytest

from conftest import REPO, run_forced
from repro.configs import get_reduced_config
from repro.launch import compile_cache

SMOKE = os.path.join(REPO, "chip_smoke.py")
TINY_TRAIN = ["--mode", "rl-agent", "--agent", "deep", "--vtrace-impl",
              "kernel", "--batch", "8", "--steps", "2"]


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_smoke_refuses_without_tpu(tmp_path, where):
    """On the CPU, and as a lone script without the repo beside it, it
    exits nonzero and prints no result line."""
    script = SMOKE
    if where == "alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SMOKE, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, script], env=env, cwd=tmp_path,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    expect = "no TPU" if where == "repo" else "No module named 'repro'"
    assert expect in proc.stderr, proc.stderr[-2000:]


def test_smoke_phases_tiny_on_cpu(monkeypatch, tmp_path):
    # set after jax is imported: JAX reads it at import, so nothing is
    # cached, and use_compile_cache leaves the config alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke as cs

    clock = cs.CompileClock()
    runtime = cs.phase_a(TINY_TRAIN, clock)
    assert runtime.frames == 2 * cs.UNROLL * 8
    lens = (7, 16)            # the reduced config's SSD chunk is 16
    cfg = cs.phase_b(clock, cfg=get_reduced_config("zamba2-2.7b"),
                     max_batch=4, max_len=64, prompt_lens=lens, requests=5,
                     gen_tokens=5)
    assert cfg.attn_impl == "kernel" and cfg.ssd_impl == "kernel"
    cs.kernel_parity(cfg, clock, b=8, prompt_lens=lens, max_batch=4,
                     max_len=64)


_FOUR_SCRIPT = r"""
import os, sys
os.environ["JAX_COMPILATION_CACHE_DIR"] = %r
sys.path.insert(0, %r)
import chip_smoke as cs
clock = cs.CompileClock()
runtime = cs.phase_a(%r + ["--mesh-data", "4"], clock)
cs.check_sharded_source(runtime, 4)
cs.mesh_loss_parity(clock, n=4, b=8)
print("FOUR OK")
"""


def test_smoke_four_chip_path_on_forced_devices(tmp_path):
    """The --four-chips phases on four forced host devices: the sharded
    learner batch and actor carries span four devices, and mesh-4 losses
    match mesh 1 with the V-trace kernel under shard_map."""
    script = _FOUR_SCRIPT % (str(tmp_path), REPO, TINY_TRAIN)
    proc = run_forced(script=script, devices=4)
    assert "FOUR OK" in proc.stdout
    assert "learner batch over 4 devices [0, 1, 2, 3]" in proc.stdout


def test_compile_cache_dir_rule(monkeypatch, tmp_path):
    """The env var wins and nothing is set; otherwise the fixed path
    inside the checkout."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv(compile_cache.ENV_VAR)
    try:
        assert compile_cache.use_compile_cache() == os.path.join(
            REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
