"""A run with the timed path broken underneath comes out not correct.

Each test skips the look for a chip and drives the rest of a run
(run.execute: set-up, window, check) at a reduced size on the CPU, with
one fault planted in the program: a learner step that returns its state
unchanged, a learner step on half of its batch, and the control (the
reference one precision step down, fp8 operands) in the program's place.
The last test reads the control's numbers beside the program's.
"""

import copy
import importlib.util
import os
import sys
import types

import jax

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, "..", "..", ".."))
sys.path[:0] = [os.path.join(ROOT, "benchmarks")]

from chip import harness  # noqa: E402
from chip.reference import impala as ref_impala  # noqa: E402

SEED = 2**32 + 77


def _run_module():
    spec = importlib.util.spec_from_file_location(
        "chipbench_run", os.path.join(ROOT, "benchmarks", "chip", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_impala():
    cell = harness.resolve_cell("impala-atari")
    cfg = copy.deepcopy(cell.config)
    cfg["model"].update(obs_shape=[12, 12, 4], channels=[4, 8, 8], fc=16)
    cfg["env"].update(obs_shape=[12, 12, 4], sprite_half=1)
    cell.config = cfg
    cell.traffic = dict(cell.traffic, unroll_length=5, batch_per_chip=4)
    return cell


def _execute(cell, seconds=1.0):
    from repro.launch import compile_cache
    args = types.SimpleNamespace(seed=SEED, seconds=seconds, trace=0,
                                 trace_dir=None, calibrate=0)
    real = compile_cache.use_compile_cache
    compile_cache.use_compile_cache = lambda: None   # no cache on the CPU
    try:
        return _run_module().execute(cell, args, jax.devices()[:1])
    finally:
        compile_cache.use_compile_cache = real


def test_sound_runs_are_correct():
    out = _execute(tiny_impala())
    assert out["correct"], out
    assert out["attempted"] > 0 and out["failed"] == 0


def _patch_step(monkeypatch, wrap):
    from repro.core import learner
    real = learner.make_train_step
    monkeypatch.setattr(learner, "make_train_step",
                        lambda *a, **k: wrap(real(*a, **k)))


def test_learner_state_unchanged_is_caught(monkeypatch):
    def wrap(step):
        def frozen(params, opt_state, i, batch):
            _, _, metrics = step(params, opt_state, i, batch)
            return params, opt_state, metrics
        return frozen
    _patch_step(monkeypatch, wrap)
    out = _execute(tiny_impala())
    assert not out["correct"]
    assert out["checks"]["update_gap"][0] > 0.9


def test_learner_half_batch_is_caught(monkeypatch):
    def wrap(step):
        def half(params, opt_state, i, batch):
            b = batch["action"].shape[1] // 2
            return step(params, opt_state, i,
                        {k: v[:, :b] for k, v in batch.items()})
        return half
    _patch_step(monkeypatch, wrap)
    assert not _execute(tiny_impala())["correct"]


def test_control_in_the_programs_place_is_caught(monkeypatch):
    """The learner step replaced by the reference's fp8 control step, on
    the program's own state: the harness reads it as not correct."""
    from chip.runners import rl_train
    cell = tiny_impala()
    hp = rl_train.Run(cell, types.SimpleNamespace(), jax.devices()[:1]).hp
    items = rl_train.hp_items(hp)

    def control(apply_fn, opt, hp_, **_):
        def step(params, opt_state, i, batch):
            n = batch["obs"].shape[0] * batch["obs"].shape[1]
            params, ms, total, _, _ = ref_impala.train_step(
                params, opt_state["ms"], i, batch, hp_items=items,
                block=rl_train._block(n), low=True)
            return params, {"ms": ms}, {"loss": total}
        return step
    from repro.core import learner
    monkeypatch.setattr(learner, "make_train_step", control)
    out = _execute(cell)
    assert not out["correct"], out["checks"]


def test_control_is_separated_from_the_program():
    """The control in the program's place reads at least 3 times what the
    program reads, on one of the compared numbers."""
    from repro.launch import compile_cache
    from chip.runners import rl_train
    args = types.SimpleNamespace(seed=SEED, seconds=1.0, trace=0,
                                 calibrate=0)
    real = compile_cache.use_compile_cache
    compile_cache.use_compile_cache = lambda: None
    try:
        run = rl_train.Run(tiny_impala(), args, jax.devices()[:1])
        first = run._first_steps(SEED)[-1]
        want = run._reference(first)
        prog = rl_train.readings(first, want)
        ctrl = rl_train.readings(run._reference(first, low=True), want)
    finally:
        compile_cache.use_compile_cache = real
    assert any(ctrl[k] >= 3 * prog[k] and ctrl[k] > 0 for k in prog), \
        (prog, ctrl)
