"""Spans inside the program: the named scopes that the RL learner steps and
the actors' unrolls leave in their compiled HLO, and the host spans that
``Runtime.run`` records on the profiler's clock (README "Tracing")."""

import glob
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.configs.atari_impala import small_train
from repro.core import learner as learner_lib
from repro.core import rollout as rollout_lib
from repro.core.runtime import Runtime
from repro.core.sources import DeviceSource
from repro.envs import catch
from repro.models.convnet import init_agent, minatar_lstm_net, minatar_net
from repro.optim import make_optimizer

T, B = 4, 2
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _op_names(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    return set(_OP_NAME.findall(text))


def _with(names, scope):
    """The op_names with ``scope`` as a component, e.g. 'jvp(loss)'."""
    token = re.compile(rf"(^|[/(]){re.escape(scope)}($|[/)])")
    return {n for n in names if token.search(n)}


def _setup(recurrent):
    env = catch.make()
    tc = small_train(unroll_length=T, batch_size=B)
    if recurrent:
        init_fn, apply_fn, init_state = minatar_lstm_net(env.obs_shape,
                                                         env.num_actions)
    else:
        init_fn, apply_fn = minatar_net(env.obs_shape, env.num_actions)
    params, _ = init_agent(init_fn, jax.random.PRNGKey(0))
    opt = make_optimizer(tc)
    key = jax.random.PRNGKey(1)
    carry = rollout_lib.env_reset_batch(env, key, B)
    if recurrent:
        unroll = rollout_lib.make_recurrent_unroll(env, apply_fn, init_state,
                                                   T)
        carry = unroll.initial_carry(*carry, B)
        step = learner_lib.make_recurrent_train_step(apply_fn, opt, tc)
    else:
        unroll = rollout_lib.make_unroll(env, apply_fn, T)
        step = learner_lib.make_train_step(apply_fn, opt, tc)
    _, rollout = jax.eval_shape(unroll, params, carry, key)
    return (unroll, (params, carry, key),
            step, (params, opt.init(params), jnp.int32(0), rollout))


@pytest.mark.parametrize("recurrent", [False, True],
                         ids=["feedforward", "recurrent"])
def test_learner_step_hlo_carries_scopes(recurrent):
    """learner_forward names the forward pass and, under transpose(, the
    backward; loss and optimizer name their own ops."""
    *_, step, args = _setup(recurrent)
    names = _op_names(step, *args)
    forward = _with(names, "learner_forward")
    assert {n for n in forward if "transpose(" not in n}
    assert _with(names, "transpose(jvp(learner_forward))")
    assert _with(names, "loss")
    assert _with(names, "optimizer")
    assert not _with(names, "actor_forward")


@pytest.mark.parametrize("recurrent", [False, True],
                         ids=["feedforward", "recurrent"])
def test_unroll_hlo_carries_scopes(recurrent):
    unroll, args, *_ = _setup(recurrent)
    names = _op_names(unroll, *args)
    assert _with(names, "actor_forward")
    assert _with(names, "env_step")
    # the scan's own ops (stacking each step's outputs) and the T+1
    # observations' concatenate
    assert _with(names, "rollout") - _with(names, "actor_forward") \
        - _with(names, "env_step")
    assert not _with(names, "learner_forward")


def _host_spans(trace_dir):
    """{line name: [(name, start_ns, end_ns, stats)]} of the host plane."""
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.setdefault(line.name, []).extend(
                (ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
                for ev in line.events)
    return out


def test_runtime_trace_has_a_train_span_per_step(tmp_path):
    """One ``train`` span per step, numbered, with the loop's pieces nested
    inside it; the checkpoint span closes the periodic save."""
    env = catch.make()
    init_fn, apply_fn = minatar_net(env.obs_shape, env.num_actions)
    params, _ = init_agent(init_fn, jax.random.PRNGKey(0))
    tc = small_train(unroll_length=T, batch_size=B)
    opt = make_optimizer(tc)
    src = DeviceSource.for_env(env, apply_fn, unroll_length=T, batch_size=B,
                               key=jax.random.PRNGKey(5), pipelined=True)
    step = jax.jit(learner_lib.make_train_step(apply_fn, opt, tc))
    seen = []
    rt = Runtime(src, step, params, opt.init(params), total_steps=3,
                 log_every=2, checkpoint_dir=str(tmp_path / "ckpt"),
                 checkpoint_every=2, print_fn=lambda _: None,
                 on_metrics=lambda s, m: seen.append(s))
    rt.run()                       # compiles outside the trace
    rt.start_step, rt.total_steps = 3, 6
    with jax.profiler.trace(str(tmp_path / "trace")):
        rt.run()
    assert seen == list(range(6))

    lines = _host_spans(tmp_path / "trace")
    line, = [evs for evs in lines.values()
             if any(e[0] == "train" for e in evs)]
    trains = sorted(e for e in line if e[0] == "train")
    assert [int(e[3]["step_num"]) for e in trains] == [3, 4, 5]
    children = {"source.next_batch", "learner.dispatch", "runtime.callbacks",
                "runtime.log", "runtime.checkpoint"}
    inside = {name: 0 for name in children}
    for name, s, e, _ in line:
        if name not in children:
            continue
        parent = [t for t in trains if t[1] <= s and e <= t[2]]
        if name == "runtime.checkpoint" and not parent:
            continue               # the final save, after the last step
        assert len(parent) == 1, (name, s, e)
        inside[name] += 1
    assert inside["source.next_batch"] == inside["learner.dispatch"] == 3
    assert inside["runtime.callbacks"] == 3
    assert inside["runtime.log"] == 2          # steps 4 and 5 (the last)
    assert inside["runtime.checkpoint"] == 1   # step 4's periodic save
