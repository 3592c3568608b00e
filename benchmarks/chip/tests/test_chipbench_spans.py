"""The reduction of the program's spans (chip/spans.py) on hand-made
events, laid out as ``spans.read_xplane`` returns a TPU trace's, and on
one step of impala-atari recorded on a TPU v5e
(data/trace_v5e_spans_step.json.gz: the device events of the window's
last actor unroll and learner step, op names cut to 120 characters, the
host spans of the Runtime's last step, times from the unroll's start, and
the op_name of each instruction from the compiled programs)."""

import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(HERE, "..", "..")]

from chip import devtrace, spans  # noqa: E402

OPS, MODS = devtrace.OPS, devtrace.MODULES
DEV = "/device:TPU:0"
HOST = "/host:CPU#0"
MS = 1_000_000
TS = "jit(train_step)"


def _op(name, start, dur):
    return (DEV, OPS, f"%{name} = f32[8]{{0}} fusion(%p.1)", start, dur)


# what the compiled learner step's HLO text says of each instruction
OP_NAMES = {spans.LEARNER: {
    "while.1": f"{TS}/jvp(learner_forward)/while",
    "fusion.1": f"{TS}/jvp(learner_forward)/conv",
    "fusion.2": f"{TS}/jvp(learner_forward)/add",
    "fusion.3": f"{TS}/transpose(jvp(learner_forward))/conv",
    "vtrace.1": f"{TS}/jvp(loss)/jit(vtrace_scan)/vtrace/pallas_call",
    "fusion.4": f"{TS}/transpose(jvp(loss))/mul",
    "fusion.5": f"{TS}/optimizer/sub",
}}


def _learner_step(t0):
    """One 10 ms jit_train_step: a while (2 ms) around two forward ops,
    the backward, the loss both ways, the optimizer and a copy."""
    return [
        (DEV, MODS, "jit_train_step(7)", t0, 10 * MS),
        _op("while.1", t0, 2 * MS),
        _op("fusion.1", t0, MS // 2),
        _op("fusion.2", t0 + MS, MS // 2),
        _op("fusion.3", t0 + 2 * MS, 4 * MS),
        _op("vtrace.1", t0 + 6 * MS, MS),
        _op("fusion.4", t0 + 7 * MS, MS),
        _op("fusion.5", t0 + 8 * MS, int(1.5 * MS)),
        _op("copy.1", t0 + int(9.5 * MS), MS // 2),
    ]


def _host_step(t0, step_ms=3):
    """The Runtime's spans of one step from ``t0``: dispatch 1 ms in,
    callbacks the last 1 ms."""
    return [(HOST, "train", t0, step_ms * MS),
            (HOST, "source.next_batch", t0, MS // 2),
            (HOST, "learner.dispatch", t0 + MS, MS // 2),
            (HOST, "runtime.callbacks", t0 + (step_ms - 1) * MS, MS)]


def test_self_time_subtracts_nested_ops():
    own = spans.self_times([(0, 10), (1, 4), (2, 3), (5, 9), (12, 13)])
    assert own == [3, 2, 1, 4, 1]


def test_scope_of_innermost_and_backward():
    scopes = spans.SCOPES[spans.LEARNER]
    assert spans.scope_of(f"{TS}/transpose(jvp(learner_forward))/dot",
                          scopes) == ("learner_forward", spans.BACKWARD)
    assert spans.scope_of(f"{TS}/jvp(loss)/jit(vtrace_scan)/vtrace",
                          scopes) == ("loss", spans.FORWARD)
    assert spans.scope_of(f"{TS}/optimizer/jit(loss_scale)/mul",
                          scopes) == ("optimizer", spans.FORWARD)
    assert spans.scope_of(f"{TS}/jit(lossy)/mul", scopes) == ("",
                                                              spans.FORWARD)
    assert spans.scope_of("", scopes) == ("", spans.FORWARD)
    unroll = spans.SCOPES[spans.UNROLL]
    assert spans.scope_of("jit(unroll)/rollout/while/body/env_step/add",
                          unroll) == ("env_step", spans.FORWARD)
    assert spans.scope_of("jit(unroll)/rollout/while/body/dynamic_update"
                          "_slice", unroll) == ("rollout", spans.FORWARD)


def test_scope_of_takes_the_pass_from_an_enclosing_part():
    """A scope nested in the backward of another (the pools inside the
    learner's forward): the pass comes from the enclosing transpose."""
    scopes = ("learner_forward", "maxpool")
    assert spans.scope_of(
        f"{TS}/transpose(jvp(learner_forward))/maxpool/jit(maxpool_bwd)/"
        "pallas_call", scopes) == ("maxpool", spans.BACKWARD)
    assert spans.scope_of(
        f"{TS}/jvp(learner_forward)/maxpool/jit(maxpool_fwd)/pallas_call",
        scopes) == ("maxpool", spans.FORWARD)
    assert spans.scope_of(f"{TS}/transpose(jvp(learner_forward))/maxpool",
                          ("learner_forward",)) == ("learner_forward",
                                                    spans.BACKWARD)
    # a transpose nested inside the scope does not make it backward
    assert spans.scope_of(f"{TS}/jvp(learner_forward)/maxpool/transpose",
                          scopes) == ("maxpool", spans.FORWARD)


def test_scopes_forward_backward_per_call():
    trace = {"device": _learner_step(0) + _learner_step(20 * MS),
             "host": _host_step(-5 * MS) + _host_step(15 * MS)}
    r = spans.reduce_spans(trace, OP_NAMES)
    p = r["scopes"][spans.LEARNER]
    assert p["calls"] == 2 and p["device_s"] == pytest.approx(0.020)
    # the while's own 1 ms counts, its two nested ops 0.5 ms each
    assert p["self_s"]["learner_forward"] == pytest.approx([0.004, 0.008])
    assert p["self_s"]["loss"] == pytest.approx([0.002, 0.002])
    assert p["self_s"][""] == pytest.approx([0.001, 0.0])
    ms = spans.layer_ms(r)
    assert ms["learner_forward_ms.train"] == pytest.approx(2.0)
    assert ms["learner_backward_ms.train"] == pytest.approx(4.0)
    assert ms["loss_ms.train"] == pytest.approx(2.0)
    assert ms["optimizer_ms.train"] == pytest.approx(1.5)
    assert ms["actor_forward_ms.train"] is None     # no unroll traced
    assert spans.coverage(r, spans.LEARNER) == pytest.approx(0.95)


def test_op_names_from_the_compiled_hlo():
    text = (
        "ENTRY %main.9 (p: f32[8]) -> f32[8] {\n"
        '  %fusion.3 = f32[8]{0} fusion(%p.1), kind=kLoop, '
        'calls=%fused_computation, metadata={op_name="jit(train_step)/'
        'transpose(jvp(learner_forward))/conv" stack_frame_id=4}\n'
        "  ROOT %copy.1 = f32[8]{0} copy(%fusion.3)\n}\n")
    names = spans.op_names_from_hlo(text)
    assert names == {"fusion.3":
                     f"{TS}/transpose(jvp(learner_forward))/conv"}
    r = spans.reduce_spans({"device": _learner_step(0), "host": []},
                           {spans.LEARNER: names})
    assert r["scopes"][spans.LEARNER]["self_s"]["learner_forward"] == \
        pytest.approx([0.0, 0.004])


def test_host_step_and_gaps_labelled_by_span():
    """Steps of 3 ms, 1 ms of it callbacks; each learner step runs 1 ms
    after its dispatch; the device's gaps take the innermost open span."""
    host = _host_step(0) + _host_step(10 * MS) + [
        (HOST, "train", 30 * MS, 3 * MS)]
    device = (_learner_step(2 * MS)
              + [(DEV, MODS, "jit_train_step(7)", 12 * MS, 10 * MS)]
              + [(DEV, MODS, "jit_unroll(8)", 24 * MS, MS)]
              + [(DEV, MODS, "jit_unroll(8)", 34 * MS, MS)])
    # idle 22-24 ms and 25-34 ms, with no span open at either midpoint
    r = spans.reduce_spans({"device": device, "host": host})
    assert r["clock"] == {"matched": 2, "ok": True,
                          "lead_ms": pytest.approx(1.0)}
    assert r["host_step_ms"] == pytest.approx(2.0 + 1.0 / 3)
    gaps = dict(r["idle_gaps_host"])
    assert gaps["none"] == pytest.approx(0.002 + 0.009)
    assert r["host_spans"]["train"][0] == 3
    host[-1:] = [(HOST, "train", 23 * MS, 2 * MS),
                 (HOST, "runtime.log", 23 * MS, MS // 2)]
    gaps = dict(spans.reduce_spans({"device": device, "host": host})
                ["idle_gaps_host"])
    assert gaps == {"runtime.log": pytest.approx(0.002),
                    "none": pytest.approx(0.009)}


def test_misaligned_clock_refuses_host_readings():
    """A learner step that starts on the device before the span that
    sent it: the host readings are refused."""
    device = _learner_step(0) + [
        (DEV, MODS, "jit_train_step(7)", 12 * MS, 5 * MS)]
    host = _host_step(-4 * MS) + _host_step(13 * MS)   # sent at 14 ms
    r = spans.reduce_spans({"device": device, "host": host}, OP_NAMES)
    assert r["clock"]["ok"] is False and r["clock"]["matched"] == 2
    assert r["clock"]["lead_ms"] == pytest.approx(-2.0)
    assert r["host_step_ms"] is None and r["idle_gaps_host"] == []
    assert spans.layer_ms(r)["host_step_ms.train"] is None
    assert spans.layer_ms(r)["learner_forward_ms.train"] is not None


def test_no_spans_read_nothing():
    """A program without scopes or host spans (as before they existed):
    every reading is None, and nothing raises."""
    unscoped = {spans.LEARNER: {k: f"{TS}/mul"
                                for k in OP_NAMES[spans.LEARNER]}}
    r = spans.reduce_spans({"device": _learner_step(0), "host": []},
                           unscoped)
    assert set(spans.layer_ms(r).values()) == {None}
    assert r["clock"]["ok"] is False and r["idle_gaps_host"] == []
    empty = spans.reduce_spans({"device": [], "host": []})
    assert empty["scopes"] == {} and spans.coverage(empty,
                                                    spans.LEARNER) is None


def test_read_xplane_keeps_the_runtime_spans(tmp_path, capsys):
    """A CPU profiler trace: the host spans by name, on one thread; the
    command line prints the whole reduction."""
    import jax
    from jax.profiler import StepTraceAnnotation, TraceAnnotation
    with jax.profiler.trace(str(tmp_path)):
        for i in range(2):
            with StepTraceAnnotation("train", step_num=i):
                with TraceAnnotation("learner.dispatch"):
                    pass
                with TraceAnnotation("not.a.span"):
                    pass
    trace = spans.read_xplane(str(tmp_path))
    assert trace["device"] == []
    names = [h[1] for h in sorted(trace["host"], key=lambda h: h[2])]
    assert names == ["train", "learner.dispatch"] * 2
    assert len({h[0] for h in trace["host"]}) == 1
    spans.main([str(tmp_path)])
    out = json.loads(capsys.readouterr().out)
    assert out["host_spans"]["train"][0] == 2
    assert out["clock"]["ok"] is False and out["host_step_ms.train"] is None


def test_tracer_hands_spans_on(tmp_path):
    """run.Tracer's ``reduce``: the device reduction, and the spans of the
    programs named, the host spans with them; no programs, no spans."""
    import importlib.util
    import jax
    from jax.profiler import StepTraceAnnotation
    spec = importlib.util.spec_from_file_location(
        "chipbench_run", os.path.join(HERE, "..", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    tracer = run.Tracer(str(tmp_path))
    tracer.start()
    with StepTraceAnnotation("train", step_num=0):
        jax.block_until_ready(jax.numpy.ones(4) + 1)
    tracer.stop()
    reduced, span_reduced, n_events = tracer.reduce({spans.LEARNER: ""})
    assert reduced["window_s"] == tracer.window_s
    assert n_events >= 0
    assert span_reduced["host_spans"]["train"][0] == 1
    assert tracer.reduce()[1] is None     # no programs named, no spans


def test_recorded_v5e_step_with_spans():
    """One impala-atari step from a v5e trace: the scopes account for
    over 96% of each program, in the same milliseconds as devtrace's
    per-call time, and the step's learner program starts after the
    dispatch span that sent it, 1.75 s of queued steps later."""
    with gzip.open(os.path.join(HERE, "data",
                                "trace_v5e_spans_step.json.gz"), "rt") as f:
        raw = json.load(f)
    trace = {k: [tuple(e) for e in raw[k]] for k in ("device", "host")}
    learner_ops = raw["op_names"][spans.LEARNER]
    r = spans.reduce_spans(trace, raw["op_names"])
    whole = devtrace.reduce_events(trace["device"], window_s=0.0726)
    for prog in (spans.LEARNER, spans.UNROLL):
        assert r["scopes"][prog]["calls"] == 1
        assert r["scopes"][prog]["device_s"] * 1e3 == pytest.approx(
            devtrace.per_call_ms(whole, prog))
    ms = spans.layer_ms(r)
    assert ms == pytest.approx({
        "learner_forward_ms.train": 14.915, "learner_backward_ms.train":
        29.104, "loss_ms.train": 0.0566, "optimizer_ms.train": 0.0479,
        "actor_forward_ms.train": 19.265, "env_ms.train": 4.301,
        "host_step_ms.train": 7.955}, rel=1e-3)
    assert spans.coverage(r, spans.LEARNER) == pytest.approx(0.9936,
                                                             abs=1e-4)
    assert spans.coverage(r, spans.UNROLL) == pytest.approx(0.9676,
                                                            abs=1e-4)
    # the V-trace kernel, under its own name, in the loss scope
    assert spans.scope_of(learner_ops["vtrace.1"],
                          spans.SCOPES[spans.LEARNER]) == ("loss",
                                                           spans.FORWARD)
    assert r["clock"]["ok"] and r["clock"]["matched"] == 1
    assert r["clock"]["lead_ms"] == pytest.approx(1752.2, abs=0.1)
    assert r["host_spans"]["train"][0] == 1


def test_training_runner_names_its_programs_text():
    """rl_train's hlo_texts, lowered after the window from the shapes that
    set-up kept, give the learner step's and the unroll's compiled text
    with the scopes that spans.SCOPES reads."""
    import copy
    import types
    import jax
    from chip import harness
    from chip.runners import rl_train
    from repro.launch import compile_cache
    cell = harness.resolve_cell("impala-atari")
    cfg = copy.deepcopy(cell.config)
    cfg["model"].update(obs_shape=[12, 12, 4], channels=[4, 8, 8], fc=16)
    cfg["env"].update(obs_shape=[12, 12, 4], sprite_half=1)
    cell.config = cfg
    cell.traffic = dict(cell.traffic, unroll_length=5, batch_per_chip=4)
    args = types.SimpleNamespace(seed=2**32 + 5, seconds=0.5, trace=0,
                                 calibrate=0)
    real = compile_cache.use_compile_cache
    compile_cache.use_compile_cache = lambda: None   # no cache on the CPU
    try:
        run = rl_train.Run(cell, args, jax.devices()[:1])
        run.setup()
        run.window(0.5, None)
        texts = run.hlo_texts()
    finally:
        compile_cache.use_compile_cache = real
    assert set(texts) == {spans.LEARNER, "jit_unroll"}
    for prog, text in texts.items():
        names = set(spans.op_names_from_hlo(text).values())
        for scope in spans.SCOPES[prog]:
            assert any(scope in n for n in names), (prog, scope)
