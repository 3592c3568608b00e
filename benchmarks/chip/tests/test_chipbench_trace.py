"""The trace reduction on hand-made events, laid out as
``devtrace.events_from_xplane`` returns a TPU trace's, and on one step of
impala-atari recorded on a TPU v5e (data/trace_v5e_step.json.gz: the
device events of one actor unroll and one learner step, times from the
first event, op names cut to 120 characters)."""

import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(HERE, "..", "..")]

from chip import devtrace  # noqa: E402

OPS, MODS = devtrace.OPS, devtrace.MODULES
DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"


def test_busy_union_programs_and_gaps():
    ms = 1_000_000
    events = [
        (DEV0, MODS, "jit_step(11)", 0, 10 * ms),
        (DEV0, OPS, "fusion.1", 0, 4 * ms),
        (DEV0, OPS, "%fusion.2 = f32[8]{0} fusion(%p.1)", 2 * ms, 6 * ms),
        (DEV0, OPS, "all-reduce.3", 8 * ms, 2 * ms),
        (DEV0, MODS, "jit_admit(12)", 15 * ms, 5 * ms),
        (DEV0, OPS, "fusion.1", 15 * ms, 5 * ms),
        (DEV0, MODS, "jit_step(11)", 30 * ms, 10 * ms),
        (DEV0, OPS, "fusion.1", 30 * ms, 10 * ms),
    ]
    r = devtrace.reduce_events(events, window_s=0.05)
    assert r["chips"] == 1
    assert r["busy_s"] == pytest.approx(0.025)
    assert r["programs"]["jit_step"] == {"calls": 2, "device_s":
                                         pytest.approx(0.020)}
    assert devtrace.per_call_ms(r, "jit_admit") == pytest.approx(5.0)
    assert devtrace.per_call_ms(r, "jit_missing") is None
    assert r["collective_s"] == pytest.approx(0.002)
    ops = dict(r["device_ops"])
    assert r["device_ops"][0] == ["jit_step/fusion.1", pytest.approx(0.014)]
    assert ops["jit_step/fusion.2"] == pytest.approx(0.006)
    assert ops["jit_admit/fusion.1"] == pytest.approx(0.005)
    gaps = dict(r["idle_gaps"])
    assert gaps["jit_admit -> jit_step"] == pytest.approx(0.010)
    assert gaps["jit_step -> jit_admit"] == pytest.approx(0.005)


def test_chips_are_averaged():
    ms = 1_000_000
    events = [(DEV0, OPS, "a", 0, 4 * ms), (DEV1, OPS, "a", 0, 2 * ms),
              (DEV0, MODS, "jit_f", 0, 4 * ms), (DEV1, MODS, "jit_f", 0,
                                                   2 * ms)]
    r = devtrace.reduce_events(events, 0.01)
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx(0.003)
    assert r["programs"]["jit_f"]["calls"] == 1


def test_empty_trace_reads_nothing():
    r = devtrace.reduce_events([], 1.0)
    assert r["chips"] == 0 and r["programs"] == {}


def test_recorded_v5e_step():
    with gzip.open(os.path.join(HERE, "data", "trace_v5e_step.json.gz"),
                   "rt") as f:
        events = [tuple(e) for e in json.load(f)]
    r = devtrace.reduce_events(events, window_s=0.07202)
    assert r["chips"] == 1
    assert set(r["programs"]) >= {"jit_unroll", "jit_train_step"}
    assert r["programs"]["jit_train_step"]["calls"] == 1
    assert devtrace.per_call_ms(r, "jit_train_step") == pytest.approx(
        44.40, rel=1e-3)
    assert devtrace.per_call_ms(r, "jit_unroll") == pytest.approx(
        27.60, rel=1e-3)
    assert 0.99 < r["busy_s"] / r["window_s"] <= 1.0
    ops = dict(r["device_ops"])
    assert r["device_ops"][0][0] == "jit_unroll/while.8"
    assert all(" " not in name for name in ops)
    assert all(k.split("/")[0] in r["programs"] for k in ops)
