"""The chat serving runner (runners/serve_chat.py) on the CPU: its
schedule, its percentiles, its reading of the served tokens, and its
check, which passes the program and fails a run with the timed path
broken underneath (a slot's decode state left unchanged for one step; a
first token's log-probability taken from another slot).

The cell is a reduced zamba2 (the registry's CPU-smoke widths) behind
``launch/serve.Server``, with the program itself in float32 at
``HIGHEST`` as a test-only reference (program_reference.py).
"""

import copy
import dataclasses
import importlib.util
import os
import sys
import types

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, "..", "..", ".."))
sys.path[:0] = [os.path.join(ROOT, "benchmarks"), HERE]

from chip import harness, schedule  # noqa: E402
from chip.runners import serve_chat  # noqa: E402

import program_reference  # noqa: E402

sys.modules["chip.reference.program_reference"] = program_reference

SEED = 2**33 + 19

MIX = {"runner": "serve_chat", "rate_per_s": 4.0, "arrival_cv": 2.0,
       "prompt_len": {"median": 12, "sigma": 0.8, "min": 4, "max": 40},
       "prompt_ladder": [4, 8, 16, 32],
       "output_len": {"median": 6, "sigma": 0.6, "min": 2, "max": 16},
       "temperature": 1.0, "greedy_share": 0.25, "check_requests": 6,
       "trace_seconds": 2}


def _lists(v):
    return [_lists(x) for x in v] if isinstance(v, (list, tuple)) else v


def tiny_config():
    """The configuration file of a reduced zamba2: the registry entry with
    the fields that ``get_reduced_config`` changes."""
    from repro.configs import get_config, get_reduced_config
    full = dataclasses.asdict(get_config("zamba2-2.7b"))
    red = dataclasses.asdict(get_reduced_config("zamba2-2.7b"))
    model = {k: _lists(v) for k, v in red.items() if full[k] != v}
    return {"name": "zamba2-tiny", "model": dict(arch="zamba2-2.7b", **model),
            "serve": {"max_batch": 4, "max_len": 64},
            "reference": "program_reference", "reference_rows": 2,
            "limits": {"logprob_gap": 0.01, "entropy_gap": 0.01,
                       "greedy_gap": 0.01}}


def tiny_cell():
    e2e = [{"name": n, "unit": u} for n, u in
           (("tbt_p50_ms", "ms"), ("tbt_p90_ms", "ms"), ("setup_s", "s"))]
    return harness.Cell("zamba2-chat-tiny", 1, "zamba2-tiny", tiny_config(),
                        "chat_tiny", copy.deepcopy(MIX), e2e, [])


def _execute(cell, seconds=3.0):
    from repro.launch import compile_cache
    spec = importlib.util.spec_from_file_location(
        "chipbench_run", os.path.join(ROOT, "benchmarks", "chip", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    args = types.SimpleNamespace(seed=SEED, seconds=seconds, trace=0,
                                 trace_dir=None, calibrate=0, sweep="")
    real = compile_cache.use_compile_cache
    compile_cache.use_compile_cache = lambda: None   # no cache on the CPU
    try:
        return run.execute(cell, args, jax.devices()[:1])
    finally:
        compile_cache.use_compile_cache = real


# -- the schedule ---------------------------------------------------------------

def test_same_seed_same_schedule():
    a = schedule.draw(MIX, SEED, 10.0, 512)
    b = schedule.draw(MIX, SEED, 10.0, 512)
    c = schedule.draw(MIX, SEED + 1, 10.0, 512)
    for k in ("arrive_s", "prompt_len", "output_len", "temperature", "keys"):
        np.testing.assert_array_equal(getattr(a, k), getattr(b, k))
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))
    # another seed: the same set of gaps and lengths in another order, and
    # other tokens, greedy requests and keys
    for k in ("prompt_len", "output_len"):
        np.testing.assert_array_equal(np.sort(getattr(a, k)),
                                      np.sort(getattr(c, k)))
        assert not np.array_equal(getattr(a, k), getattr(c, k))
    gaps = schedule.gamma_gaps(MIX["rate_per_s"], MIX["arrival_cv"], len(a))
    for s in (a, c):
        assert np.isin(np.round(np.diff(s.arrive_s), 9),
                       np.round(gaps, 9)).all()
    assert not np.array_equal(a.arrive_s, c.arrive_s)
    assert a.greedy.sum() == c.greedy.sum()
    assert not np.array_equal(a.greedy, c.greedy)
    assert not np.array_equal(a.keys, c.keys)
    assert not any(np.array_equal(x, y) for x, y in zip(a.prompts,
                                                         c.prompts))
    # shuffled, not sorted: the bursts fall through the window
    assert not np.all(np.diff(a.arrive_s, 2) >= 0)
    assert not np.all(np.diff(a.prompt_len) >= 0)
    assert len(a) == 40 and a.arrive_s[0] == 0.0 and a.arrive_s[-1] < 10.0
    assert int(a.greedy.sum()) == 10


def test_gamma_gaps_have_the_mix_cv():
    gaps = schedule.gamma_gaps(rate=5.0, cv=2.0, n=10_000)
    assert np.mean(gaps) == pytest.approx(0.2)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(2.0, rel=0.05)
    # and at a window's own number of requests: 16 to 40 in 10 s
    for n in (16, 20, 24, 30, 40):
        gaps = schedule.gamma_gaps(rate=n / 10.0, cv=2.0, n=n)
        assert np.sum(gaps) == pytest.approx(10.0)
        assert np.std(gaps) / np.mean(gaps) == pytest.approx(2.0, rel=0.01)
    s = schedule.draw(dict(MIX, rate_per_s=1000.0), SEED, 10.0, 512)
    gaps = np.diff(s.arrive_s)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(2.0, rel=0.05)


def test_lengths_are_clipped_to_the_mix():
    mix = dict(MIX, rate_per_s=500.0)
    mix.pop("prompt_ladder")
    s = schedule.draw(mix, SEED, 10.0, 512)
    for k, lens in (("prompt_len", s.prompt_len),
                    ("output_len", s.output_len)):
        assert lens.min() == mix[k]["min"] and lens.max() == mix[k]["max"]
        assert np.median(lens) == pytest.approx(mix[k]["median"], abs=1)
    assert [len(p) for p in s.prompts] == list(s.prompt_len)
    laddered = schedule.draw(dict(MIX, rate_per_s=500.0), SEED, 10.0, 512)
    assert set(laddered.prompt_len) == set(MIX["prompt_ladder"])
    assert schedule.prompt_lengths(MIX) == [4, 8, 16, 32]
    np.testing.assert_array_equal(
        schedule.on_ladder(np.array([1, 4, 5, 31, 33, 40]), [4, 8, 16, 32]),
        [4, 4, 8, 32, 32, 32])


def test_percentiles_match_numpy():
    rng = np.random.default_rng(3)
    times = [np.cumsum(rng.exponential(0.02, rng.integers(1, 30)))
             for _ in range(50)]
    recs = [serve_chat.Record(times=list(t)) for t in times]
    gaps = np.concatenate([np.diff(r.times) for r in recs])
    first = [r.times[0] for r in recs]
    assert serve_chat.percentile(gaps, 99) == np.percentile(gaps, 99)
    assert serve_chat.percentile(first, 90) == np.percentile(first, 90)
    assert serve_chat.percentile([], 90) is None


# -- reading the served tokens ----------------------------------------------------

def test_tap_keeps_each_token_as_the_session_returned_it():
    """One request through the server, read by the Tap: one entry per
    token, receipt times in order, and the tokens, log-probabilities and
    entropies that prefill_into and step give for the same request on a
    session of its own."""
    from repro.core.generate import DecodeSession
    from repro.launch.serve import Server
    cfg = serve_chat.model_config(tiny_config())
    params = program_reference.weights(jax.random.PRNGKey(4), None,
                                       tiny_config())
    server = Server(cfg, params, max_batch=4, max_len=64)
    tap = serve_chat.Tap(server)
    server.start()
    prompt = np.arange(1, 9, dtype=np.int32)
    key = np.array([3, 5], np.uint32)
    try:
        h = server.submit(prompt, max_tokens=7, temperature=1.0, key=key)
        out = h.result(timeout=300)
    finally:
        server.stop()
    rec = tap.records[h]
    assert len(rec.tokens) == len(rec.times) == len(rec.logprob) == 7
    assert list(out[8:]) == rec.tokens
    assert all(b > a for a, b in zip(rec.times, rec.times[1:]))
    sess = DecodeSession(params, cfg, max_batch=4, max_len=64)
    first = sess.prefill_into(0, prompt, key=key, temperature=1.0)
    want = [(int(first["token"]), float(first["logprob"]),
             float(first["entropy"]))]
    for _ in range(6):
        o = sess.step()
        want.append((int(o["token"][0]), float(o["logprob"][0]),
                     float(o["entropy"][0])))
    assert list(zip(rec.tokens, rec.logprob, rec.entropy)) == want


# -- the check --------------------------------------------------------------------

def test_sound_run_is_correct():
    out = _execute(tiny_cell())
    assert out["correct"], out
    assert out["attempted"] == 12 and out["failed"] == 0
    assert set(out["checks"]) == {"logprob_gap", "entropy_gap",
                                  "greedy_gap"}
    assert set(out["metrics"]) == {"tbt_p50_ms", "tbt_p90_ms", "setup_s"}


def test_check_reads_every_limit_and_inf_without_rows():
    """A number that no checked request gives reads inf; a mix that
    cannot send both kinds of request fails set-up."""
    row = {"prompt": np.arange(1, 5, dtype=np.int32),
           "tokens": np.array([5, 6], np.int32), "logprob": [0.0, 0.0],
           "entropy": [0.0, 0.0], "temperature": schedule.GREEDY_T,
           "greedy": True}

    def logits(toks):
        return jax.nn.one_hot(toks, 8) * 10.0     # the next token is best
    prog, _ = serve_chat.compare([row], logits, 2, 8)
    assert prog["logprob_gap"] == prog["entropy_gap"] == float("inf")
    assert set(prog) == {"logprob_gap", "entropy_gap", "greedy_gap"}
    for share in (0.0, 1.0):
        cell = tiny_cell()
        cell.traffic["greedy_share"] = share
        with pytest.raises(harness.SetupError, match="greedy"):
            _execute(cell)


def test_step_that_returns_its_state_unchanged_is_caught(monkeypatch):
    """The session's step gives its tokens but keeps the decode state it
    was given: every token after a request's second is made without the
    ones before it."""
    from repro.core import generate
    real = generate.DecodeSession.step

    def step(self):
        before = jax.tree.map(lambda x: x.copy(), self._state)
        out = real(self)
        self._state = before
        return out
    monkeypatch.setattr(generate.DecodeSession, "step", step)
    out = _execute(tiny_cell())
    assert not out["correct"], out["checks"]


def test_first_logprob_from_another_slot_is_caught(monkeypatch):
    """Each admission returns the first token's log-probability of the
    admission before it."""
    from repro.core import generate
    real = generate.DecodeSession.prefill_into
    last = [None]

    def prefill_into(self, slot, prompt, **kw):
        out = real(self, slot, prompt, **kw)
        mine, out["logprob"] = out["logprob"], (
            out["logprob"] if last[0] is None else last[0])
        last[0] = mine
        return out
    monkeypatch.setattr(generate.DecodeSession, "prefill_into",
                        prefill_into)
    out = _execute(tiny_cell())
    assert not out["correct"], out["checks"]
    assert out["checks"]["logprob_gap"][0] > 0.1


def test_calibration_reads_the_faults_and_the_control(capsys):
    """--calibrate: the program reads near 0 on every number; the two
    planted faults and the control each read above the limits."""
    import json
    from repro.launch import compile_cache
    args = types.SimpleNamespace(seed=SEED, seconds=2.0, trace=0,
                                 trace_dir=None, calibrate=1, sweep="")
    real = compile_cache.use_compile_cache
    compile_cache.use_compile_cache = lambda: None
    try:
        serve_chat.Run(tiny_cell(), args, jax.devices()[:1]).calibrate()
    finally:
        compile_cache.use_compile_cache = real
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    limits = tiny_config()["limits"]
    assert all(v <= limits[k] for k, v in line["program"].items())
    for name in ("control", "state_unchanged", "first_logprob_other_slot"):
        assert any(v > limits[k] for k, v in line[name].items()
                   if k in limits), (name, line[name])


def test_calibration_refuses_a_fault_never_planted():
    """A fault whose moment never comes (a request's 10**6th token) is no
    reading of the fault: the calibration stops."""
    from repro.launch import compile_cache
    args = types.SimpleNamespace(seed=SEED, seconds=1.0, trace=0,
                                 trace_dir=None, calibrate=1, sweep="")
    real = compile_cache.use_compile_cache
    compile_cache.use_compile_cache = lambda: None
    try:
        run = serve_chat.Run(tiny_cell(), args, jax.devices()[:1])
        run.setup()
        run._free_server()
        sched = schedule.draw(MIX, SEED, 1.0, run.model_cfg.vocab_size)
        with pytest.raises(RuntimeError, match="never planted"):
            run._serve_fresh(sched, 1.0, (1, ("state_unchanged", 10**6)))
    finally:
        compile_cache.use_compile_cache = real
