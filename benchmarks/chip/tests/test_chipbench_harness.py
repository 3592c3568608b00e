"""BENCHMARK.json resolves every cell's files by name, keeps the contract's
shape, and run.py refuses to run without a TPU."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, "..", "..", ".."))
sys.path[:0] = [os.path.join(ROOT, "benchmarks")]

from chip import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_contract_shape():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmarks/chip"]
    assert b["command"][1].startswith("benchmarks/chip/")
    assert 1 <= b["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert "setup_s" in {m["name"] for m in b["end_to_end"]}
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 2)
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
    assert len(json.dumps(b)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_every_cell_resolves_by_name(cell):
    c = harness.resolve_cell(cell)
    assert c.traffic["runner"] == "rl_train"
    assert os.path.exists(os.path.join(ROOT, "benchmarks", "chip", "runners",
                                       c.traffic["runner"] + ".py"))
    assert c.config["name"] == c.config_name
    reported = {m["name"] for m in c.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in reported
        assert callable(harness.metric_reader(m["name"]))


def test_config_files_are_distinct_and_listed():
    b = _bench()
    files = [c["file"] for c in b["configs"]]
    assert len(files) == len(set(files))
    for c in b["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            data = json.load(f)
        assert data["reduced"] == c["reduced"]
        assert data["source"] == c["source"]


def test_new_cell_is_found_from_data_files_alone(tmp_path):
    """A cell, configuration, mix and metric added as files and entries
    only: the harness finds each by its name."""
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(os.path.join(ROOT, "benchmarks", "chip"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = _bench()
    cfg = json.load(open(os.path.join(ROOT, b["configs"][0]["file"])))
    cfg["name"] = "impala-deep-atari-b64"
    (bench_dir / "configs" / "impala-deep-atari-b64.json").write_text(
        json.dumps(cfg))
    mix = json.load(open(bench_dir / "traffic" / "atari_unroll.json"))
    mix["batch_per_chip"] = 64
    (bench_dir / "traffic" / "atari_unroll_b64.json").write_text(
        json.dumps(mix))
    (bench_dir / "metrics" / "learner_ms.b64.py").write_text(
        "def read(ctx):\n    return 1.5\n")
    b["configs"].append(dict(b["configs"][0], name="impala-deep-atari-b64",
                             file="benchmarks/chip/configs/"
                                  "impala-deep-atari-b64.json"))
    b["workloads"].append({"name": "impala-atari-b64",
                           "config": "impala-deep-atari-b64",
                           "traffic": "atari_unroll_b64", "chips": 1,
                           "why": "a larger batch"})
    b["end_to_end"][0]["workloads"].append("impala-atari-b64")
    b["per_layer"].append({"name": "learner_ms.b64", "unit": "ms",
                           "better": "lower", "source": "device_trace",
                           "layer": "learner", "moves": "frames_per_s",
                           "workloads": ["impala-atari-b64"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    c = harness.resolve_cell("impala-atari-b64", root=str(tmp_path))
    assert c.traffic["batch_per_chip"] == 64
    assert c.config["name"] == "impala-deep-atari-b64"
    assert [m["name"] for m in c.per_layer] == ["learner_ms.b64"]
    assert harness.metric_reader("learner_ms.b64", str(tmp_path))({}) == 1.5
    with pytest.raises(SystemExit):
        harness.resolve_cell("no-such-cell", root=str(tmp_path))


def _run_py(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "benchmarks/chip/run.py"] + args,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    out = _run_py(["--workload", "impala-atari", "--seed", str(2**33 + 5),
                   "--seconds", "1", "--trace", "0"], ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_run_exits_nonzero_in_a_bare_checkout(tmp_path):
    """A directory holding only BENCHMARK.json and benchmarks/chip: the
    program is missing, so no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks", "chip"),
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_py(["--workload", "impala-atari", "--seed", "7",
                   "--seconds", "1", "--trace", "0"], str(tmp_path))
    assert out.returncode != 0
    assert out.stdout.strip() == ""


@pytest.mark.parametrize("metric", [m["name"] for m in _bench()["per_layer"]])
def test_reader_of_an_empty_trace_reads_nothing(metric):
    """A reader that finds nothing returns None (never a 0 share)."""
    from chip import devtrace
    ctx = {"trace": devtrace.reduce_events([], 1.0), "counters": {},
           "peaks": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
           "chips": 1, "config": {}, "traffic": {}}
    assert harness.metric_reader(metric)(ctx) is None


def test_listed_metric_that_reads_nothing_fails_the_run():
    """A trace without the program a listed metric reads: no result line,
    a nonzero exit naming the programs that were traced."""
    from chip import devtrace
    cell = harness.resolve_cell("impala-atari")
    events = [("/device:TPU:0", devtrace.MODULES, "jit_other(3)", 0, 10),
              ("/device:TPU:0", devtrace.OPS, "fusion.1", 0, 10)]
    ctx = {"trace": devtrace.reduce_events(events, 1.0), "counters": {},
           "peaks": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
           "chips": 1, "config": cell.config, "traffic": cell.traffic}
    with pytest.raises(SystemExit, match="jit_other"):
        harness.per_layer_metrics(cell, ctx)


def test_synth_env_carry_can_be_donated():
    """The source donates its carry on a TPU: the env's state and its
    observation must be buffers of their own, or the second unroll is
    refused for donating one buffer twice."""
    import jax
    from chip import weights
    from chip.envs import atari_synth
    from repro.core.sources import DeviceSource
    from repro.models.convnet import impala_deep

    shape = dict(obs_shape=(16, 16, 4), num_actions=18, channels=(4, 4, 4),
                 fc=8)
    _, apply_fn = impala_deep(shape["obs_shape"], 18,
                              channels=shape["channels"], fc=shape["fc"])
    params = weights.impala_params(jax.random.PRNGKey(0), **shape)
    source = DeviceSource.for_env(
        atari_synth.make(obs_shape=(16, 16, 4), sprite_half=1), apply_fn,
        unroll_length=3, batch_size=2, key=jax.random.PRNGKey(1),
        pipelined=True, donate=True)
    for _ in range(3):
        batch = source.next_batch(params)
    obs = batch["obs"]
    assert obs.dtype == jax.numpy.float32 and obs.shape == (4, 2, 16, 16, 4)
    assert float(obs.min()) >= 0.0 and float(obs.max()) <= 1.0
