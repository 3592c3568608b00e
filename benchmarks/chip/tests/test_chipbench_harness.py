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
    # a metric's cells, where listed, are named cells, and at least one
    cells = {w["name"] for w in b["workloads"]}
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            assert m["workloads"] and set(m["workloads"]) <= cells, m
    assert len(json.dumps(b)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_every_cell_resolves_by_name(cell):
    c = harness.resolve_cell(cell)
    runner = os.path.join(ROOT, "benchmarks", "chip", "runners",
                          c.traffic["runner"] + ".py")
    assert os.path.exists(runner)
    assert "\nclass Run:" in open(runner).read()
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


# The serving metrics as the first serving cell's PR adds them to
# BENCHMARK.json (a metric that no cell reports may not stand there); the
# runner and the three readers are in place. The bounds here are the
# stand-in's; that cell sets its own from its runs.
SERVING_E2E = [
    {"name": "tbt_p50_ms", "unit": "ms", "better": "lower", "bound": 0.036,
     "source": "host_clock"},
    {"name": "tbt_p90_ms", "unit": "ms", "better": "lower", "bound": 0.034,
     "source": "host_clock"}]
SERVING_PER_LAYER = [
    {"name": "decode_ms.chat", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "decode session",
     "moves": "tbt_p50_ms"},
    {"name": "admit_ms.chat", "unit": "ms", "better": "lower",
     "source": "device_trace", "layer": "decode session",
     "moves": "tbt_p90_ms"},
    {"name": "idle_share.chat", "unit": "%", "better": "lower",
     "source": "device_trace", "layer": "device", "moves": "tbt_p50_ms"}]


def test_serving_cell_is_found_from_data_files_alone(tmp_path):
    """A serving cell added as a configuration, a chat mix and entries in
    BENCHMARK.json only: it names the serve_chat runner, reports the
    serving end-to-end metrics and the three chat readers, and every
    existing file is left as it was."""
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(os.path.join(ROOT, "benchmarks", "chip"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    b = _bench()
    cfg = {"name": "zamba2-chat-model", "source": "https://example.org/cfg",
           "model": {"arch": "zamba2-2.7b"}, "serve": {"max_batch": 16,
                                                      "max_len": 1024},
           "reference": "zamba2_chat_model", "limits": {}, "reduced": []}
    (bench_dir / "configs" / "zamba2-chat-model.json").write_text(
        json.dumps(cfg))
    mix = {"runner": "serve_chat", "rate_per_s": 8.0, "arrival_cv": 2.0,
           "prompt_len": {"median": 256, "sigma": 0.8, "min": 32,
                          "max": 896},
           "output_len": {"median": 64, "sigma": 0.6, "min": 8, "max": 256},
           "check_requests": 16, "trace_seconds": 5}
    (bench_dir / "traffic" / "chat.json").write_text(json.dumps(mix))
    b["configs"].append({"name": "zamba2-chat-model",
                         "source": cfg["source"],
                         "file": "benchmarks/chip/configs/"
                                 "zamba2-chat-model.json",
                         "reduced": [], "why": "a serving model"})
    b["workloads"].append({"name": "zamba2-chat", "config":
                           "zamba2-chat-model", "traffic": "chat",
                           "chips": 1, "why": "open-loop chat"})
    chat = [m["name"] for m in SERVING_E2E + SERVING_PER_LAYER]
    assert not set(chat) & {m["name"] for m in b["end_to_end"]
                            + b["per_layer"]}
    b["end_to_end"] += [dict(m, workloads=["zamba2-chat"])
                        for m in SERVING_E2E]
    b["per_layer"] += [dict(m, workloads=["zamba2-chat"])
                       for m in SERVING_PER_LAYER]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    c = harness.resolve_cell("zamba2-chat", root=str(tmp_path))
    assert c.traffic["runner"] == "serve_chat"
    assert os.path.exists(bench_dir / "runners" / "serve_chat.py")
    assert c.config["reference"] == "zamba2_chat_model"
    reported = {m["name"] for m in c.end_to_end}
    assert reported == {"setup_s", "tbt_p50_ms", "tbt_p90_ms"}
    assert {m["name"] for m in c.per_layer} == {"decode_ms.chat",
                                               "admit_ms.chat",
                                               "idle_share.chat"}
    for m in c.per_layer:
        assert m["moves"] in reported
        assert callable(harness.metric_reader(m["name"], str(tmp_path)))
    # impala-atari reports none of them
    i = harness.resolve_cell("impala-atari", root=str(tmp_path))
    assert not {m["name"] for m in i.end_to_end + i.per_layer} & set(chat)
    for p, data in before.items():
        assert p.read_bytes() == data, p


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


# every reader file, listed in BENCHMARK.json or waiting for its first cell
READERS = sorted(f[:-len(".py")] for f in os.listdir(
    os.path.join(ROOT, "benchmarks", "chip", "metrics")) if f.endswith(".py"))


@pytest.mark.parametrize("metric", READERS)
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
