"""What every cell shares: finding a cell's files by name, the device gate,
compile accounting, per-layer metric readers and the result line."""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class SetupError(SystemExit):
    """A cell that cannot run here: exits nonzero and prints no result."""


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, deleted after reading)")
    ap.add_argument("--calibrate", type=int, default=0,
                    help="instead of a run: read the compared numbers of "
                         "the program, the control and the faults on this "
                         "many seeds from --seed on")
    ap.add_argument("--sweep", default="",
                    help="instead of a run, for a serving mix: offer each "
                         "of these comma-separated rates (requests/s) for "
                         "--seconds and report whether the server keeps "
                         "pace")
    return ap.parse_args(argv)


def load_benchmark(root=ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise SetupError(f"no {path}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file's contents
    traffic_name: str
    traffic: dict         # the mix file's contents
    end_to_end: list      # BENCHMARK.json metric entries this cell reports
    per_layer: list


def _applies(metric: dict, cell: str, e2e_names=None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def resolve_cell(name: str, bench: dict | None = None,
                 root: str = ROOT) -> Cell:
    """The cell ``name`` with its configuration, mix and metrics, each
    found by the name BENCHMARK.json gives it."""
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    with open(traffic_path(w["traffic"], root)) as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    return Cell(name, int(w["chips"]), w["config"], config, w["traffic"],
                traffic, e2e, per_layer)


def bench_dir(root: str = ROOT) -> str:
    return os.path.join(root, "benchmarks", "chip")


def traffic_path(name: str, root: str = ROOT) -> str:
    return os.path.join(bench_dir(root), "traffic", f"{name}.json")


def metric_path(name: str, root: str = ROOT) -> str:
    return os.path.join(bench_dir(root), "metrics", f"{name}.py")


def metric_reader(name: str, root: str = ROOT):
    """metrics/<name>.py's ``read(ctx)``."""
    path = metric_path(name, root)
    spec = importlib.util.spec_from_file_location(
        "chip_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer_metrics(cell: Cell, ctx: dict) -> dict:
    """The cell's per-layer metrics, each read by its reader from ``ctx``.
    A metric that no reading gives is left out, unless its ``workloads``
    lists this cell: then the run fails, naming the programs traced."""
    out = {}
    for m in cell.per_layer:
        value = metric_reader(m["name"])(ctx)
        if finite(value):
            out[m["name"]] = {"value": value, "unit": m["unit"]}
        elif cell.name in m.get("workloads", ()):
            raise SetupError(
                f"metric {m['name']} found nothing to read in {cell.name}, "
                f"which lists it; programs traced: "
                f"{sorted(ctx['trace']['programs'])}")
    return out


def client_options(options: dict) -> None:
    """Options of the accelerator's runtime client, set before JAX starts
    it; a mix names them, e.g. ``max_inflight_computations``, how many
    programs the host may have sent ahead of the device."""
    if options:
        import jax
        jax.config.update("jax_pjrt_client_create_options", dict(options))


def device_gate(chips: int):
    """The devices of the run; refuses a host without enough TPU chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SetupError(f"no TPU: JAX runs on {devices[0].platform}")
    if len(devices) < chips:
        raise SetupError(f"{chips} chips needed, {len(devices)} visible")
    return devices[:chips]


def use_compile_cache() -> str:
    """The program's persistent compilation cache (a fixed directory in the
    checkout, or JAX_COMPILATION_CACHE_DIR), holding every program however
    quick its compile, so that a warm set-up compiles nothing."""
    import jax
    from repro.launch.compile_cache import use_compile_cache as program_cache
    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileClock:
    """Sums XLA backend compile time and persistent-cache hits."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def summary(self) -> str:
        return (f"compile {self.seconds:.1f}s in {self.compiles} programs, "
                f"{self.cache_hits} persistent-cache hits")


def abstract(tree):
    """The shapes, types and placements of a call's arguments, as
    ``jax.jit(f).lower`` needs them to give the program that the call ran
    (an array that the program did not place on a device is uncommitted,
    and so is its stand-in)."""
    import jax
    import numpy as np
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        np.shape(x), x.dtype, sharding=x.sharding
        if getattr(x, "_committed", False) else None), tree)


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def finite(x) -> bool:
    return x is not None and math.isfinite(x)


def result_line(*, correct, attempted, failed, metrics, device, checks,
                breakdown=None) -> str:
    """The last stdout line; ``checks`` ({name: (value, limit)}) comes last
    and is printed on stderr too, one check per line, as the last lines."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    # a number that could not be read is inf, and JSON has no inf: null
    out["checks"] = {k: {"value": v if finite(v) else None, "limit": lim}
                     for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v!r} limit {lim!r}")
    return json.dumps(out)
