"""Run one benchmark cell on the chips of this host.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (BENCHMARK.json ``workloads``) names a configuration file and a
traffic mix; the mix names the runner (runners/<runner>.py) that builds
the system under test, warms up every shape the cell uses, measures for
``--seconds`` and checks what the timed path produced against a plain
reference. ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` traces the window and reports its per-layer metrics, each
read by metrics/<name>.py. The last stdout line is one JSON object.
Without enough TPU chips it exits nonzero and prints no result.
"""

import time

T_START = time.monotonic()

import gc  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.dirname(HERE)]

from chip import devtrace, flops, harness  # noqa: E402
from chip.harness import log  # noqa: E402


class Tracer:
    """Profiler trace around the window: start() at open, stop() at close."""

    def __init__(self, keep_dir=None):
        self.dir = keep_dir or tempfile.mkdtemp(prefix="chipbench-trace-")
        self.keep = keep_dir is not None
        self.t0 = self.window_s = None

    def start(self):
        import jax
        jax.profiler.start_trace(self.dir)
        self.t0 = time.perf_counter()

    def stop(self):
        import jax
        self.window_s = time.perf_counter() - self.t0
        jax.profiler.stop_trace()

    def reduce(self):
        events = devtrace.events_from_xplane(self.dir)
        if self.keep:
            devtrace.save_events(events, os.path.join(self.dir,
                                                      "events.json"))
        else:
            shutil.rmtree(self.dir, ignore_errors=True)
        return devtrace.reduce_events(events, self.window_s)


def execute(cell, args, devices) -> dict:
    """Set up, measure and check one cell on ``devices``; the result line's
    object. Everything of a run but the look for chips."""
    cache_dir = harness.use_compile_cache()
    clock = harness.CompileClock()
    runner = importlib.import_module(f"chip.runners.{cell.traffic['runner']}")
    run = runner.Run(cell, args, devices)
    run.setup()
    setup_s = time.monotonic() - T_START
    log(f"set-up {setup_s:.1f}s ({clock.summary()}; cache {cache_dir})")
    compiles_before = clock.compiles
    tracer = None
    seconds = args.seconds
    if args.trace:
        tracer = Tracer(args.trace_dir)
        seconds = min(seconds, float(cell.traffic["trace_seconds"]))
    run.window(seconds, tracer)
    if clock.compiles != compiles_before:
        log(f"warning: {clock.compiles - compiles_before} programs compiled "
            "inside the window")
    peak = harness.memory_peak_bytes(devices)
    reduced = tracer.reduce() if tracer else None
    checks = run.check()

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    metrics, breakdown = {}, None
    if not args.trace:
        values = dict(run.e2e, setup_s=setup_s)
        for m in cell.end_to_end:
            if harness.finite(values.get(m["name"])):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
        ctx = {"trace": reduced, "counters": run.counters,
               "peaks": flops.peaks(devices[0].device_kind),
               "chips": len(devices), "config": cell.config,
               "traffic": cell.traffic}
        metrics = harness.per_layer_metrics(cell, ctx)
    ok = run.failed == 0 and all(v <= lim for v, lim in checks.values())
    out = dict(correct=ok, attempted=run.attempted, failed=run.failed,
               metrics=metrics, device=device, checks=checks,
               breakdown=breakdown)
    del run
    gc.collect()
    return out


def main(argv=None):
    args = harness.parse_args(argv)
    cell = harness.resolve_cell(args.workload)
    harness.client_options(cell.traffic.get("pjrt_client_options", {}))
    devices = harness.device_gate(cell.chips)
    from repro.kernels.compat import resolve_interpret
    log(f"jax ready {time.monotonic() - T_START:.1f}s")
    if args.calibrate:
        harness.use_compile_cache()
        runner = importlib.import_module(
            f"chip.runners.{cell.traffic['runner']}")
        runner.Run(cell, args, devices).calibrate()
        return
    result = execute(cell, args, devices)
    stats = resolve_interpret.stats()
    if stats["fallbacks"]:
        raise harness.SetupError(f"kernels ran in interpret mode: {stats}")
    log(f"total {time.monotonic() - T_START:.1f}s")
    print(harness.result_line(**result))


if __name__ == "__main__":
    try:
        main()
    except harness.SetupError as e:
        print(f"refused: {e.code}", file=sys.stderr)
        sys.exit(2)
