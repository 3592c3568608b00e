"""Run one benchmark cell on the chips of this host.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (BENCHMARK.json ``workloads``) names a configuration file and a
traffic mix; the mix names the runner (runners/<runner>.py) that builds
the system under test, warms up every shape the cell uses, measures for
``--seconds`` and checks what the timed path produced against a plain
reference. ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` traces the window and reports its per-layer metrics, each
read by metrics/<name>.py, which find in their context the device
trace's reduction and, where the runner gives its programs' compiled
text (``Run.hlo_texts()``), the program's spans (spans.py). The last
stdout line is one JSON object. Without enough TPU chips it exits
nonzero and prints no result.

``--calibrate N`` reads the compared numbers of the program, the control
and the planted faults on N seeds instead of a run; ``--sweep R1,R2,..``
offers a serving mix at each rate for ``--seconds`` and reports whether
the server keeps pace.
"""

import time

T_START = time.monotonic()

import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.dirname(HERE)]

from chip import devtrace, flops, harness, spans  # noqa: E402
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

    def reduce(self, hlo_texts=None):
        """(devtrace's reduction, ``spans.reduce_spans`` of the trace or
        None without ``hlo_texts``, the number of device events)."""
        trace = spans.read_xplane(self.dir)
        if self.keep:
            devtrace.save_events(trace["device"],
                                 os.path.join(self.dir, "events.json"))
        else:
            shutil.rmtree(self.dir, ignore_errors=True)
        op_names = {p: spans.op_names_from_hlo(t)
                    for p, t in (hlo_texts or {}).items()}
        return (devtrace.reduce_events(trace["device"], self.window_s),
                spans.reduce_spans(trace, op_names) if op_names else None,
                len(trace["device"]))


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
    if tracer:
        t0 = time.monotonic()
        texts = run.hlo_texts() if hasattr(run, "hlo_texts") else None
        reduced, span_reduced, n_events = tracer.reduce(texts)
        log(f"trace read in {time.monotonic() - t0:.1f}s "
            f"({n_events} device events; "
            f"{clock.summary()})")
        if span_reduced is not None:
            log(f"spans: {json.dumps(spans.layer_ms(span_reduced))}; "
                f"clock {json.dumps(span_reduced['clock'])}")
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
        if span_reduced is not None:
            breakdown["idle_gaps_host"] = span_reduced["idle_gaps_host"]
        # "spans": the program's scopes and host spans (spans.reduce_spans),
        # where the runner names its programs' compiled text
        ctx = {"trace": reduced, "counters": run.counters,
               "peaks": flops.peaks(devices[0].device_kind),
               "chips": len(devices), "config": cell.config,
               "traffic": cell.traffic, "spans": span_reduced}
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
    if args.calibrate or args.sweep:
        harness.use_compile_cache()
        runner = importlib.import_module(
            f"chip.runners.{cell.traffic['runner']}")
        run = runner.Run(cell, args, devices)
        if args.sweep:
            run.sweep([float(r) for r in args.sweep.split(",")])
        else:
            run.calibrate()
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
