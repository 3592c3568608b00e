"""The program's own spans, read off one traced window.

The program names its layers for the profiler (README "Tracing"):
device scopes (``learner_forward``, ``loss``, ``optimizer`` in the
learner step; ``rollout`` and inside it ``actor_forward`` and
``env_step`` in the unroll), which reach each compiled instruction as its
``op_name``, and ``Runtime`` host spans (``train`` per step around
``source.next_batch``, ``learner.dispatch``, ``runtime.callbacks``,
``runtime.log``, ``runtime.checkpoint``) on the device trace's clock.
From one ``.xplane.pb`` this module gives:

- per program, device self-seconds per innermost scope: each op's
  duration less what the ops nested inside it cover (a ``while`` and the
  ops of its body are events of their own), the backward pass told apart
  by ``transpose(``;
- the host spans of the Runtime's thread;
- a clock check: the window's ``jit_train_step`` executions, matched from
  the last with the ``learner.dispatch`` spans that sent them, each start
  after their span does, else the host readings are refused;
- the device's idle gaps, each labelled by the innermost host span open
  at its midpoint.

A TPU v5e trace's op events carry no ``op_name`` (their stats hold only
times), so it comes from the compiled HLO text of each program
(``op_names_from_hlo``). ``reduce_spans`` works on plain tuples, so a
small recorded list (tests/data) checks it on the CPU. Run on a trace
kept by ``run.py --trace 1 --trace-dir DIR``:

    python3 benchmarks/chip/spans.py DIR jit_train_step=HLO_TEXT_FILE \
        jit_unroll=HLO_TEXT_FILE
"""

from __future__ import annotations

import argparse
import bisect
import collections
import glob
import json
import os
import re
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chip import devtrace  # noqa: E402

LEARNER, UNROLL = "jit_train_step", "jit_unroll"
SCOPES = {LEARNER: ("learner_forward", "loss", "optimizer"),
          UNROLL: ("actor_forward", "env_step", "rollout")}
STEP, DISPATCH, CALLBACKS = "train", "learner.dispatch", "runtime.callbacks"
HOST_SPANS = (STEP, "source.next_batch", DISPATCH, CALLBACKS, "runtime.log",
              "runtime.checkpoint")
FORWARD, BACKWARD = 0, 1

# metric -> (program, scope, passes); each reads milliseconds per call
LAYER_MS = {
    "learner_forward_ms.train": (LEARNER, "learner_forward", (FORWARD,)),
    "learner_backward_ms.train": (LEARNER, "learner_forward", (BACKWARD,)),
    "loss_ms.train": (LEARNER, "loss", (FORWARD, BACKWARD)),
    "optimizer_ms.train": (LEARNER, "optimizer", (FORWARD, BACKWARD)),
    "actor_forward_ms.train": (UNROLL, "actor_forward", (FORWARD, BACKWARD)),
    "env_ms.train": (UNROLL, "env_step", (FORWARD, BACKWARD)),
}

_HLO_OP = re.compile(r'^\s*(?:ROOT )?%([^\s=]+) = .*?metadata=\{op_name="'
                     r'([^"]*)"', re.M)
_TOKEN = re.compile(r"[\w.\-]+")


# -- reading a trace ---------------------------------------------------------

def read_xplane(path: str) -> dict:
    """{"device": devtrace's (plane, line, name, start_ns, duration_ns)
    events, "host": [(thread, name, start_ns, duration_ns)] of the host
    planes' spans in HOST_SPANS}, ``thread`` being '<plane>#<line>'."""
    from jax.profiler import ProfileData
    device = devtrace.events_from_xplane(path)   # raises if there is none
    host, wanted = [], set(HOST_SPANS)
    if os.path.isdir(path):      # the newest trace written under it
        path = max(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            host.extend((f"{plane.name}#{i}", ev.name, int(ev.start_ns),
                         int(ev.duration_ns))
                        for ev in line.events if ev.name in wanted)
    return {"device": device, "host": host}


def op_names_from_hlo(text: str) -> dict:
    """{instruction: op_name} from a compiled program's HLO text
    (``jitted.lower(...).compile().as_text()``)."""
    return dict(_HLO_OP.findall(text))


# -- device scopes -----------------------------------------------------------

def scope_of(op_name: str, scopes) -> tuple:
    """(innermost of ``scopes`` in ``op_name``, FORWARD or BACKWARD), or
    ('', FORWARD) where none is. 'jit(f)/transpose(jvp(loss))/mul' ->
    ('loss', BACKWARD)."""
    for part in reversed(op_name.split("/")):
        for token in reversed(_TOKEN.findall(part)):
            if token in scopes:
                return token, BACKWARD if "transpose(" in part else FORWARD
    return "", FORWARD


def self_times(intervals) -> list:
    """Per [start, end) interval, its length less what the intervals
    nested directly inside it cover."""
    order = sorted(range(len(intervals)),
                   key=lambda k: (intervals[k][0], -intervals[k][1]))
    own = [e - s for s, e in intervals]
    stack = []
    for k in order:
        s, e = intervals[k]
        while stack and intervals[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            own[p] -= min(e, intervals[p][1]) - s
        stack.append(k)
    return own


def _device_scopes(events, op_names) -> dict:
    """{program: {"calls", "device_s", "self_s": {scope: [fwd, bwd]}}},
    averaged over the device planes; scope '' holds unscoped ops."""
    planes = sorted({e[0] for e in events})
    out = {}
    for plane in planes:
        mods = sorted((s, s + d, devtrace.program_name(name))
                      for p, line, name, s, d in events
                      if p == plane and line == devtrace.MODULES)
        starts = [m[0] for m in mods]
        ops = [e for e in events if e[0] == plane and e[1] == devtrace.OPS]
        own = self_times([(s, s + d) for _, _, _, s, d in ops])
        for s, e, prog in mods:
            p = out.setdefault(prog, {"calls": 0.0, "device_s": 0.0,
                                      "self_s": {}})
            p["calls"] += 1 / len(planes)
            p["device_s"] += (e - s) * 1e-9 / len(planes)
        for (_, _, name, s, _), t in zip(ops, own):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= mods[i][1]:
                continue
            prog = mods[i][2]
            op_name = op_names.get(prog, {}).get(devtrace.op_name(name), "")
            scope, way = scope_of(op_name, SCOPES.get(prog, ()))
            acc = out[prog]["self_s"].setdefault(scope, [0.0, 0.0])
            acc[way] += t * 1e-9 / len(planes)
    return out


# -- host spans and the clock ------------------------------------------------

def _runtime_thread(host) -> list:
    """The spans of the thread that ran the Runtime's steps, by start."""
    steps = collections.Counter(t for t, name, _, _ in host if name == STEP)
    if not steps:
        return []
    thread = steps.most_common(1)[0][0]
    return sorted((s, s + d, name) for t, name, s, d in host if t == thread)


def _host_step_ms(spans):
    """Mean over the ``train`` spans of the span less its callbacks."""
    steps = [(s, e) for s, e, name in spans if name == STEP]
    if not steps:
        return None
    cb = [(s, e) for s, e, name in spans if name == CALLBACKS]
    total = 0.0
    for s, e in steps:
        total += (e - s) - sum(ce - cs for cs, ce in cb if s <= cs and ce <= e)
    return 1e-6 * total / len(steps)


def clock_check(events, spans, program=LEARNER) -> dict:
    """Each device plane's executions of ``program``, matched from the last
    with the ``learner.dispatch`` spans in order: every one has to start
    after its span. ``lead_ms``: the least start after its span."""
    sent = sorted(s for s, _, name in spans if name == DISPATCH)
    matched, lead = 0, None
    for plane in sorted({e[0] for e in events}):
        runs = sorted(s for p, line, name, s, _ in events
                      if p == plane and line == devtrace.MODULES
                      and devtrace.program_name(name) == program)
        n = min(len(runs), len(sent))
        for run, send in zip(runs[len(runs) - n:], sent[len(sent) - n:]):
            lead = (run - send) if lead is None else min(lead, run - send)
        matched += n
    ok = matched > 0 and lead >= 0
    return {"matched": matched, "ok": ok,
            "lead_ms": None if lead is None else lead * 1e-6}


def idle_gaps_host(events, spans, top: int = 10) -> list:
    """The device's idle gaps between programs, as devtrace finds them,
    each labelled by the innermost span open at its midpoint ('none')."""
    planes = sorted({e[0] for e in events})
    gaps = collections.Counter()
    for plane in planes:
        mods = [(s, s + d) for p, line, _, s, d in events
                if p == plane and line == devtrace.MODULES]
        _, merged = devtrace._union(mods)
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            mid = (e0 + s1) / 2
            label = max(((s, -e, name) for s, e, name in spans
                         if s <= mid < e), default=(0, 0, "none"))[2]
            gaps[label] += (s1 - e0) * 1e-9 / len(planes)
    return [[k, v] for k, v in gaps.most_common(top)]


# -- the whole reduction -------------------------------------------------------

def reduce_spans(trace: dict, op_names=None) -> dict:
    """The scopes, host spans, clock check and host-labelled idle gaps of
    one window (``trace`` as ``read_xplane`` gives it; ``op_names``:
    {program: {instruction: op_name}}, from ``op_names_from_hlo``)."""
    events, spans = trace["device"], _runtime_thread(trace["host"])
    clock = clock_check(events, spans)
    host = collections.defaultdict(lambda: [0, 0.0])
    for s, e, name in spans:
        host[name][0] += 1
        host[name][1] += (e - s) * 1e-9
    return {
        "scopes": _device_scopes(events, op_names or {}),
        "host_spans": dict(host),
        "clock": clock,
        "host_step_ms": _host_step_ms(spans) if clock["ok"] else None,
        "idle_gaps_host": idle_gaps_host(events, spans) if clock["ok"]
        else [],
    }


def layer_ms(reduced: dict) -> dict:
    """The per-layer readings: LAYER_MS's device milliseconds per call
    and ``host_step_ms.train``; None where the window holds nothing."""
    out = {}
    for metric, (prog, scope, ways) in LAYER_MS.items():
        p = reduced["scopes"].get(prog)
        got = p and p["calls"] and p["self_s"].get(scope)
        out[metric] = (1e3 * sum(got[w] for w in ways) / p["calls"]
                       if got and any(got[w] for w in ways) else None)
    out["host_step_ms.train"] = reduced["host_step_ms"]
    return out


def coverage(reduced: dict, program: str):
    """Share of ``program``'s device time under its named scopes."""
    p = reduced["scopes"].get(program)
    if not p or not p["device_s"]:
        return None
    scoped = sum(sum(v) for k, v in p["self_s"].items() if k)
    return scoped / p["device_s"]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a trace directory or .xplane.pb file")
    ap.add_argument("hlo", nargs="*", metavar="PROGRAM=HLO_TEXT_FILE",
                    help="a program's compiled HLO text, which names "
                         "the scope of each of its instructions")
    args = ap.parse_args(argv)
    op_names = {}
    for item in args.hlo:
        prog, path = item.split("=", 1)
        with open(path) as f:
            op_names[prog] = op_names_from_hlo(f.read())
    trace = read_xplane(args.trace)
    reduced = reduce_spans(trace, op_names)
    print(json.dumps(dict(
        layer_ms(reduced),
        coverage={p: coverage(reduced, p) for p in SCOPES},
        **reduced), indent=1))


if __name__ == "__main__":
    main()
