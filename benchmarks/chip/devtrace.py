"""Reduction of a profiler trace to device busy time, per-program device
time, collective time and the largest ops and idle gaps.

``events_from_xplane`` reads the ``.xplane.pb`` that ``jax.profiler``
writes and keeps, for each TPU core plane, the events of the "XLA Ops"
and "XLA Modules" lines as plain tuples; ``reduce_events`` works on those
tuples alone, so a small recorded list (tests/data) checks it on the CPU.
"""

from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import re

OPS, MODULES = "XLA Ops", "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)+$")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all", re.I)


def program_name(name: str) -> str:
    """'jit_train_step(1234)' -> 'jit_train_step'."""
    return _SUFFIX.sub("", name.strip())


def op_name(name: str) -> str:
    """'%fusion.12 = f32[8]{0} fusion(...)' -> 'fusion.12' (a TPU op event
    carries its whole HLO instruction)."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def events_from_xplane(path: str) -> list:
    """[(plane, line, name, start_ns, duration_ns)] of the device planes."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):      # the newest trace written under it
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise FileNotFoundError(f"no xplane file in {path}")
        path = max(found, key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name not in (OPS, MODULES):
                continue
            for ev in line.events:
                out.append((plane.name, line.name, ev.name,
                            int(ev.start_ns), int(ev.duration_ns)))
    return out


def save_events(events, path):
    with open(path, "w") as f:
        json.dump([list(e) for e in events], f)


def load_events(path):
    with open(path) as f:
        return [tuple(e) for e in json.load(f)]


def _union(intervals):
    """Total length and the merged list of [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def reduce_events(events, window_s: float, top: int = 10) -> dict:
    """Per-chip averages over the device planes in ``events``.

    busy_s: union of op intervals. programs: {name: {calls, device_s}}
    from the module line. collective_s: summed collective op time.
    device_ops / idle_gaps: the ``top`` largest, as [name, seconds]; an op
    is named "<program>/<instruction>", a gap by the programs on either
    side of it.
    """
    planes = sorted({e[0] for e in events})
    if not planes:
        return {"chips": 0, "busy_s": 0.0, "window_s": window_s,
                "programs": {}, "collective_s": 0.0, "device_ops": [],
                "idle_gaps": []}
    n = len(planes)
    busy = 0.0
    programs = collections.defaultdict(lambda: [0, 0.0])
    op_time = collections.Counter()
    gap_time = collections.Counter()
    collective = 0.0
    for plane in planes:
        ops = [(s, s + d) for p, line, _, s, d in events
               if p == plane and line == OPS]
        total, _ = _union(ops)
        busy += total * 1e-9
        mods = sorted((s, s + d, program_name(name))
                      for p, line, name, s, d in events
                      if p == plane and line == MODULES)
        for s, e, name in mods:
            programs[name][0] += 1
            programs[name][1] += (e - s) * 1e-9
        _, merged = _union([(s, e) for s, e, _ in mods])
        for (s0, e0), (s1, _) in zip(merged, merged[1:]):
            before = max((m for m in mods if m[1] <= s1 and m[1] >= e0),
                         key=lambda m: m[1], default=None)
            after = min((m for m in mods if m[0] >= s1),
                        key=lambda m: m[0], default=None)
            label = (f"{before[2] if before else '?'} -> "
                     f"{after[2] if after else '?'}")
            gap_time[label] += (s1 - e0) * 1e-9
        starts = [m[0] for m in mods]
        for p, line, name, s, d in events:
            if p != plane or line != OPS:
                continue
            i = bisect.bisect_right(starts, s) - 1
            where = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            op_time[f"{where}/{op_name(name)}"] += d * 1e-9
            if COLLECTIVE.search(name):
                collective += d * 1e-9
    return {
        "chips": n,
        "busy_s": busy / n,
        "window_s": window_s,
        "programs": {k: {"calls": v[0] / n, "device_s": v[1] / n}
                     for k, v in programs.items()},
        "collective_s": collective / n,
        "device_ops": [[k, v / n] for k, v in op_time.most_common(top)],
        "idle_gaps": [[k, v / n] for k, v in gap_time.most_common(top)],
    }


def per_call_ms(reduced: dict, program: str):
    """Device milliseconds per call of ``program``; None where it never
    ran in the traced window."""
    p = reduced["programs"].get(program)
    if not p or not p["calls"]:
        return None
    return 1e3 * p["device_s"] / p["calls"]
