"""From a profiler trace to numbers: device busy and idle time, device
time by program, and the longest idle gaps named by what the tick
thread was doing.

The JAX profiler writes `<dir>/plugins/profile/<run>/*.xplane.pb`;
`jax.profiler.ProfileData` reads it with nothing but JAX.  `load_xplane`
turns it into a neutral dict (the same form the recorded fixture under
`fixtures/` is kept in), and `reduce_trace` works on that form only, so
the arithmetic is checked by `check.py` against a real v5e slice without
a chip.

Neutral form::

    {"device": {"<plane name>": {"<line name>": [[name, start_ns, dur_ns], ...]}},
     "host":   {"<annotation name>": [[start_ns, dur_ns], ...]}}

A TPU device plane (`/device:TPU:<n>`) carries, among others, the lines
`XLA Ops` (one event per executed HLO op: these are the instants an
operation ran on the device) and `XLA Modules` (one event per launched
program, named `jit_<function>(<fingerprint>)`).  Busy time is the
union of the `XLA Ops` intervals; per-program time is summed from
`XLA Modules`.  Host annotations are the `TraceAnnotation`s the
program's `PipelineTracer` emits (`stage:<name>`), found on whichever
host thread line carries them.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE_RE = re.compile(r"^/device:TPU:(\d+)$")
ANNOTATION_RE = re.compile(r"^(stage|bench):")
GAP_FLOOR_NS = 20_000


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return hits[-1]


def describe_xplane(path: str, top: int = 12) -> dict:
    """Planes, lines and their commonest event names: what one looks at
    by hand before trusting `load_xplane`'s choice of lines."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            names, n, total = {}, 0, 0
            for ev in line.events:
                n += 1
                total += int(ev.duration_ns)
                names[ev.name] = names.get(ev.name, 0) + 1
            common = sorted(names.items(), key=lambda kv: -kv[1])[:top]
            lines[line.name] = {"events": n, "dur_ns": total,
                                "names": common}
        out[plane.name] = lines
    return out


def load_xplane(path: str, t_lo_ns: int = None, t_hi_ns: int = None
                ) -> dict:
    """Neutral form of one `.xplane.pb`, optionally cut to
    `[t_lo, t_hi)` of the trace's own clock (events overlapping an edge
    are clipped to it)."""
    from jax.profiler import ProfileData

    def clip(s, d):
        e = s + d
        if t_lo_ns is not None:
            s = max(s, t_lo_ns)
        if t_hi_ns is not None:
            e = min(e, t_hi_ns)
        return (s, e - s) if e > s else None

    device, host = {}, {}
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE_RE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                evs = []
                for ev in line.events:
                    c = clip(int(ev.start_ns), int(ev.duration_ns))
                    if c:
                        evs.append([ev.name, c[0], c[1]])
                lines[line.name] = evs
            device[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ANNOTATION_RE.match(ev.name):
                        c = clip(int(ev.start_ns), int(ev.duration_ns))
                        if c:
                            host.setdefault(ev.name, []).append(
                                [c[0], c[1]])
    return {"device": device, "host": host}


def clip_trace(trace: dict, lo_ns: int, hi_ns: int) -> dict:
    """`trace` cut to `[lo, hi)`; events across an edge are clipped."""
    def clip(s, d):
        s2, e2 = max(s, lo_ns), min(s + d, hi_ns)
        return (s2, e2 - s2) if e2 > s2 else None

    device = {}
    for plane, lines in trace["device"].items():
        device[plane] = {}
        for line, evs in lines.items():
            out = []
            for n, s, d in evs:
                c = clip(s, d)
                if c:
                    out.append([n, c[0], c[1]])
            device[plane][line] = out
    host = {}
    for name, evs in trace["host"].items():
        out = [list(c) for c in (clip(s, d) for s, d in evs) if c]
        if out:
            host[name] = out
    return {"device": device, "host": host}


def save_fixture(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f, separators=(",", ":"))


def load_fixture(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def merge_intervals(ivals) -> list:
    """Sorted, merged `[start, end)` intervals."""
    out = []
    for s, e in sorted(ivals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def program_name(module_event_name: str) -> str:
    """`jit__fanout_protect(1234567)` -> `jit__fanout_protect`."""
    return module_event_name.split("(", 1)[0]


def reduce_trace(trace: dict, top: int = 10) -> dict:
    """Busy/idle, per-program device time and gap attribution.

    The traced window runs from the first to the last event seen on any
    device or host line.  `busy_s` is the union of the device's op
    intervals, averaged over the device planes.  Each idle gap (between
    merged busy intervals of device 0) is named by the innermost host
    annotation open for most of it, `(none)` where the tick thread was
    inside no stage: between ticks, in the supervisor or in the
    benchmark's own loop.
    """
    planes = trace["device"]
    if not planes:
        return {}
    lo, hi = None, None
    for lines in planes.values():
        for evs in lines.values():
            for _n, s, d in evs:
                lo = s if lo is None else min(lo, s)
                hi = s + d if hi is None else max(hi, s + d)
    for evs in trace["host"].values():
        for s, d in evs:
            lo = s if lo is None else min(lo, s)
            hi = s + d if hi is None else max(hi, s + d)
    if lo is None:
        return {}
    window_ns = hi - lo
    busy, merged0 = [], None
    programs, launches = {}, {}
    for name in sorted(planes):
        lines = planes[name]
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        merged = merge_intervals([s, s + d] for _n, s, d in ops)
        busy.append(sum(e - s for s, e in merged))
        if merged0 is None:
            merged0 = merged
        for n, _s, d in lines.get(MODULES_LINE, []):
            p = program_name(n)
            programs[p] = programs.get(p, 0) + d
            launches[p] = launches.get(p, 0) + 1
    busy_ns = sum(busy) / len(busy)
    # gaps of device 0, edges of the window included
    gaps, prev = [], lo
    for s, e in merged0:
        if s > prev:
            gaps.append((prev, s))
        prev = e
    if hi > prev:
        gaps.append((prev, hi))
    # spans of the program's stages, flat: (start, end, name); the
    # benchmark's own `bench:*` marks only cut the slice
    spans = sorted((s, s + d, k) for k, v in trace["host"].items()
                   if not k.startswith("bench:") for s, d in v)
    starts = np.array([s for s, _e, _k in spans], dtype=np.int64)
    ends = np.array([e for _s, e, _k in spans], dtype=np.int64)

    def name_gap(g0, g1):
        """The stage the tick thread was inside for most of the gap.
        Stages nest (`_on_media` runs inside `reverse_chain`): at each
        instant the INNERMOST open span counts, the one that started
        last; `(none)` where no span is open."""
        over = np.nonzero((starts < g1) & (ends > g0))[0]
        if not len(over):
            return "(none)"
        cuts = sorted({g0, g1} | {int(np.clip(x, g0, g1)) for i in over
                                  for x in (starts[i], ends[i])})
        cover = {}
        for a, b in zip(cuts, cuts[1:]):
            inner = [i for i in over if starts[i] <= a and ends[i] >= b]
            k = spans[max(inner, key=lambda i: starts[i])][2] \
                if inner else "(none)"
            cover[k] = cover.get(k, 0) + (b - a)
        return max(cover, key=cover.get)

    # a gap shorter than GAP_FLOOR_NS lies between two ops of one
    # program: counted as idle, not worth a name
    by_stage = {}
    for g0, g1 in gaps:
        k = name_gap(g0, g1) if g1 - g0 >= GAP_FLOOR_NS \
            else "(within a program)"
        by_stage[k] = by_stage.get(k, 0) + (g1 - g0)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    named = [[name_gap(g0, g1), (g1 - g0) / 1e9] for g0, g1 in longest]
    ops_by_name = {}
    for lines in planes.values():
        for n, _s, d in lines.get(OPS_LINE, []):
            ops_by_name[n] = ops_by_name.get(n, 0) + d
    n_dev = len(planes)
    return {
        "window_s": window_ns / 1e9, "busy_s": busy_ns / 1e9,
        "idle_pct": 100.0 * (1.0 - busy_ns / window_ns),
        "program_s": {p: t / 1e9 / n_dev for p, t in programs.items()},
        "program_launches": launches,
        "idle_by_stage_s": {k: v / 1e9 for k, v in by_stage.items()},
        "breakdown": {
            "device_ops": [[p, t / 1e9 / n_dev] for p, t in sorted(
                programs.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": named},
        "top_ops": [[n, t / 1e9 / n_dev] for n, t in sorted(
            ops_by_name.items(), key=lambda kv: -kv[1])[:top]],
    }


def _main(argv) -> int:
    """`describe <xplane>` | `report <xplane or fixture>` |
    `export <xplane> <out.trace.json.gz> [seconds]` (a fixture: the
    first `seconds` of the traced ticks, times rebased to 0)."""
    import sys

    cmd, path = argv[1], argv[2]
    if cmd == "describe":
        json.dump(describe_xplane(path), sys.stdout, indent=1)
        return 0
    trace = (load_fixture(path) if path.endswith(".json.gz")
             else load_xplane(path))
    ticks = trace["host"].get("bench:tick")
    if ticks:
        lo = min(s for s, _d in ticks)
        hi = max(s + d for s, d in ticks)
        if cmd == "export" and len(argv) > 4:
            hi = min(hi, lo + int(float(argv[4]) * 1e9))
        trace = clip_trace(trace, lo, hi)
    if cmd == "export":
        base = min(s for lines in trace["device"].values()
                   for evs in lines.values() for _n, s, _d in evs)
        base = min([base] + [s for evs in trace["host"].values()
                             for s, _d in evs])
        for lines in trace["device"].values():
            for evs in lines.values():
                for ev in evs:
                    ev[1] -= base
        for evs in trace["host"].values():
            for ev in evs:
                ev[0] -= base
        save_fixture(trace, argv[3])
    red = reduce_trace(trace)
    json.dump({k: v for k, v in red.items()}, sys.stdout, indent=1)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(_main(sys.argv))
