#!/usr/bin/env python
"""Offline device-occupancy report from a jax profiler trace.

`jax.profiler.trace()` writes a Perfetto/Chrome-format trace
(`*.trace.json.gz`) that ui.perfetto.dev renders beautifully — but a
browser tab is not checked-in evidence.  This tool parses the trace
with stdlib only (gzip + json) and prints the numbers ROADMAP #1
needs on the record: device idle share over the capture, the largest
dispatch gaps (host stalls between consecutive device slices), and
the top kernels by accumulated device time.

  python scripts/trace_report.py /tmp/libjitsi_tpu_trace
  python scripts/trace_report.py --capture-loop-echo
  python scripts/trace_report.py --merge-bridges a.om b.om
  python scripts/trace_report.py --merge-bridges \\
      http://127.0.0.1:9101 http://127.0.0.1:9102

`--merge-bridges` is the offline twin of `/debug/fleet`: each source
is either a saved OpenMetrics exposition file or a live bridge base
URL; the hop-labeled `packet_journey_seconds` exemplars from every
source are stitched by trace id (service/obs_server.stitch_journeys),
and the report lists each cross-bridge journey's spans — the packet's
path across the cascade trunk.

The capture mode runs the small loop-echo scenario (perf_gate's
`loop_echo_pps` twin) once for the loop's phase split and once under
`jax.profiler.trace`, then reports the trace occupancy AND the
phase-ledger host share — the two independent views the host-bound
diagnosis rests on.  On a CPU-only box the profiler may not emit a
device track; the report says so instead of inventing one.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "scripts"))

#: process_name metadata matching this marks a device (accelerator)
#: track; everything else is host-side plumbing
DEVICE_TRACK_RE = re.compile(r"(?i)(tpu|gpu|/device|accelerator|xla)")

#: slices named like these are transfers, split out from compute
TRANSFER_RE = re.compile(r"(?i)(copy|transfer|h2d|d2h|memcpy|infeed|"
                         r"outfeed)")


def find_trace_file(path: str) -> str:
    """Accept a trace dir (jax layout: plugins/profile/<run>/...) or a
    direct *.trace.json[.gz] file."""
    if os.path.isfile(path):
        return path
    hits = sorted(
        glob.glob(os.path.join(path, "**", "*.trace.json.gz"),
                  recursive=True)
        + glob.glob(os.path.join(path, "**", "*.trace.json"),
                    recursive=True))
    if not hits:
        raise FileNotFoundError(
            f"no *.trace.json[.gz] under {path!r} — did the "
            "jax.profiler.trace() block run any device work?")
    return hits[-1]           # newest run sorts last (timestamped dirs)


def load_events(trace_file: str) -> list:
    opener = gzip.open if trace_file.endswith(".gz") else open
    with opener(trace_file, "rt") as f:
        doc = json.load(f)
    return doc.get("traceEvents", doc if isinstance(doc, list) else [])


def _interval_union(ivals):
    """Total covered length of [start, end) intervals, merged."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(ivals):
        if cur_s is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
    if cur_s is not None:
        total += cur_e - cur_s
    return total


def build_report(events: list) -> dict:
    """Pure analysis over Chrome-trace events — unit-testable with a
    synthetic event list.  Times in the trace are microseconds."""
    proc_names = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            proc_names[ev.get("pid")] = \
                ev.get("args", {}).get("name", "")
    device_pids = {pid for pid, name in proc_names.items()
                   if DEVICE_TRACK_RE.search(name or "")}
    slices = [ev for ev in events
              if ev.get("ph") == "X" and ev.get("dur") is not None]
    if not slices:
        return {"error": "trace has no complete (ph=X) slices"}
    t0 = min(ev["ts"] for ev in slices)
    t1 = max(ev["ts"] + ev["dur"] for ev in slices)
    wall_us = t1 - t0
    dev = [ev for ev in slices if ev.get("pid") in device_pids]
    report = {
        "trace_wall_s": wall_us / 1e6,
        "num_slices": len(slices),
        "device_tracks": sorted(proc_names[p] for p in device_pids),
    }
    if not dev:
        report["error"] = (
            "no device track matched %r — host-only capture (CPU "
            "backend traces often lack one); use the phase-ledger "
            "host share instead" % DEVICE_TRACK_RE.pattern)
        return report
    busy_us = _interval_union(
        (ev["ts"], ev["ts"] + ev["dur"]) for ev in dev)
    # largest gaps between consecutive device slices = dispatch
    # stalls: the host didn't have the next launch ready
    merged = []
    for s, e in sorted((ev["ts"], ev["ts"] + ev["dur"]) for ev in dev):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    gaps = sorted(
        ((b[0] - a[1]) / 1e6 for a, b in zip(merged, merged[1:])),
        reverse=True)[:5]
    by_kernel = {}
    transfer_us = 0.0
    for ev in dev:
        name = ev.get("name", "?")
        by_kernel[name] = by_kernel.get(name, 0.0) + ev["dur"]
        if TRANSFER_RE.search(name):
            transfer_us += ev["dur"]
    top = sorted(by_kernel.items(), key=lambda kv: -kv[1])[:8]
    report.update({
        "device_busy_s": busy_us / 1e6,
        "device_idle_pct": 100.0 * (1.0 - busy_us / wall_us),
        "device_transfer_s": transfer_us / 1e6,
        "largest_dispatch_gaps_s": gaps,
        "top_kernels": [(name, us / 1e6) for name, us in top],
    })
    return report


def format_report(report: dict) -> str:
    lines = ["== trace occupancy report =="]
    if "trace_wall_s" in report:
        lines.append(f"  wall span:        "
                     f"{report['trace_wall_s'] * 1e3:.2f} ms "
                     f"({report['num_slices']} slices)")
        lines.append(f"  device tracks:    "
                     f"{report['device_tracks'] or '(none)'}")
    if "error" in report:
        lines.append(f"  NOTE: {report['error']}")
        return "\n".join(lines)
    lines.append(f"  device busy:      "
                 f"{report['device_busy_s'] * 1e3:.2f} ms")
    lines.append(f"  device idle:      "
                 f"{report['device_idle_pct']:.1f} % of capture")
    lines.append(f"  transfer share:   "
                 f"{report['device_transfer_s'] * 1e3:.2f} ms")
    lines.append("  largest dispatch gaps (s): "
                 + ", ".join(f"{g:.4f}"
                             for g in report["largest_dispatch_gaps_s"]))
    lines.append("  top kernels by device time:")
    for name, s in report["top_kernels"]:
        lines.append(f"    {s * 1e3:9.3f} ms  {name}")
    return "\n".join(lines)


def capture_loop_echo(log_dir: str) -> dict:
    """Two-pass loop-echo evidence capture: {trace report, phase ledger}.

    Pass 1 (phase ledger + pps): the gate's windowed loop-echo with NO
    jax.profiler trace active — profiler instrumentation overhead
    lands inside the dispatch spans and would misattribute the tick.  Warmup totals are snapshotted out
    so bucket compiles don't pollute the steady-state ledger (the same
    discipline as perf_gate's `loop_host_share` scenario).

    Pass 2 (occupancy report): a shorter run of the same scenario under
    jax.profiler.trace for the offline Perfetto view.  It is slower
    under instrumentation by design; pass 1 owns the headline numbers.
    """
    import perf_gate
    from libjitsi_tpu.utils import perf as perf_mod
    from jax.profiler import trace

    profilers = []
    warm_marks = []
    orig_init = perf_mod.LoopPerf.__init__

    def noting_init(self, *a, **kw):
        orig_init(self, *a, **kw)
        profilers.append(self)

    def snapshot_warm():
        warm_marks.extend(
            (prof, dict(getattr(prof, "phase_totals", {})))
            for prof in profilers)

    perf_mod.LoopPerf.__init__ = noting_init
    try:
        # saturated offered load (128-pkt bursts, the gate scenario's
        # configuration): host share is workload-dependent — per-call
        # dispatch overhead is constant, so it is measured where it
        # classifies overload, not at trickle load
        done, net = perf_gate._run_loop_echo(
            n_pkts=128, cycles=16, pipeline_depth=3,
            on_steady=snapshot_warm)
    finally:
        perf_mod.LoopPerf.__init__ = orig_init
    # steady-state delta only (warmup compiles land in `dispatch`)
    phases = {}
    for prof, warm in warm_marks:
        for name, secs in getattr(prof, "phase_totals", {}).items():
            phases[name] = (phases.get(name, 0.0) + secs
                            - warm.get(name, 0.0))
    with trace(log_dir):
        perf_gate._run_loop_echo(n_pkts=64, cycles=8, pipeline_depth=3)
    report = build_report(load_events(find_trace_file(log_dir)))
    from libjitsi_tpu.io.udp import probe_engine_mode
    return {"loop_echo_pps": done / net, "phases": phases,
            # the ingest engine the capture ran with: before/after
            # occupancy comparisons are only valid within one mode
            "engine_mode": probe_engine_mode(),
            "host_share": perf_mod.host_share(phases),
            "bound": perf_mod.classify_bound(phases),
            "trace": report}


def merge_bridges(sources: list) -> dict:
    """Fleet journey stitch over offline scrapes and/or live bridges.
    Each source is a file holding an OpenMetrics exposition or an
    http(s) base URL (its /metrics is fetched with the OM Accept
    header).  Returns the same document /debug/fleet serves."""
    from libjitsi_tpu.service.obs_server import (fetch_metrics,
                                                 stitch_journeys)
    scrapes, errors = {}, {}
    for src in sources:
        name = src
        try:
            if src.startswith(("http://", "https://")):
                scrapes[name] = fetch_metrics(src)
            else:
                name = os.path.basename(src)
                with open(src, "r") as f:
                    scrapes[name] = f.read()
        except Exception as exc:
            errors[name] = repr(exc)
    doc = stitch_journeys(scrapes)
    doc["errors"] = errors
    return doc


def format_fleet(doc: dict) -> str:
    lines = ["== cross-bridge journey report =="]
    for name, b in sorted(doc["bridges"].items()):
        hops = ", ".join(f"{h}={int(c)}"
                         for h, c in sorted(b["hops"].items()))
        lines.append(f"  {name}: {b['exemplars']} journey exemplars"
                     + (f"  [{hops}]" if hops else ""))
    for name, err in sorted(doc.get("errors", {}).items()):
        lines.append(f"  {name}: SCRAPE FAILED {err}")
    stitched = doc["stitched_trace_ids"]
    lines.append(f"  stitched journeys (seen on >1 bridge): "
                 f"{len(stitched)}")
    for j in doc["journeys"]:
        if not j["stitched"]:
            continue
        lines.append(f"  trace {j['trace_id']}:")
        for s in j["spans"]:
            lines.append(f"    {s['bridge']:>16s}  hop={s['hop']:<12s}"
                         f" {s['seconds'] * 1e3:8.3f} ms")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", nargs="*",
                    default=["/tmp/libjitsi_tpu_trace"],
                    help="trace dir or *.trace.json[.gz] file; with "
                         "--merge-bridges, two+ exposition files or "
                         "bridge base URLs")
    ap.add_argument("--capture-loop-echo", action="store_true",
                    help="capture a fresh loop-echo trace first")
    ap.add_argument("--merge-bridges", action="store_true",
                    help="stitch cross-bridge journeys from the given "
                         "scrapes/URLs instead of reading a trace")
    ap.add_argument("--json", action="store_true",
                    help="emit the raw report dict as JSON")
    args = ap.parse_args(argv)
    if args.merge_bridges:
        doc = merge_bridges(args.path)
        if args.json:
            print(json.dumps(doc, indent=2, default=str))
        else:
            print(format_fleet(doc))
        return 0 if doc["bridges"] and not doc.get("errors") else 1
    path = args.path[0] if args.path else "/tmp/libjitsi_tpu_trace"
    if args.capture_loop_echo:
        doc = capture_loop_echo(path)
        if args.json:
            print(json.dumps(doc, indent=2, default=str))
            return 0
        print(format_report(doc["trace"]))
        print("== phase ledger (every tick fenced, steady state) ==")
        total = sum(doc["phases"].values()) or 1.0
        for name, secs in sorted(doc["phases"].items(),
                                 key=lambda kv: -kv[1]):
            print(f"  {name:15s} {secs * 1e3:9.2f} ms "
                  f"({100 * secs / total:5.1f} %)")
        print(f"  host share (host / host+device): "
              f"{100 * doc['host_share']:.1f} %  -> {doc['bound']}-bound")
        print(f"  engine mode: {doc['engine_mode']} (compare captures "
              f"within one mode only)")
        print(f"  loop_echo_pps (every-tick fenced — attribution "
              f"overhead depresses this vs the perf-gate number): "
              f"{doc['loop_echo_pps']}")
        return 0
    report = build_report(load_events(find_trace_file(path)))
    if args.json:
        print(json.dumps(report, indent=2, default=str))
    else:
        print(format_report(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
