#!/usr/bin/env python3
"""What a fan-out launch after a tick's first costs the tick thread, in
rows of device time: the number `sfu/translator.py:LAUNCH_COST_ROWS`
states, read off a traced slice (`benchmarks/run.py --trace 1` on the
chip leaves the `.xplane.pb`; this reads it with `JAX_PLATFORMS=cpu`).

    python3 scripts/launch_cost.py <xplane.pb> [...]

A tick whose `stage:expand` says `launches` 2 books every span of its
second launch with `launch` 1: the cost is that launch's
`fanout_dispatch` + `fanout_wait` + `fanout_d2h` + `nack_cache` +
`egress` (the hand-over), the median over such ticks, over the
1,024-row fan-out program's own time a row (device 0's `XLA Modules`
events of the fan-out program that last what a 1,024-row launch lasts).
Ticks whose second launch pads to the 1,024-row class wait for a
program of their own and are told apart from those whose second launch
is a small one (`rows_padded` of the tick; `second_by_rows` counts them
by the class of that launch, the fan-out's own 512-row class among
them since PR 44).  One JSON line a trace.
"""

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import xstats  # noqa: E402

STAGES = ("fanout_dispatch", "fanout_wait", "fanout_d2h", "nack_cache",
          "egress")
#: a 1,024-row CM or per-row GCM fan-out program lasts 1.84 / 2.04 ms on
#: a v5e, the 256-row one 0.53 / 0.58, the 512-row one 0.93 / 1.06 and
#: the 4,096-row one 8.4 / 8.0 (PERF.md section 6, PR 44): only the
#: 1,024-row one falls inside this window
PROGRAM_1024_MS = (1.4, 2.8)


def med(v):
    return round(float(np.median(v)), 4) if len(v) else None


def read(path: str) -> dict:
    got = xstats.load(path)
    ticks = {}
    for name, _s, dur, stats in got["host"]:
        t = ticks.setdefault(stats.get("tick"), {"second": {}})
        stage = name[len("stage:"):]
        if stage == "expand" and "launches" in stats:
            t["expand"] = stats
        elif stage == "tick":
            t["tick_ms"] = dur / 1e6
        elif stage in STAGES and stats.get("launch") == 1:
            t["second"][stage] = t["second"].get(stage, 0.0) + dur / 1e6
            if stage == "egress":
                t["behind"] = stats.get("behind", 0)
    progs = [d / 1e6 for n, _s, d, _st in got["modules"] if "fanout" in n]
    p1024 = [d for d in progs if PROGRAM_1024_MS[0] <= d <= PROGRAM_1024_MS[1]]
    us_row = 1e3 * float(np.median(p1024)) / 1024 if p1024 else None
    out = {"trace": path, "ticks": len(ticks),
           "ticks_by_launches": {}, "class_cut_ticks": 0,
           "program_1024_ms": med(p1024), "programs_1024": len(p1024),
           "device_us_a_row": round(us_row, 4) if us_row else None,
           "second_by_rows": {}}
    kinds = {"second_small": [], "second_1024": []}
    for t in ticks.values():
        exp = t.get("expand")
        if exp is None:
            continue
        n = int(exp["launches"])
        by = out["ticks_by_launches"]
        by[n] = by.get(n, 0) + 1
        out["class_cut_ticks"] += int(exp.get("class_cut", 0))
        # 1,024 rows and a remainder: the cut this constant decides
        second = exp["rows_padded"] - 1024
        if (n == 2 and exp.get("class_cut") and exp["rows"] > 1024
                and len(t["second"]) == len(STAGES)):
            kinds["second_small" if second < 1024
                  else "second_1024"].append(t)
            by = out["second_by_rows"]
            by[int(second)] = by.get(int(second), 0) + 1
    for kind, ts in kinds.items():
        cost = [sum(t["second"].values()) for t in ts]
        out[kind] = {
            "ticks": len(ts), "cost_ms": med(cost),
            "cost_ms_q1_q3": [round(float(q), 4) for q in
                              np.percentile(cost, [25, 75])] if ts else None,
            "cost_rows": round(1e3 * float(np.median(cost)) / us_row)
            if ts and us_row else None,
            "behind_pct": round(100.0 * float(np.mean(
                [t.get("behind", 0) for t in ts])), 2) if ts else None,
            "tick_ms": med([t["tick_ms"] for t in ts if "tick_ms" in t]),
            **{s + "_ms": med([t["second"][s] for t in ts])
               for s in STAGES}}
    return out


if __name__ == "__main__":
    for p in sys.argv[1:]:
        print(json.dumps(read(p)))
