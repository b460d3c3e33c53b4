"""The device seam: each device call's host phases beside its program's
own start and end.

A tick makes two device calls (seam `unprotect`, seam `fanout`; a call
per size class).  The tick thread books four host phases round each
(`libjitsi_tpu/core/staging.py`, `utils/tracing.py`):

    S_dispatch [ pack | S_put | jit call ]  block  S_d2h
    ^ t_open                                    ^ t_close
                         [ program ]        <- the chip's `XLA Modules`
               start lag   program   end lag

(`block` is `unprotect_block` / `fanout_wait`).

**The clocks.**  The host's `stage:*` events and the runtime's own host
events share the profiler's host clock.  The device planes do NOT: a
v5e's `XLA Modules` events lie some milliseconds off the host's, and
the offset steps inside a slice (PERF.md, PR 37: 4.3 ms, then 1.5 ms
from one launch to the next).  Durations on a device plane are exact;
instants are not.  What ties a program to the host clock is the
runtime: for every execution the TPU client writes, on the HOST plane,
`DoEnqueueProgram` (the program is handed to the chip's queue) and
`CompleteCallbacks` (the host has seen it end), both with the `run_id`
and `device_ordinal` that the device's `XLA Modules` event carries.  So

- a call's program is found by TIME and tick on the host clock, never
  by name: the `DoEnqueueProgram` of the chip that starts between
  `t_open` and `t_close` (a tick's calls in order, a program given to
  one call only), and from it by `run_id` the device's event;
- the chip's clock is tied to the host's as one-way-delay clocks are:
  a program cannot start before its enqueue returned, nor end after
  the host saw it end, so `offset` in [enqueue end - device start,
  completion seen - device end], and the fastest pick-up among the
  `WINDOW` launches either side is taken as no delay (`offset` = the
  largest lower bound of the neighbourhood).  Where that is above a
  neighbour's upper bound (the offset stepped there) the launch has no
  host-clock instant and its tick gives no number.

For a matched call

    t_close - t_open == start lag + program time + end lag

holds by construction and is asserted; the program time is the
device's own, exact.  The split of the rest between the two lags
carries the tie's error: the spread of the pick-up delay, some tens of
microseconds (`report` prints the slack a launch).  A tick whose spans
and programs do not pair up gives NO number: it is left out, and where
fewer than `MIN_PAIRED` of the slice's calling ticks pair the reader
returns None, so a mis-match never prints.

Every reader returns None in an untraced run, off the chip (no device
plane, no runtime events) and where the program has no such span (the
parent of the PR that added them has `fanout_dispatch` / `fanout_wait`
/ `fanout_d2h` and `unprotect_wait`: the fan-out's lags and the
overhead read on it, the unprotect's lags do not).  Nothing here
imports the program.

`python3 benchmarks/seams.py report <xplane.pb>` prints the split of
every seam and, for a mesh, the skew between the chips;
`... inventory <xplane.pb>` lists what ELSE the runtime wrote between
a put and its program's start and between the program's end and the
end of the copy back (PERF.md, PR 37, item 3).
"""

from __future__ import annotations

import bisect
import functools

import numpy as np

import reduce
import xstats

#: seam -> the stage that opens a call, the stage whose end closes it,
#: and the two leaves inside and behind it
SEAMS = {
    "unprotect": {"dispatch": "unprotect_dispatch", "put": "unprotect_put",
                  "block": "unprotect_block", "d2h": "unprotect_d2h"},
    "fanout": {"dispatch": "fanout_dispatch", "put": "fanout_put",
               "block": "fanout_wait", "d2h": "fanout_d2h"},
}
#: what the tick thread spends round the two calls (PERF.md section 5)
OVERHEAD_SPANS = ("unprotect_wait", "fanout_dispatch", "fanout_wait",
                  "fanout_d2h")
#: share of the slice's calling ticks that must pair for a number
MIN_PAIRED = 0.9
#: launches either side whose fastest pick-up ties the chip's clock
WINDOW = 16
#: the runtime's host events that carry `run_id` and `device_ordinal`
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"
#: the copies' own: they end when the bytes have crossed (`chip_id`)
COPIED = {"tpu::System::TransferToDevice=>IssueEvent=>Done": "h2d_done",
          "tpu::System::TransferFromDevice=>IssueEvent=>Done": "d2h_done"}


# ---- the trace --------------------------------------------------------------

@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    """`{"host": the slice's `stage:*` events (`xstats.load`), "lo",
    "hi", "chips": {ordinal: chip}}` of one `.xplane.pb`, a chip being
    `{"modules": [(run_id, dev_start_ns, dur_ns)]` by start, device
    clock; `"enqueue": [(start_ns, end_ns, run_id)]` by start, host
    clock; `"seen": {run_id: ns}`, host clock; `"h2d_done"`,
    `"d2h_done"`: [ns] by time, host clock, when a copy's bytes had
    crossed`}`, each cut to the executions whose enqueue starts inside
    the slice."""
    from jax.profiler import ProfileData

    evs = xstats.load(path)
    lo, hi = evs["lo"], evs["hi"]
    chips = {}

    def chip(ordinal):
        return chips.setdefault(int(ordinal), {
            "modules": [], "enqueue": [], "seen": {}, "h2d_done": [],
            "d2h_done": []})

    for plane in ProfileData.from_file(path).planes:
        device = reduce.DEVICE_PLANE_RE.match(plane.name)
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name != reduce.MODULES_LINE:
                continue
            for ev in line.events:
                if device:
                    run = dict(ev.stats).get("run_id")
                    if run is not None:
                        chip(device.group(1))["modules"].append(
                            (run, int(ev.start_ns), int(ev.duration_ns)))
                elif ev.name in COPIED:
                    end = int(ev.start_ns + ev.duration_ns)
                    on = dict(ev.stats).get("chip_id")
                    if lo <= end < hi and on is not None:
                        chip(on)[COPIED[ev.name]].append(end)
                elif ev.name in (ENQUEUE, COMPLETE):
                    st = dict(ev.stats)
                    if "run_id" not in st or "device_ordinal" not in st:
                        continue
                    c, s = chip(st["device_ordinal"]), int(ev.start_ns)
                    if ev.name == COMPLETE:
                        c["seen"][st["run_id"]] = s
                    elif lo <= s < hi:
                        c["enqueue"].append(
                            (s, s + int(ev.duration_ns), st["run_id"]))
    for c in chips.values():
        c["enqueue"].sort()
        c["h2d_done"].sort()
        c["d2h_done"].sort()
        runs = {r for _s, _e, r in c["enqueue"]}
        c["modules"] = sorted((m for m in c["modules"] if m[0] in runs),
                              key=lambda m: m[1])
    return {"host": evs["host"], "lo": lo, "hi": hi,
            "chips": {k: c for k, c in chips.items() if c["modules"]}}


def _slice(ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("xplane"):
        return None
    got = load(tr["xplane"])
    return got if got["chips"] else None


def programs(chip: dict) -> dict:
    """`{run_id: (start_ns, end_ns)}` on the HOST clock of the chip's
    programs whose instant the tie gives (the module's note): each
    launch's offset is the largest lower bound (`enqueue end - device
    start`) among the `WINDOW` launches either side, dropped where it
    exceeds one of their upper bounds (`completion seen - device
    end`)."""
    enq_end = {r: e for _s, e, r in chip["enqueue"]}
    mods = [m for m in chip["modules"]
            if m[0] in enq_end and m[0] in chip["seen"]]
    if not mods:
        return {}
    lower = np.array([enq_end[r] - s for r, s, _d in mods])
    upper = np.array([chip["seen"][r] - (s + d) for r, s, d in mods])
    out = {}
    for i, (run, s, d) in enumerate(mods):
        a, b = max(0, i - WINDOW), i + WINDOW + 1
        off = int(lower[a:b].max())
        if off <= upper[a:b].min():
            out[run] = (s + off, s + off + d)
    return out


def slack_ms(chip: dict):
    """Median over the chip's launches of (completion seen - enqueue
    end - program time), ms: pick-up delay plus the notice of the end,
    which no clock here can split."""
    enq_end = {r: e for _s, e, r in chip["enqueue"]}
    got = [chip["seen"][r] - enq_end[r] - d for r, _s, d in chip["modules"]
           if r in enq_end and r in chip["seen"]]
    return float(np.median(got)) / 1e6 if got else None


# ---- pairing ----------------------------------------------------------------

def by_tick(host, stage: str) -> dict:
    """`{tick: [(start_ns, end_ns), ...]}` of the slice's
    `stage:<stage>` events, in order of start."""
    out = {}
    for name, s, d, stats in host:
        if name == "stage:" + stage and "tick" in stats:
            out.setdefault(stats["tick"], []).append((s, s + d))
    return out


def pair(host, chip: dict, open_stage: str, close_stage: str,
         progs: dict = None) -> dict:
    """`{tick: [(t_open, t_close, p_start, p_end), ...] or None}` for
    every tick that has a `stage:<open_stage>`: its i-th such event's
    start and its i-th `stage:<close_stage>`'s end, with the program
    the chip was handed between them (the first `DoEnqueueProgram` not
    yet taken that starts inside), on the host clock (`programs`);
    None where the tick does not pair up."""
    progs = programs(chip) if progs is None else progs
    opens, closes = by_tick(host, open_stage), by_tick(host, close_stage)
    enqueue = chip["enqueue"]
    starts = [e[0] for e in enqueue]
    taken = [False] * len(enqueue)
    out = {}
    for tick in sorted(opens, key=lambda t: opens[t][0][0]):
        o, c = opens[tick], closes.get(tick, [])
        calls = [] if len(o) == len(c) else None
        for (t_open, _e), (_s, t_close) in zip(o, c):
            if calls is None:
                break
            i = bisect.bisect_left(starts, t_open)
            while i < len(enqueue) and taken[i]:
                i += 1
            if i == len(enqueue) or starts[i] > t_close:
                calls = None
                break
            taken[i] = True
            p = progs.get(enqueue[i][2])
            if p is None or p[1] > t_close:
                calls = None    # no instant, or not what this wait ended on
                break
            start_lag, end_lag = p[0] - t_open, t_close - p[1]
            assert start_lag >= 0 and end_lag >= 0 and \
                start_lag + (p[1] - p[0]) + end_lag == t_close - t_open
            calls.append((t_open, t_close, p[0], p[1]))
        out[tick] = calls
    return out


def _paired(per_tick: dict):
    """The calls of the ticks that paired, flat and in order; None
    where too few did."""
    good = [calls for calls in per_tick.values() if calls is not None]
    if not per_tick or len(good) < MIN_PAIRED * len(per_tick):
        return None
    return [call for calls in good for call in calls]


def _chip0(got: dict) -> dict:
    return got["chips"][min(got["chips"])]


# ---- the readers ------------------------------------------------------------

def lag_p50_ms(ctx, seam: str, which: str):
    """Median over the slice's `seam` calls of `which` = `start`
    (program start less the start of `stage:S_dispatch`: pack, put, the
    jit call and whatever the runtime does before the chip begins) or
    `end` (end of the block less program end: how long after the chip
    finished the tick thread ran again), in ms; chip 0's."""
    got = _slice(ctx)
    if got is None:
        return None
    st = SEAMS[seam]
    calls = _paired(pair(got["host"], _chip0(got), st["dispatch"],
                         st["block"]))
    if not calls:
        return None
    lags = [p0 - t0 if which == "start" else t1 - p1
            for t0, t1, p0, p1 in calls]
    return float(np.median(lags)) / 1e6


def overhead_p50_ms(ctx):
    """`device_seam_overhead_p50_ms`: per tick, the time of its
    `OVERHEAD_SPANS` less the device time of the programs its two calls
    launched (chip 0's; exact, whatever the clocks); median over the
    ticks whose calls all paired, in ms.  The unprotect's program is
    looked for inside `stage:unprotect_wait` (which holds its dispatch
    and its block), so the parent reads too."""
    got = _slice(ctx)
    if got is None:
        return None
    host, chip = got["host"], _chip0(got)
    progs = programs(chip)
    # a tick's `unprotect_wait` ends before its `fanout_dispatch`
    # opens, so no program is enqueued inside both calls' windows
    calls = pair(host, chip, "unprotect_wait", "unprotect_wait", progs)
    for tick, fan in pair(host, chip, SEAMS["fanout"]["dispatch"],
                          SEAMS["fanout"]["block"], progs).items():
        mine = calls.get(tick, [])
        calls[tick] = None if fan is None or mine is None else mine + fan
    if not calls or sum(c is not None for c in calls.values()) \
            < MIN_PAIRED * len(calls):
        return None
    spans = {}
    for stage in OVERHEAD_SPANS:
        for tick, evs in by_tick(host, stage).items():
            spans[tick] = spans.get(tick, 0) + sum(e - s for s, e in evs)
    over = [spans[tick] - sum(p1 - p0 for _a, _b, p0, p1 in c)
            for tick, c in calls.items() if c is not None and tick in spans]
    return float(np.median(over)) / 1e6 if over else None


def chip_calls(ctx, seam: str = "fanout"):
    """`[{ordinal: (p_start, p_end)}]`: for each `seam` call of the
    slice that pairs on EVERY chip, its program's start and end a chip
    on the host clock (each chip's clock tied on its own).  None in an
    untraced run, with fewer than two chips, or where too few ticks
    pair."""
    got = _slice(ctx)
    if got is None or len(got["chips"]) < 2:
        return None
    st = SEAMS[seam]
    per_chip = {k: pair(got["host"], chip, st["dispatch"], st["block"])
                for k, chip in got["chips"].items()}
    ticks = set.intersection(*(set(p) for p in per_chip.values()))
    first = next(iter(per_chip.values()))
    both = {t: None if any(p[t] is None for p in per_chip.values())
            else [{k: p[t][i][2:] for k, p in per_chip.items()}
                  for i in range(len(first[t]))]
            for t in sorted(ticks)}
    return _paired(both)


def chip_skew_p50_ms(ctx, which: str, seam: str = "fanout"):
    """Per `seam` launch of a mesh: the latest chip's program start
    less the earliest chip's (`which` = `start`), or the same of the
    ends (`end`); median over the slice's launches, in ms."""
    calls = chip_calls(ctx, seam)
    if not calls:
        return None
    return float(np.median(_skews(calls, which == "end"))) / 1e6


def _skews(calls, k: int) -> list:
    """Latest less earliest chip of each call's starts (`k` 0) or ends
    (`k` 1), ns."""
    return [max(c[k] for c in call.values())
            - min(c[k] for c in call.values()) for call in calls]


# ---- by hand: the split, and what else the runtime wrote --------------------

def _med_ms(ns):
    return round(float(np.median(ns)) / 1e6, 4) if len(ns) else None


def report(path: str) -> dict:
    """Every seam's split over the slice of one `.xplane.pb`, medians
    in ms: the spans (dispatch, put, block, d2h a tick), the two lags
    and the program a call (chip 0), each lag split where the
    runtime's own events split it (`_milestones`); the overhead a
    tick; a chip the launches, how many
    the tie gave an instant, and the slack a launch (pick-up delay plus
    the notice of the end: what no clock here splits); on a mesh, on
    the host clock, the skew of the chips' enqueues, of their programs'
    starts and ends, of the host's seeing them end, and which chip was
    last how often."""
    ctx = {"trace": {"xplane": path}}
    got = load(path)
    host = got["host"]
    out = {"ticks": len(by_tick(host, "tick")), "chips": {
        k: {"launches": len(c["modules"]), "tied": len(programs(c)),
            "slack": slack_ms(c)} for k, c in got["chips"].items()}}
    for seam, st in SEAMS.items():
        mine = {stage: _med_ms([sum(e - s for s, e in v) for v in
                                by_tick(host, stage).values()])
                for stage in st.values()}
        per_tick = pair(host, _chip0(got), st["dispatch"],
                        st["block"]) if got["chips"] else {}
        calls = _paired(per_tick)
        if calls:
            mine["calls"] = len(calls)
            mine["start_lag"] = _med_ms([p0 - t0 for t0, _t, p0, _p in calls])
            mine["program"] = _med_ms([p1 - p0 for _a, _b, p0, p1 in calls])
            mine["end_lag"] = _med_ms([t1 - p1 for _t, t1, _p, p1 in calls])
            mine.update(_milestones(host, _chip0(got), st, per_tick))
        chips = chip_calls(ctx, seam)
        if chips:
            for k, which in enumerate(("start", "end")):
                mine[f"chip_{which}_skew"] = _med_ms(_skews(chips, k))
                last = {}
                for call in chips:
                    chip = max(call, key=lambda n: call[n][k])
                    last[chip] = last.get(chip, 0) + 1
                mine[f"chip_last_to_{which}"] = last
            mine["chip_program"] = {
                k: _med_ms([c[k][1] - c[k][0] for c in chips])
                for k in chips[0]}
        out[seam] = mine
    if len(got["chips"]) > 1:       # exact: the host clock alone
        runs = set.intersection(*(set(c["seen"]) & {
            r for _s, _e, r in c["enqueue"]} for c in got["chips"].values()))
        enq = {k: {r: e for _s, e, r in c["enqueue"]}
               for k, c in got["chips"].items()}
        out["mesh_host_clock"] = {
            "launches": len(runs),
            "enqueue_skew": _med_ms([
                max(e[r] for e in enq.values())
                - min(e[r] for e in enq.values()) for r in runs]),
            "seen_skew": _med_ms([
                max(c["seen"][r] for c in got["chips"].values())
                - min(c["seen"][r] for c in got["chips"].values())
                for r in runs])}
        for what, pick in (("last_enqueued", lambda k, r: enq[k][r]),
                           ("last_seen",
                            lambda k, r: got["chips"][k]["seen"][r])):
            last = {}
            for r in runs:
                k = max(got["chips"], key=lambda k: pick(k, r))
                last[k] = last.get(k, 0) + 1
            out["mesh_host_clock"][what] = last
    for stage in ("unprotect_wait", "owner_plan", "mesh_scatter"):
        out[stage] = _med_ms([sum(e - s for s, e in v)
                              for v in by_tick(host, stage).values()])
    out["device_seam_overhead"] = overhead_p50_ms(ctx)
    return out


def _milestones(host, chip: dict, st: dict, per_tick: dict) -> dict:
    """The two lags and the copy back of a seam's paired calls, split
    by the runtime's own events on the host clock, medians in ms: the
    start lag at the moment the put's bytes had crossed (`h2d_done`),
    the end lag at the moment the host saw the program end (`seen`),
    `S_d2h` at the moment the copy's bytes had crossed (`d2h_done`)."""
    def first(times, lo, hi):
        i = bisect.bisect_left(times, lo)
        return times[i] if i < len(times) and times[i] <= hi else None

    puts, d2hs = by_tick(host, st["put"]), by_tick(host, st["d2h"])
    seen_at = sorted(chip["seen"].values())
    parts = {}
    for tick, calls in per_tick.items():
        if not calls or len(puts.get(tick, ())) != len(calls) \
                or len(d2hs.get(tick, ())) != len(calls):
            continue
        for (t0, t1, p0, p1), put, d2h in zip(calls, puts[tick],
                                              d2hs[tick]):
            crossed = first(chip["h2d_done"], put[0], p0)
            seen = first(seen_at, p1, t1)
            back = first(chip["d2h_done"], d2h[0], d2h[1])
            for name, a, b in (
                    ("open_to_put", t0, put[0]),
                    ("put_end_to_h2d_done", put[1], crossed),
                    ("h2d_done_to_program_start", crossed, p0),
                    ("program_end_to_seen", p1, seen),
                    ("seen_to_block_end", seen, t1),
                    ("d2h_start_to_d2h_done", d2h[0], back),
                    ("d2h_done_to_d2h_end", back, d2h[1])):
                if a is not None and b is not None:
                    parts.setdefault(name, []).append(b - a)
    return {name: _med_ms(v) for name, v in parts.items()}


def inventory(path: str, top: int = 40) -> dict:
    """What the runtime itself wrote round the copies: every event of
    a host plane or of a device plane's other lines (NOT `stage:*` /
    `bench:*`, nor on an `XLA Modules` / `XLA Ops` line) that starts,
    for a paired call of chip 0, inside `h2d`: [start of `stage:S_put`,
    program start] or `d2h`: [program end, end of `stage:S_d2h`], both
    on the host clock.  By (window, plane, line, name): how many, the
    median offset of its start from the window's start and its median
    duration, us; in order of offset, the `top` commonest."""
    from jax.profiler import ProfileData

    got = load(path)
    windows = []        # (lo, hi, kind)
    for seam, st in SEAMS.items():
        calls = pair(got["host"], _chip0(got), st["dispatch"], st["block"])
        puts, d2hs = (by_tick(got["host"], st[k]) for k in ("put", "d2h"))
        for tick, mine in calls.items():
            if not mine or len(puts.get(tick, [])) != len(mine) \
                    or len(d2hs.get(tick, [])) != len(mine):
                continue
            for call, put, d2h in zip(mine, puts[tick], d2hs[tick]):
                windows.append((put[0], call[2], seam + ".h2d"))
                windows.append((call[3], d2h[1], seam + ".d2h"))
    windows.sort()
    los = [w[0] for w in windows]
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if reduce.DEVICE_PLANE_RE.match(plane.name):
            continue        # a device plane's instants are another clock's
        for line in plane.lines:
            for ev in line.events:
                if reduce.ANNOTATION_RE.match(ev.name):
                    continue
                s = int(ev.start_ns)
                i = bisect.bisect_right(los, s) - 1
                if i < 0 or s > windows[i][1]:
                    continue
                key = (windows[i][2], plane.name,
                       line.name.split("/")[0], ev.name)
                found.setdefault(key, []).append(
                    (s - windows[i][0], int(ev.duration_ns)))
    n_win = {k: sum(1 for w in windows if w[2] == k)
             for k in {w[2] for w in windows}}
    rows = sorted(found.items(), key=lambda kv: -len(kv[1]))[:top]
    rows.sort(key=lambda kv: (kv[0][0], np.median([o for o, _d in kv[1]])))
    return {"windows": n_win,
            "window_us": {k: round(float(np.median(
                [hi - lo for lo, hi, kind in windows if kind == k])) / 1e3,
                1) for k in n_win},
            "events": [{"window": k[0], "plane": k[1], "line": k[2],
                        "name": k[3], "n": len(v),
                        "offset_us": round(float(np.median(
                            [o for o, _d in v])) / 1e3, 1),
                        "dur_us": round(float(np.median(
                            [d for _o, d in v])) / 1e3, 1)}
                       for k, v in rows]}


if __name__ == "__main__":
    import json
    import sys

    fn = {"report": report, "inventory": inventory}[sys.argv[1]]
    json.dump(fn(sys.argv[2]), sys.stdout, indent=1)
    print()
