"""The traced slice's events WITH their stats, for the `layers/*.py`
readers that need more than names and times.

`reduce.py` keeps names, starts and durations only (its neutral form is
what the recorded fixture holds).  The program's spans carry more: every
`stage:<name>` annotation has a `tick` stat (the id all spans of one
tick share) and the counts of what it carried (`rows`, `rows_padded`,
`h2d_bytes`, ...), `stage:tick` has `wall_ns`, and a device event may
carry the `jax.named_scope` path of the operation it ran.  `load` opens
the `.xplane.pb` that `run.py` leaves in `ctx["trace"]["xplane"]` once,
cuts it to the `bench:tick` slice and keeps those events with their
stats.  Nothing here imports the program.

Every reader returns None where there is nothing to read: an untraced
run, or a program whose spans carry no stats (the parent of the PR that
added them).
"""

from __future__ import annotations

import functools

import numpy as np

import reduce


@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    """`{"host": [(name, start_ns, dur_ns, stats)], "modules": [...],
    "lo": ns, "hi": ns}` of one `.xplane.pb`: the `stage:*` host
    annotations and device 0's `XLA Modules` events that START inside
    the `bench:tick` slice `[lo, hi)`, each with its stats as a dict, in
    order of start."""
    from jax.profiler import ProfileData

    host, ticks, modules = [], [], {}
    # `planes` can be walked once only
    for plane in ProfileData.from_file(path).planes:
        device = reduce.DEVICE_PLANE_RE.match(plane.name)
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name != reduce.MODULES_LINE:
                continue
            for ev in line.events:
                if device:
                    modules.setdefault(plane.name, []).append(
                        (ev.name, int(ev.start_ns), int(ev.duration_ns),
                         dict(ev.stats)))
                elif ev.name == "bench:tick":
                    ticks.append((int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns)))
                elif ev.name.startswith("stage:"):
                    host.append((ev.name, int(ev.start_ns),
                                 int(ev.duration_ns), dict(ev.stats)))
    lo = min((s for s, _e in ticks), default=0)
    hi = max((e for _s, e in ticks), default=1 << 62)

    def inside(evs):
        return sorted((e for e in evs if lo <= e[1] < hi),
                      key=lambda e: e[1])

    return {"host": inside(host), "lo": lo, "hi": hi,
            "modules": inside(modules[min(modules)] if modules else [])}


def _varint(buf, i: int):
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def _fields(buf):
    """`(number, value)` of each field of one protobuf message: an int
    for a varint, a memoryview for a length-delimited field; fixed-width
    fields (a stat's double) are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        no, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield no, v
        elif wire == 2:
            ln, i = _varint(buf, i)
            yield no, buf[i:i + ln]
            i += ln
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire}")


def op_paths(path: str, lo_ns: int, hi_ns: int) -> list:
    """`[(duration_ns, strings)]` of device 0's `XLA Ops` events that
    start in `[lo, hi)`, with every string its operation's METADATA
    holds (display name and string stats: where the profiler keeps an
    operation's `jax.named_scope` path, `tf_op`).

    `jax.profiler.ProfileData` shows an event's own stats only (device
    offset and duration), not its metadata's, so this reads the
    `.xplane.pb` itself: `XSpace.planes[1]`, of an `XPlane` `name[2]`,
    `lines[3]`, `event_metadata[4]` and `stat_metadata[5]` (maps: key
    1, value 2); `XLine` `name[2]`, `timestamp_ns[3]`, `events[4]`;
    `XEvent` `metadata_id[1]`, `offset_ps[2]`, `duration_ps[3]`;
    `XEventMetadata` `id[1]`, `name[2]`, `display_name[4]`, `stats[5]`;
    `XStat` `metadata_id[1]`, `str_value[5]`, `ref_value[7]` (the id of
    a stat metadata whose NAME is the string)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for no, plane in _fields(space):
        if no == 1:
            name = next((bytes(v).decode() for f_, v in _fields(plane)
                         if f_ == 2), "")
            if reduce.DEVICE_PLANE_RE.match(name):
                planes.append((name, plane))
    if not planes:
        return []
    lines, emeta, smeta = [], {}, {}
    for no, v in _fields(min(planes)[1]):
        if no == 3:
            lines.append(v)
        elif no in (4, 5):
            entry = dict(_fields(v))
            (emeta if no == 4 else smeta)[entry.get(1, 0)] = entry.get(2)
    stat_name = {k: bytes(dict(_fields(v)).get(2, b"")).decode()
                 for k, v in smeta.items() if v is not None}
    strings = {}
    for k, v in emeta.items():
        mine = []
        for no, f_ in _fields(v or b""):
            if no == 4:
                mine.append(bytes(f_).decode())
            elif no == 5:
                stat = dict(_fields(f_))
                if 5 in stat:
                    mine.append(bytes(stat[5]).decode(errors="replace"))
                elif 7 in stat:
                    mine.append(stat_name.get(stat[7], ""))
        strings[k] = mine
    out = []
    for line in lines:
        head = dict((no, v) for no, v in _fields(line) if no in (2, 3))
        if bytes(head.get(2, b"")).decode() != reduce.OPS_LINE:
            continue
        t0 = head.get(3, 0)
        for no, ev in _fields(line):
            if no != 4:
                continue
            e = dict(_fields(ev))
            start = t0 + e.get(2, 0) // 1000
            if lo_ns <= start < hi_ns:
                out.append((e.get(3, 0) / 1000.0, strings.get(e.get(1), [])))
    return out


def slice_events(ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("xplane"):
        return None
    return load(tr["xplane"])


def count_ratio_pct(ctx, stage: str, num: str, den: str):
    """100 x sum of stat `num` / sum of stat `den` over the slice's
    `stage:<stage>` events."""
    evs = slice_events(ctx)
    if evs is None:
        return None
    n = d = 0
    for name, _s, _d, stats in evs["host"]:
        if name == "stage:" + stage and num in stats and den in stats:
            n += stats[num]
            d += stats[den]
    return 100.0 * n / d if d else None


def residence_p50_ms(ctx, first: str = "ingress", last: str = "egress"):
    """Per tick (the `tick` stat): end of its last `stage:<last>` less
    end of its `stage:<first>` — from the batch in hand to the last
    datagram handed to the kernel.  Median over ticks with both."""
    evs = slice_events(ctx)
    if evs is None:
        return None
    t_in, t_out = {}, {}
    for name, s, d, stats in evs["host"]:
        tick = stats.get("tick")
        if tick is None:
            continue
        if name == "stage:" + first:
            t_in[tick] = s + d
        elif name == "stage:" + last:
            t_out[tick] = max(t_out.get(tick, 0), s + d)
    both = [t_out[t] - t_in[t] for t in t_in if t in t_out]
    return float(np.median(both)) / 1e6 if both else None


def scope_share_pct(ctx, program: str, scope: str):
    """Device time of the `XLA Ops` events that ran under the named
    scope `scope` inside `program` (`jit__fanout_protect`: operations
    whose path starts `jit(_fanout_protect)/`), as a share of that
    program's device time (its `XLA Modules` events).  None where no
    operation of the program ran under the scope: a program without it,
    or a trace whose operations carry no scope path."""
    evs = slice_events(ctx)
    if evs is None:
        return None
    total = sum(d for name, _s, d, _st in evs["modules"]
                if reduce.program_name(name) == program)
    prefix = "jit(" + program[len("jit_"):] + ")/"
    needle = "/" + scope + "/"
    under = sum(d for d, strings in op_paths(ctx["trace"]["xplane"],
                                             evs["lo"], evs["hi"])
                if any(p.startswith(prefix) and needle in p + "/"
                       for p in strings))
    return 100.0 * under / total if under and total else None


def unspanned_pct(ctx, leaves):
    """Median over packet-carrying ticks of the share of `tick_s` that
    lies inside none of `leaves` (the tick's leaf stages, from the
    supervisor's drained ledger).  None where the program books none of
    them beyond the three it always had."""
    t = ctx["ticks"]
    stage = t["stage"]
    if not any(k in stage for k in leaves
               if k not in ("ingress", "recovery", "egress")):
        return None
    busy = t["rx"] > 0
    if not busy.any():
        return None
    inside = sum(stage[k][busy] for k in leaves if k in stage)
    return float(np.median(100.0 * (1.0 - inside / t["tick_s"][busy])))
