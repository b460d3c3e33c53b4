"""Every device plane of the traced slice, for the cells that run on
more than one chip.

`xstats.load` keeps device 0's `XLA Modules` events and
`reduce.reduce_trace` one mean over the planes: enough where one chip
does all the work.  A bridge on a device mesh launches each program on
every chip at once, and what its readers ask is what EACH chip did:
how busy the least and the most busy chip were, and how much device
time the mesh programs (`jit_mesh_*`, `libjitsi_tpu/mesh/`) took summed
over the chips.  `of` opens the `.xplane.pb` that `run.py` leaves in
`ctx["trace"]["xplane"]` (or a recorded slice in `reduce.py`'s neutral
form, `*.json.gz`), cuts it to the `bench:tick` slice as `run.py` does,
and keeps, a device plane, the busy time and the time by program.
Nothing here imports the program.

Every reader returns None where there is nothing to read: an untraced
run, a trace with fewer than two device planes, or one in which no
mesh program ran (a one-chip bridge; the parent of the PR that named
the programs).
"""

from __future__ import annotations

import functools

import opcount
import reduce
import xstats

MESH_PROGRAM_PREFIX = "jit_mesh_"


def per_plane(trace: dict) -> dict:
    """`{plane: {"busy_s", "program_s": {program: seconds}}}` and the
    slice's length, of a trace in `reduce.py`'s neutral form already
    cut to its slice.  Busy is the union of the plane's `XLA Ops`
    intervals (its `XLA Modules` where it has no op line)."""
    lo, hi = None, None
    for s, e in _spans(trace):
        lo = s if lo is None else min(lo, s)
        hi = e if hi is None else max(hi, e)
    planes = {}
    for name, lines in trace["device"].items():
        ops = lines.get(reduce.OPS_LINE) or lines.get(
            reduce.MODULES_LINE) or []
        busy = sum(e - s for s, e in reduce.merge_intervals(
            [s, s + d] for _n, s, d in ops))
        programs = {}
        for n, _s, d in lines.get(reduce.MODULES_LINE, []):
            p = reduce.program_name(n)
            programs[p] = programs.get(p, 0.0) + d / 1e9
        planes[name] = {"busy_s": busy / 1e9, "program_s": programs}
    return {"window_s": (hi - lo) / 1e9 if lo is not None else 0.0,
            "planes": planes}


def _spans(trace: dict):
    """(start, end) of every device and host event of `trace`."""
    for lines in trace["device"].values():
        for evs in lines.values():
            for _n, s, d in evs:
                yield s, s + d
    for evs in trace["host"].values():
        for s, d in evs:
            yield s, s + d


@functools.lru_cache(maxsize=2)
def _load(path: str) -> dict:
    trace = (reduce.load_fixture(path) if path.endswith(".json.gz")
             else reduce.load_xplane(path))
    ticks = trace["host"].get("bench:tick")
    if ticks:
        trace = reduce.clip_trace(trace, min(s for s, _d in ticks),
                                  max(s + d for s, d in ticks))
    return per_plane(trace)


def of(ctx):
    """`per_plane` of the run's traced slice; None in an untraced run
    or where the trace holds fewer than two device planes."""
    tr = ctx.get("trace")
    if not tr or not tr.get("xplane"):
        return None
    got = _load(tr["xplane"])
    return got if len(got["planes"]) >= 2 and got["window_s"] > 0 \
        else None


def busy_pcts(ctx):
    """Busy share of the slice, a device plane, in %."""
    got = of(ctx)
    if got is None:
        return None
    return [100.0 * p["busy_s"] / got["window_s"]
            for p in got["planes"].values()]


def mesh_crypto(ctx):
    """(device seconds of the mesh programs SUMMED over the planes,
    packets they carried, least seconds by the peaks for the slice's
    rows on one chip, chips).  Packets carried and the least time are
    `readers._crypto`'s: those the loop read (each opened once) plus
    the fan-out rows sent (each protected once); `opcount.call_cost` of
    a tick's real rows against the HBM peak.  Device time a chip is the
    sum over `chips`, and the least time a chip the one-chip least time
    over `chips`: their ratio reads the same work the same on one chip
    and on four."""
    got = of(ctx)
    if got is None:
        return None
    dev_s = sum(t for p in got["planes"].values()
                for name, t in p["program_s"].items()
                if name.startswith(MESH_PROGRAM_PREFIX))
    sl = ctx["trace"].get("slice")
    if dev_s <= 0 or not sl:
        return None
    pkts = sl["rx_packets"] + sl["forwarded"]
    if pkts <= 0:
        return None
    sysm = ctx["system"]
    least = 0.0
    for n in sl["rx"][sl["rx"] > 0].tolist():
        for rows in (n, n * sysm["fanout"]):
            least += opcount.least_time_s(
                opcount.call_cost(sysm["suite"], rows,
                                  sysm["mean_length"]), ctx["peaks"])
    return dev_s, pkts, least, len(got["planes"])


def by_tick(ctx, stage: str, *keys: str):
    """`{tick: [(stat, ...), ...]}`: for each tick of the slice, one
    tuple of the stats `keys` per `stage:<stage>` event that carries
    them all (a stage runs once per width class, so a tick may hold
    more than one: a launch's `lanes` or `shards` are its own and are
    never summed with another's); None in an untraced run."""
    evs = xstats.slice_events(ctx)
    if evs is None:
        return None
    out = {}
    for name, _s, _d, stats in evs["host"]:
        if name == "stage:" + stage and "tick" in stats \
                and all(k in stats for k in keys):
            out.setdefault(stats["tick"], []).append(
                tuple(stats[k] for k in keys))
    return out
