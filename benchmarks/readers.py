"""Reader kinds for per-layer metrics.

A per-layer metric is one file `layers/<metric>.json` naming one of the
kinds below with its parameters, or `layers/<metric>.py` with a
`read(ctx)` of its own.  A reader that finds nothing to read returns
None and the harness leaves the metric out of the result line.

`ctx` (built by `run.py` after a window):

  ticks     per-tick arrays over the window: `tick_s` (host wall of
            `sup.tick()`), `rx` (packets the loop read in that tick),
            `stage` {name: seconds} from the supervisor's drained
            stage ledger (`PipelineTracer` spans, inclusive time)
  counters  deltas of program counters over the window
  client    what the generator and the clients saw (`loadgen.analyze`)
  trace     `reduce.reduce_trace` of the traced slice, plus `slice`:
            the slice's own per-tick `rx` and counter deltas; None in
            an untraced run
  system    `default_deadline_ms`, `fanout`, `suite`, `mean_length`
  peaks     the row of `peaks.json` for this device
"""

from __future__ import annotations

import re

import numpy as np

import opcount


def _quantile(a, q):
    a = np.asarray(a, dtype=np.float64)
    return float(np.percentile(a, q)) if len(a) else None


def tick_quantile(ctx, spec):
    """Quantile of the per-tick wall time, over ticks that carried
    packets unless `all_ticks`."""
    t = ctx["ticks"]
    sel = slice(None) if spec.get("all_ticks") else t["rx"] > 0
    v = _quantile(t["tick_s"][sel], spec["q"])
    return None if v is None else v * spec.get("scale", 1.0)


def stage_quantile(ctx, spec):
    """Quantile of one stage's per-tick seconds (ticks in which the
    stage ran)."""
    a = ctx["ticks"]["stage"].get(spec["stage"])
    if a is None:
        return None
    a = a[a > 0]
    v = _quantile(a, spec["q"])
    return None if v is None else v * spec.get("scale", 1.0)


def tick_over_deadline_pct(ctx, spec):
    """Share of packet-carrying ticks over the PRODUCT'S default
    deadline (`SupervisorConfig().deadline_ms`), whatever deadline the
    configuration runs under."""
    t = ctx["ticks"]
    a = t["tick_s"][t["rx"] > 0]
    if not len(a):
        return None
    return 100.0 * float(
        (a * 1e3 > ctx["system"]["default_deadline_ms"]).mean())


def counter_ratio(ctx, spec):
    """counter `num` over counter `den`, both deltas over the window."""
    c = ctx["counters"]
    den = c.get(spec["den"], 0)
    return float(c[spec["num"]]) / den if den else None


def client_value(ctx, spec):
    v = ctx["client"].get(spec["key"])
    return None if v is None else float(v) * spec.get("scale", 1.0)


def offered_loss_pct(ctx, spec):
    c = ctx["client"]
    return 100.0 * c["lost"] / c["offered"] if c["offered"] else None


def trace_value(ctx, spec):
    tr = ctx.get("trace")
    if not tr or spec["key"] not in tr:
        return None
    return float(tr[spec["key"]]) * spec.get("scale", 1.0)


def _crypto(ctx, spec):
    """(device seconds of the programs matching `programs`, packets
    they carried, least seconds by the peaks) over the traced slice.
    Packets carried: those the loop read (each opened once) plus the
    fan-out rows sent (each protected once)."""
    tr = ctx.get("trace")
    if not tr or not tr.get("program_s"):
        return None
    pats = [re.compile(p) for p in spec["programs"]]
    dev_s = sum(t for name, t in tr["program_s"].items()
                if any(p.search(name) for p in pats))
    sl = tr["slice"]
    pkts = sl["rx_packets"] + sl["forwarded"]
    if dev_s <= 0 or pkts <= 0:
        return None
    sysm = ctx["system"]
    least = 0.0
    for n in sl["rx"][sl["rx"] > 0].tolist():
        for rows in (n, n * sysm["fanout"]):
            least += opcount.least_time_s(
                opcount.call_cost(sysm["suite"], rows,
                                  sysm["mean_length"]), ctx["peaks"])
    return dev_s, pkts, least


def crypto_device_us_per_pkt(ctx, spec):
    c = _crypto(ctx, spec)
    return None if c is None else 1e6 * c[0] / c[1]


def crypto_roofline_pct(ctx, spec):
    c = _crypto(ctx, spec)
    return None if c is None else 100.0 * c[2] / c[0]


KINDS = {f.__name__: f for f in (
    tick_quantile, stage_quantile, tick_over_deadline_pct, counter_ratio,
    client_value, offered_loss_pct, trace_value,
    crypto_device_us_per_pkt, crypto_roofline_pct)}
