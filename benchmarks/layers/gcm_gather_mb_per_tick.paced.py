"""`gcm_gather_mb_per_tick`: GHASH matrices the tick's two launches
gather from the key tables, in MB — the `gm_gather_bytes` stats of the
slice's `stage:unprotect_wait` and `stage:fanout_dispatch` events (16
KiB a padded row for the per-row form, a group for the grouped one),
summed per tick, median over the ticks that carry any.  It follows the
row CLASS a tick lands in (256 fan-out rows: 4.2 MB; 1,024: 16.8), not
the packets.  None in an untraced run, or where the spans carry no such
stat (another suite; the parent)."""

import numpy as np

import xstats


def read(ctx):
    evs = xstats.slice_events(ctx)
    if evs is None:
        return None
    per_tick = {}
    for name, _s, _d, stats in evs["host"]:
        if name in ("stage:unprotect_wait", "stage:fanout_dispatch") \
                and "gm_gather_bytes" in stats and "tick" in stats:
            per_tick[stats["tick"]] = (per_tick.get(stats["tick"], 0)
                                       + stats["gm_gather_bytes"])
    return float(np.median(list(per_tick.values()))) / 1e6 \
        if per_tick else None
