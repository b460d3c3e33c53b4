"""`mesh_hot_shard_share_pct`: the share of a tick's fan-out rows that
the hottest chip owns (`rows_hottest_shard` of its
`stage:fanout_dispatch`, of each launch where a tick has one a width
class, over `rows` of its `stage:expand`), median over
the slice's ticks, in %.  On four chips 25 is even; 100 is one chip
doing the tick while three compute its padding.  None in an untraced
run, or where the spans carry no `rows_hottest_shard` (a one-chip
bridge; the parent)."""

import numpy as np

import planes


def read(ctx):
    rows = planes.by_tick(ctx, "expand", "rows")
    hot = planes.by_tick(ctx, "fanout_dispatch", "rows_hottest_shard")
    if not rows or not hot:
        return None
    share = []
    for t, evs in hot.items():
        n = sum(r for r, in rows.get(t, ()))
        if n:
            share.append(100.0 * sum(h for h, in evs) / n)
    return float(np.median(share)) if share else None
