"""`mesh_lanes_useful_pct`: a tick's REAL fan-out rows (`rows` of its
`stage:expand`) over the lanes its fan-out launch computes on all
chips (`shards` x `lanes` of its `stage:fanout_dispatch`: every chip is
padded to the row class of the hottest chip), median over the slice's
ticks, in %.  Both paddings of a mesh launch in one number: the row
class and the skew between the chips.  None in an untraced run, or where
the spans carry no `lanes` (a one-chip bridge; the parent)."""

import numpy as np

import planes


def read(ctx):
    rows = planes.by_tick(ctx, "expand", "rows")
    launches = planes.by_tick(ctx, "fanout_dispatch", "shards", "lanes")
    if not rows or not launches:
        return None
    share = []
    for t, evs in launches.items():
        lanes = sum(shards * lanes for shards, lanes in evs)
        if t in rows and lanes:
            share.append(100.0 * sum(r for r, in rows[t]) / lanes)
    return float(np.median(share)) if share else None
