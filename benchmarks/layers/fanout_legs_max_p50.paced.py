"""`fanout_legs_max_p50`: the longest receiver list of a tick's fan-out:
the median `legs_max` stat of the traced slice's `stage:expand` events
(the launches after a tick's first book an `expand` without it).  63 in
a meeting of 64; 511 says a webinar room's whole list reached the
translator as ONE packet's rows, which `fanout_launches_per_tick_max` 1
and `fanout_rows_useful_pct` 99.8 then say went out as one launch of the
512-row class.  None in an untraced run or where the span carries no
`legs_max`."""

import numpy as np

import xstats


def read(ctx):
    evs = xstats.slice_events(ctx)
    if evs is None:
        return None
    got = [stats["legs_max"] for name, _s, _d, stats in evs["host"]
           if name == "stage:expand" and "legs_max" in stats]
    return float(np.median(got)) if got else None
