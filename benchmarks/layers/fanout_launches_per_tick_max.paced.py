"""`fanout_launches_per_tick_max`: the most device calls one tick's
fan-out took in the traced slice: the largest `launches` stat of a
`stage:expand` event.  The program cuts a tick's (packet, receiver) rows
into launches of at most its largest warmed row class (4,096), so 1
means no tick of the slice outgrew one class (the split never engaged
there) and 3 or so is a tick that found a backlog (the profiler's start
stalls the tick thread) and went out in three launches, none of a shape
the ladder had not warmed.  None in an untraced run, or where the span
carries no `launches` (the parent of the PR that added the split)."""

import xstats


def read(ctx):
    evs = xstats.slice_events(ctx)
    if evs is None:
        return None
    got = [stats["launches"] for name, _s, _d, stats in evs["host"]
           if name == "stage:expand" and "launches" in stats]
    return float(max(got)) if got else None
