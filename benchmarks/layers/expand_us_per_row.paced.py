"""`expand_us_per_row`: what building one (packet, receiver) row of the
fan-out costs the tick thread: the time of the slice's `stage:expand`
events over their `rows` stats, in us (as `nack_cache_us_per_row` is
read).  The span holds the route lists' concatenation, the per-row
vectors, the gather of the packet bytes into the staging plane a launch
and the IVs; where a tick has several launches the later launches'
expansions are events of their own that carry no `rows`, and their time
counts.  In a conference of 8 the per-tick part leads (2-3 us a row);
at 63 receivers a packet the per-row part is what is left.  None in an
untraced run or where the span carries no `rows`."""

import xstats


def read(ctx):
    evs = xstats.slice_events(ctx)
    if evs is None:
        return None
    ns = rows = 0
    for name, _s, d, stats in evs["host"]:
        if name == "stage:expand":
            ns += d
            rows += stats.get("rows", 0)
    return ns / 1e3 / rows if rows else None
