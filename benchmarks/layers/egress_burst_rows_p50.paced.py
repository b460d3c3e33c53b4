"""`egress_burst_rows_p50`: the datagrams of one hand-over to the egress
worker: the median `rows` stat of the traced slice's `stage:egress`
events (one a fan-out launch).  The worker sends a burst one datagram
after another (8-10 us each on the chip machines' kernel), so the median
delivery waits half a burst behind its hand-over: 7 rows a packet in a
conference of 8, 63 in a meeting of 64, 511 in a webinar room of 512,
where the burst is a fifth of every 20 ms.  None in an untraced run
or where the span carries no `rows`."""

import numpy as np

import xstats


def read(ctx):
    evs = xstats.slice_events(ctx)
    if evs is None:
        return None
    got = [stats["rows"] for name, _s, _d, stats in evs["host"]
           if name == "stage:egress" and "rows" in stats]
    return float(np.median(got)) if got else None
