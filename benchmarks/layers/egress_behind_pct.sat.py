"""`egress_behind_pct`: fan-out bursts handed to the engine's egress
worker while the burst before them was still queued or in flight, as a
share of all bursts handed over, from the `behind` and `queued` stats
of the slice's `stage:egress` events.  0 means every send ended before
the next tick's fan-out came back: the overlap engaged and the tick
thread never caught up with the worker.  A high reading means the
worker is the pace (the next step is then the send itself: GSO, a
gather per receiver, more workers).  A program that sends on the tick
thread books neither stat and the metric is left out."""

import xstats


def read(ctx):
    return xstats.count_ratio_pct(ctx, "egress", "behind", "queued")
