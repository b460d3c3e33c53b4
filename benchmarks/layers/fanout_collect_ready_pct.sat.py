"""`fanout_collect_ready_pct`: fan-out launches whose every array was
ready when the tick thread came to collect them, as a share of all
launches collected, from the `ready` and `collected` stats of the
slice's `stage:fanout_wait` events (as `egress_behind_pct` reads
`behind` over `queued` of `stage:egress`).  The bridge dispatches a
tick's fan-out and collects it in the NEXT tick, after that tick's
`ingress` and `demux`: 100 means the host work in between hid the
whole launch and the wait was none; 0 that every collection still
waited (a program longer than the stretch it runs under: the next step
is then a shorter program or a later collection).  The counter that
says the overlap engages.  A program that waits for its fan-out in the
tick that dispatched it books neither stat and the metric is left
out."""

import xstats


def read(ctx):
    return xstats.count_ratio_pct(ctx, "fanout_wait", "ready", "collected")
