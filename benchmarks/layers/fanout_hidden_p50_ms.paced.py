"""`fanout_hidden_p50_ms`: the tick thread's time between a fan-out
launch's dispatch (its jit call had returned) and the start of the
`fanout_wait` that collects it, in ms: the `hidden_us` stat of the
slice's `stage:fanout_wait` events, median over the launches.  It is
the host work the launch, the runtime's notice of its end and the copy
back run under (`supervise`, the reap, the next tick's `ingress` and
`demux`); what of the launch is longer than this is what
`stage_fanout_wait_p50_ms` still reads.  None in an untraced run or on
a program whose `fanout_wait` carries no such stat (one that waits in
the tick that dispatched)."""

import numpy as np

import xstats


def read(ctx):
    evs = xstats.slice_events(ctx)
    if evs is None:
        return None
    us = [stats["hidden_us"] for name, _s, _d, stats in evs["host"]
          if name == "stage:fanout_wait" and "hidden_us" in stats]
    return float(np.median(us)) / 1e3 if us else None
