"""`ingress_pkts_per_tick_p95`: the 95th percentile of the packets one
tick read off the bridge's socket, over the window's ticks that read
any.  The mean (`ingress_pkts_per_tick`) says what a tick carries; this
says how far the busier ticks stand above it: talk spurts that overlap,
or a tick that found the backlog of a pause.  It is what decides the
row class the busier ticks' fan-out pads to (packets x receivers), and
with it the tail of the tick and of the added latency."""

import numpy as np


def read(ctx):
    rx = ctx["ticks"]["rx"]
    rx = rx[rx > 0]
    return float(np.percentile(rx, 95)) if len(rx) else None
