"""`bridge_residence_p50_ms`: how long a tick's packets stay in the
bridge once it has them — per tick, end of its last `stage:egress` less
end of its `stage:ingress`, matched by the spans' `tick` stat, on the
profiler's clock; median over the slice's ticks that have both.  What
`added_latency_p50_ms` holds beyond it is the wait for the tick that
reads the packet, the socket and the client."""

import xstats


def read(ctx):
    return xstats.residence_p50_ms(ctx)
