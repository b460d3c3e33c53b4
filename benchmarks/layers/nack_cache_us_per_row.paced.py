"""`nack_cache_us_per_row`: what caching one fan-out row for NACK
service costs the tick thread — the time of the slice's
`stage:nack_cache` events over their `rows` stats, in us.  The span
(`sfu_bridge.py:_emit_fanout`) holds the address filter and the insert
into the per-leg retransmission cache; a cost per row here is Python
per delivery (a `bytes` and a dict entry a row read 4.8 / 3.8 us), a
cost per batch reads under 1.  None in an untraced run or where the
span carries no `rows`."""

import xstats


def read(ctx):
    evs = xstats.slice_events(ctx)
    if evs is None:
        return None
    ns = rows = 0
    for name, _s, d, stats in evs["host"]:
        if name == "stage:nack_cache" and stats.get("rows"):
            ns += d
            rows += stats["rows"]
    return ns / 1e3 / rows if rows else None
