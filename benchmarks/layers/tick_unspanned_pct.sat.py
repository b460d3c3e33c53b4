"""`tick_unspanned_pct`: how much of a tick lies in no leaf span.

Median over the window's packet-carrying ticks of
100 x (1 - sum of the leaf stages' seconds / `tick_s`), both from the
supervisor (`sup.last_ledger`, `sup.last_tick_s`).  The leaves are the
spans of an `SfuBridge` tick that have no child span, less `supervise`
and `gc`, which lie outside `tick_s` or inside another leaf.  A reading
over 10 means a stretch of the tick that no `stage_*` metric can see:
the program then needs one more leaf, not a wider container.
"""

import xstats

LEAVES = ("ingress", "demux", "unprotect_host", "unprotect_wait", "parse",
          "recovery", "bwe", "abs_send_time", "route", "expand",
          "fanout_dispatch", "fanout_wait", "fanout_d2h", "nack_cache",
          "egress")


def read(ctx):
    return xstats.unspanned_pct(ctx, LEAVES)
