"""`fanout_only_dropped_per_tick_max`: the most uplink RTP rows one tick
of the traced slice dropped at the loop's fanout-only mask (a visitor's
media is forwarded to nobody): the largest `fanout_only_dropped` stat of
a `stage:demux` event.  The span carries the stat on a bridge that has
listener rows, so 0 in the window says both that no visitor sent media
there (their one packet each is in the lead-in) and that the mask, not
the router, is what silences them.  None in an untraced run and on a
bridge without listener rows (or a program without the count)."""

import xstats


def read(ctx):
    evs = xstats.slice_events(ctx)
    if evs is None:
        return None
    got = [stats["fanout_only_dropped"]
           for name, _s, _d, stats in evs["host"]
           if name == "stage:demux" and "fanout_only_dropped" in stats]
    return float(max(got)) if got else None
