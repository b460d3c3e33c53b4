"""`device_launches_per_tick`: how many programs a tick starts on the
device — the slice's `XLA Modules` events (device 0) over its
`stage:tick` events that carried packets (`rx` > 0; an empty tick
launches nothing).  The work of a tick is two programs, the unprotect
and the fan-out; whatever reads above 2 is staging that became a
program of its own (a `convert_element_type` per argument whose dtype
the host had not settled).  None in an untraced run, or where the
program writes no `stage:tick`, or where the trace holds no device
plane (off the chip)."""

import xstats


def read(ctx):
    evs = xstats.slice_events(ctx)
    if evs is None or not evs["modules"]:
        return None
    ticks = sum(1 for name, _s, _d, stats in evs["host"]
                if name == "stage:tick" and stats.get("rx", 0) > 0)
    return len(evs["modules"]) / ticks if ticks else None
