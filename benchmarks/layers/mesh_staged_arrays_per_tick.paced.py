"""`mesh_staged_arrays_per_tick`: the arrays a tick's two mesh launches
stage, in and back: `h2d_arrays` + `d2h_arrays` over the tick's
`stage:unprotect_wait`, `stage:fanout_dispatch` and `stage:fanout_d2h`
events (counted by the program from the arrays that cross, each a
block a chip), median over the slice's ticks.  An array an argument
reads 17 (6 + 3 for the unprotect, 6 + 2 for the fan-out); one packed
lane plane each way (core/staging.py) reads 4.  None in an untraced
run, or where the spans carry no such stat."""

import numpy as np

import planes


def read(ctx):
    per_tick = {}
    for stage, keys in (("unprotect_wait", ("h2d_arrays", "d2h_arrays")),
                        ("fanout_dispatch", ("h2d_arrays",)),
                        ("fanout_d2h", ("d2h_arrays",))):
        for t, evs in (planes.by_tick(ctx, stage, *keys) or {}).items():
            per_tick[t] = per_tick.get(t, 0) + sum(sum(e) for e in evs)
    return float(np.median(list(per_tick.values()))) if per_tick else None
