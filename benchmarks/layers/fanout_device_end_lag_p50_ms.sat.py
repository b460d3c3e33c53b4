"""`fanout_device_end_lag_p50_ms`: from the end of the program a tick's
fan-out call launched on device 0 to the end of its `stage:fanout_wait`,
median over the slice's calls, ms: how long after the chip finished the
tick thread ran again.  None in an untraced run and off the chip."""

import seams


def read(ctx):
    return seams.lag_p50_ms(ctx, "fanout", "end")
