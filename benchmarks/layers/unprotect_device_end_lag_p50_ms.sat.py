"""`unprotect_device_end_lag_p50_ms`: from the end of the program a tick's
unprotect call launched on device 0 to the end of its
`stage:unprotect_block`, median over the slice's calls, ms: how long
after the chip finished the tick thread ran again.  None in an untraced
run, off the chip, and on a program without `unprotect_dispatch` /
`unprotect_block`."""

import seams


def read(ctx):
    return seams.lag_p50_ms(ctx, "unprotect", "end")
