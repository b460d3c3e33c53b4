"""`fanout_device_start_lag_p50_ms`: from the start of a tick's
`stage:fanout_dispatch` to the start of the program it launched on chip
0 (found by time through the runtime's `DoEnqueueProgram`, put on the
host clock by `seams.programs`), median over the slice's calls, ms:
pack, the put, the jit call and whatever the runtime does before the
chip begins.  None in an untraced run and off the chip."""

import seams


def read(ctx):
    return seams.lag_p50_ms(ctx, "fanout", "start")
