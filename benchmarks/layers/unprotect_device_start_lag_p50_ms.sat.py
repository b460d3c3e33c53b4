"""`unprotect_device_start_lag_p50_ms`: from the start of a tick's
`stage:unprotect_dispatch` to the start of the program it launched on
chip 0 (found by time through the runtime's `DoEnqueueProgram`, put on
the host clock by `seams.programs`), median over the slice's calls, ms:
pack, the put, the jit call and whatever the runtime does before the
chip begins.  None in an untraced run, off the chip, and on a program
without `unprotect_dispatch` / `unprotect_block`."""

import seams


def read(ctx):
    return seams.lag_p50_ms(ctx, "unprotect", "start")
