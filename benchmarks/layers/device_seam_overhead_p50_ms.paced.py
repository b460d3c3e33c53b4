"""`device_seam_overhead_p50_ms`: what the tick thread spends round its two
device calls that is not the device's: per tick, `unprotect_wait` +
`fanout_dispatch` + `fanout_wait` + `fanout_d2h` (the slice's `stage:`
events) less the device time of the programs those calls launched (chip
0's, found by time through the runtime's `DoEnqueueProgram`:
`seams.pair`); median over the slice's ticks, ms.  The one number a
change to the seam (the plane kept on the device, an asynchronous copy
back, a tick a shard) should push down.  None in an untraced run and off
the chip."""

import seams


def read(ctx):
    return seams.overhead_p50_ms(ctx)
