"""`mesh_chip_end_skew_p50_ms`: per fan-out launch of a bridge on a device
mesh (`stage:fanout_dispatch` ..  `stage:fanout_wait`, its program found
by time on EVERY chip, each chip's clock tied to the host's on its own:
`seams.chip_calls`), the end of its program on the chip that finished
last, the one the tick thread's `block_until_ready` waits for, less that
on the chip that finished first; median over the slice's launches, ms.
None in an untraced run, with fewer than two device planes (a one-chip
bridge) and off the chip."""

import seams


def read(ctx):
    return seams.chip_skew_p50_ms(ctx, "end")
