"""`mesh_chip_start_skew_p50_ms`: per fan-out launch of a bridge on a
device mesh (`stage:fanout_dispatch` ..  `stage:fanout_wait`, its
program found by time on EVERY chip, each chip's clock tied to the
host's on its own: `seams.chip_calls`), the start of its program on the
chip that began last less that on the chip that began first; median over
the slice's launches, ms.  None in an untraced run, with fewer than two
device planes (a one-chip bridge) and off the chip."""

import seams


def read(ctx):
    return seams.chip_skew_p50_ms(ctx, "start")
