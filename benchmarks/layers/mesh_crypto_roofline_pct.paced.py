"""`mesh_crypto_roofline_pct`: the least time the chips could take for
the slice's rows (`opcount.call_cost` of each tick's REAL rows against
`chips` x one chip's HBM peak) over the mesh programs' device time a
chip (the mean over the planes): 100 x least / (device time summed over
the planes).  `crypto_roofline_pct`'s reader sets a tick's WHOLE rows on
one chip's peak against a time already divided by the planes, and would
read `chips` times high here; this one reads the same work the same on
one chip and on four.  HBM-bound by `opcount`, so far below 100 for a
cipher.  None in an untraced run, with fewer than two device planes, or
where no mesh program ran."""

import planes


def read(ctx):
    c = planes.mesh_crypto(ctx)
    return None if c is None else 100.0 * c[2] / c[0]
