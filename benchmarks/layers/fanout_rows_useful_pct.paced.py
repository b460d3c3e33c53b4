"""`fanout_rows_useful_pct`: real rows of the fan-out launches over the
rows the launches were padded to (the row class), from the `rows` and
`rows_padded` stats of the slice's `stage:expand` events.  The device
computes every padded row; the rest of this share is its waste."""

import xstats


def read(ctx):
    return xstats.count_ratio_pct(ctx, "expand", "rows", "rows_padded")
