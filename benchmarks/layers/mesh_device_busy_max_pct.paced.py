"""`mesh_device_busy_max_pct`: busy share of the traced slice of the
MOST busy device plane; see `mesh_device_busy_min_pct`.  None in an
untraced run or with fewer than two device planes."""

import planes


def read(ctx):
    pcts = planes.busy_pcts(ctx)
    return max(pcts) if pcts else None
