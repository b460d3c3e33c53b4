"""`mesh_device_busy_min_pct`: busy share of the traced slice of the
LEAST busy device plane (the union of its `XLA Ops` intervals over the
slice), where `device_idle_pct` is 100 less the mean over the planes.
Under a row-sharded table every chip runs every launch at the lanes of
the hottest chip, so the planes should read alike; a plane that reads
near 0 is a chip that did not work.  None in an untraced run or with
fewer than two device planes."""

import planes


def read(ctx):
    pcts = planes.busy_pcts(ctx)
    return min(pcts) if pcts else None
