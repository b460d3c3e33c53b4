"""`mesh_crypto_device_us_per_pkt`: device time of the mesh programs
(`jit_mesh_*`), summed over the device planes, over the packets the
slice carried (read once, sent once a receiver), in chip-microseconds:
what `crypto_device_us_per_pkt` is on one chip, whose reader finds its
programs by name and does not know these.  Padding to the lanes of the
hottest chip, on every chip, is in it.  None in an untraced run, with
fewer than two device planes, or where no mesh program ran."""

import planes


def read(ctx):
    c = planes.mesh_crypto(ctx)
    return None if c is None else 1e6 * c[0] / c[1]
