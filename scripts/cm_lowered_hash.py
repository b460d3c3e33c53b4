#!/usr/bin/env python3
"""One sha256 over the lowered text of the CM programs a served tick
launches (`_fanout_protect`, `_unprotect_rtp_packed` and its donated
twin), at every row class x the three width classes x the payload
offsets the ladder warms: 135 programs.  A PR whose issue says "the
lowered CM programs are byte-equal" runs it on both trees and compares:

    cd /tmp && for t in <parent checkout> <this checkout>; do
        JAX_PLATFORMS=cpu PYTHONPATH=$t python3 $t/scripts/cm_lowered_hash.py [core]
    done

`core` (e.g. `bitsliced_tower`, the chip's) overrides the platform's AES
core (`kernels/aes.py:get_core`); without it the CPU's `table`.  The
hash depends on the JAX version: compare trees, do not pin it.
"""

import hashlib
import sys

import jax
import jax.numpy as jnp

from libjitsi_tpu.core import staging
from libjitsi_tpu.core.packet import (CLASS_HEADROOM, LENGTH_CLASSES,
                                      ROW_CLASSES)
from libjitsi_tpu.kernels import aes
from libjitsi_tpu.sfu import translator as tr
from libjitsi_tpu.transform.srtp import context as ctx


def main() -> int:
    if len(sys.argv) > 1:
        aes.set_core(sys.argv[1])
    s = jax.ShapeDtypeStruct
    rk, mid = s((10240, 11, 16), jnp.uint8), s((10240, 2, 5), jnp.uint32)
    h, n = hashlib.sha256(), 0
    for rows in ROW_CLASSES:
        for cls in LENGTH_CLASSES:
            plane = s((rows, cls + CLASS_HEADROOM + staging.TAIL),
                      jnp.uint8)
            for off in (12, 20, None):
                for fn in (tr._fanout_protect, ctx._unprotect_rtp_packed,
                           ctx._unprotect_rtp_packed_donated):
                    h.update(fn.lower(rk, mid, plane, 10, True,
                                      off_const=off).as_text().encode())
                    n += 1
    print(n, "programs", aes.get_core(), h.hexdigest())
    return 0


if __name__ == "__main__":
    sys.exit(main())
