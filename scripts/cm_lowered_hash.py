#!/usr/bin/env python3
"""Two sha256 over the lowered text of the served CM programs, at every
row class x the three width classes.  `stay`: the programs that still
specialise on a uniform payload offset (`_unprotect_rtp_packed` and its
donated twin, `_protect_rtp_dev`, at the offsets 12, 20 and None) and
the SRTCP pair: 165 programs.  `fanout`: `_fanout_protect`, one program
a shape since PR 43 (its payload offset is a word of the plane): 15.
A PR whose issue says "the lowered CM programs are byte-equal" runs it
on both trees and compares the line it means:

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
    stay, fanout, n_stay, n_fanout = hashlib.sha256(), hashlib.sha256(), 0, 0
    for rows in ROW_CLASSES:
        rowv = [s((rows,), jnp.int32)] * 2
        iv = s((rows, 16), jnp.uint8)
        for cls in LENGTH_CLASSES:
            width = cls + CLASS_HEADROOM
            plane = s((rows, width + staging.TAIL), jnp.uint8)
            data = s((rows, width), jnp.uint8)
            fanout.update(tr._fanout_protect.lower(
                rk, mid, plane, 10, True).as_text().encode())
            n_fanout += 1
            texts = [
                ctx._protect_rtcp_dev.lower(
                    rk, mid, *rowv[:1], data, *rowv[:1], iv,
                    s((rows,), jnp.uint32), 10, True),
                ctx._unprotect_rtcp_dev.lower(
                    rk, mid, *rowv[:1], data, *rowv[:1], iv, 10, True)]
            for off in (12, 20, None):
                texts += [fn.lower(rk, mid, plane, 10, True, off_const=off)
                          for fn in (ctx._unprotect_rtp_packed,
                                     ctx._unprotect_rtp_packed_donated)]
                texts.append(ctx._protect_rtp_dev.lower(
                    rk, mid, *rowv[:1], data, *rowv, iv,
                    s((rows,), jnp.uint32), 10, True, off_const=off))
            for t in texts:
                stay.update(t.as_text().encode())
            n_stay += len(texts)
    print("stay", n_stay, "programs", aes.get_core(), stay.hexdigest())
    print("fanout", n_fanout, "programs", aes.get_core(),
          fanout.hexdigest())
    return 0


if __name__ == "__main__":
    sys.exit(main())
