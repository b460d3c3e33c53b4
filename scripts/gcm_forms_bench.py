#!/usr/bin/env python3
"""Per-row against grouped GHASH for the AEAD_AES_128_GCM RTP calls, at
the shapes a served bridge meets: the measurement `_gcm_form_grid`'s
thresholds in `transform/srtp/context.py` come from (PERF.md, PR 31).

    chiprun -- python3 scripts/gcm_forms_bench.py [part2]

A 10,240-row table (the matrices a deployment gathers from), width 224
(the 192-byte length class plus headroom), uniform AAD 12.  Per row
class: the per-row program and the grouped one at the grids that
`_gcm_grid` yields — (rows/4, 4) is what the old ladder warmed (every
stream four packets, the grouped form's best case, no padding);
(rows/2, 4) and (rows, 2) are what `bucket_by_size`'s cycling makes of a
live tick whose packets come from distinct streams (3/8 and 3/4 of the
class real, the grid padded to twice the rows).  The per-row unprotect
and the per-row fan-out are the served programs, each on one packed
plane (`core/staging.py`; the fan-out without its donation, so that one
staged plane serves every launch); the grouped and leg-major forms and
`protect_rtp` take an array an argument.  Arguments are staged
once, the programs compile side by side, then each runs alone:
milliseconds a launch over `ITERS` back-to-back launches, the queue
drained once at the end.  One JSON line last; the table also goes to
`chiprun_out/gcm_forms.json`.

`part2` measures what the first table left open instead: grids of 8 to
32 rows a stream (no padding: what a canonical grouped grid could win),
16,384 rows (four times the largest row class, where the per-row form
gathers 268 MB of matrices), and the fan-out's leg-major form against
its per-row form at (16 legs, 16 packets) and (256, 16), AAD 20.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from libjitsi_tpu.core import staging  # noqa: E402
from libjitsi_tpu.core.packet import _round_rows  # noqa: E402
from libjitsi_tpu.transform.srtp import context as ctx  # noqa: E402
from libjitsi_tpu.transform.srtp.policy import SrtpProfile  # noqa: E402
from libjitsi_tpu.utils.compile_cache import compile_concurrently  # noqa: E402

CAPACITY = 10240
WIDTH = 224
AAD = 12
ITERS = 30
ROWS = (64, 256, 1024, 4096)


def cases(rows: int):
    """(label, stream ids [rows]) per grid of the docstring."""
    rng = np.random.default_rng(rows)

    def cycled(n_real):
        real = rng.choice(CAPACITY, n_real, replace=False)
        return np.resize(real, rows)

    yield "warm_p4", np.repeat(rng.choice(CAPACITY, rows // 4,
                                          replace=False), 4)
    yield "live_3of8", cycled(3 * rows // 8)
    yield "live_3of4", cycled(3 * rows // 4)


def packed(tab_rk, tab_gm, stream, data, length, off, iv):
    """The arguments of a packed per-row program: the two key tables
    and one staged plane."""
    plane = staging.alloc(len(stream), WIDTH)
    plane[:, :WIDTH] = data
    staging.pack(plane, (stream, length, off), iv)
    return tab_rk, tab_gm, jax.device_put(plane)


def dev(*arrays):
    return tuple(jnp.asarray(a) for a in arrays)


def part2_jobs(tab_rk, tab_gm, rng, tiny: bool):
    from libjitsi_tpu.sfu import translator as tr

    # the served per-row fan-out donates its plane: time its undonated
    # twin, so that one staged plane serves every launch
    per_row = jax.jit(tr._fanout_protect_gcm.__wrapped__)

    def fanout_per_row(*a, aad_const=None):
        # one form since PR 43: the AAD length is a word of the plane
        return per_row(*a)

    def args(rows, aad):
        return (rng.integers(0, 256, (rows, WIDTH), dtype=np.uint8),
                rng.integers(68, 189, rows, dtype=np.int32),
                np.full(rows, aad, dtype=np.int32),
                rng.integers(0, 256, (rows, 12), dtype=np.uint8))

    jobs = []
    shapes = ([(256, 8), (256, 16)] if tiny else
              [(1024, 8), (1024, 16), (1024, 32), (4096, 8), (4096, 16),
               (16384, 4)])
    for rows, p in shapes:
        stream = np.repeat(rng.choice(CAPACITY, rows // p, replace=False),
                           p)
        gr, us, inv = ctx._gcm_grid(stream.astype(np.int64))
        host = args(rows, AAD)
        base = (tab_rk, tab_gm, jnp.asarray(stream, dtype=jnp.int32),
                *dev(*host))
        if rows > 4096:
            jobs.append((rows, "any", "per_row", "unprotect",
                         ctx._unprotect_gcm_dev,
                         packed(tab_rk, tab_gm, stream, *host), None,
                         AAD))
        jobs.append((rows, f"p{p}", "grouped", "unprotect",
                     ctx._unprotect_gcm_grouped_dev,
                     base + (jnp.asarray(gr),
                             jnp.asarray(us, dtype=jnp.int32),
                             jnp.asarray(inv)), gr.shape, AAD))
    for legs, pk in ([(16, 16)] if tiny else [(16, 16), (256, 16)]):
        rr = rng.choice(CAPACITY, legs, replace=False)
        rows = legs * pk
        data, length, off, iv = args(rows, 20)
        jobs.append((rows, f"legs{legs}x{pk}", "per_row", "fanout",
                     fanout_per_row,
                     packed(tab_rk, tab_gm, np.tile(rr, pk), data, length,
                            off, iv), None, 20))
        jobs.append((rows, f"legs{legs}x{pk}", "leg_major", "fanout",
                     tr._fanout_protect_gcm_legs,
                     (tab_rk, tab_gm, jnp.asarray(rr, dtype=jnp.int32),
                      *dev(data[:pk], length[:pk],
                           iv.reshape(legs, pk, 12))), (legs, pk), 20))
    # 7 legs x 2 packets, a small conference's single-sender tick: 16
    # per-row rows against the (16, 16) leg-major grid above
    jobs.append((16, "legs7x2", "per_row", "fanout", fanout_per_row,
                 packed(tab_rk, tab_gm, np.arange(16), *args(16, 20)),
                 None, 20))
    return jobs


def main() -> int:
    global CAPACITY, ROWS, ITERS
    part2 = "part2" in sys.argv[1:]
    tiny = [a for a in sys.argv[1:] if a.isdigit()]
    if tiny:                            # rehearsal off the chip
        CAPACITY, ROWS, ITERS = int(tiny[0]), (64, 256), 2
    chip = jax.devices()[0]
    print(f"device {chip.platform} {chip.device_kind}", flush=True)
    table = ctx.SrtpStreamTable(CAPACITY, SrtpProfile.AEAD_AES_128_GCM)
    rng = np.random.default_rng(1)
    table.add_streams(np.arange(CAPACITY),
                      rng.integers(0, 256, (CAPACITY, 16), dtype=np.uint8),
                      rng.integers(0, 256, (CAPACITY, 12), dtype=np.uint8))
    tab_rk, tab_gm, _, _ = table._device()
    jobs = []           # (rows, case, form, op, fn, args, grid, aad)
    if part2:
        jobs = part2_jobs(tab_rk, tab_gm, rng, bool(tiny))
    for rows in () if part2 else ROWS:
        assert _round_rows(rows) == rows
        host = (rng.integers(0, 256, (rows, WIDTH), dtype=np.uint8),
                rng.integers(68, 189, rows, dtype=np.int32),
                np.full(rows, AAD, dtype=np.int32),
                rng.integers(0, 256, (rows, 12), dtype=np.uint8))
        hdev = dev(*host)
        for label, stream in cases(rows):
            grid = ctx._gcm_grid(stream.astype(np.int64))
            base = (tab_rk, tab_gm, jnp.asarray(stream, dtype=jnp.int32),
                    *hdev)
            for op, per_row, grouped in (
                    ("unprotect", ctx._unprotect_gcm_dev,
                     ctx._unprotect_gcm_grouped_dev),
                    ("protect", ctx._protect_gcm_dev,
                     ctx._protect_gcm_grouped_dev)):
                if op == "protect" and rows not in (256, ROWS[-1]):
                    continue
                if label == "warm_p4":      # per-row: one program a class
                    jobs.append((rows, "any", "per_row", op, per_row,
                                 packed(tab_rk, tab_gm, stream, *host)
                                 if op == "unprotect" else base,
                                 None, AAD))
                if grid is None:
                    print(f"no grid: {rows} {label}", flush=True)
                    continue
                gr, us, inv = grid
                jobs.append((rows, label, "grouped", op, grouped,
                             base + (jnp.asarray(gr),
                                     jnp.asarray(us, dtype=jnp.int32),
                                     jnp.asarray(inv)), gr.shape, AAD))
    t0 = time.perf_counter()
    compile_concurrently([
        (lambda fn=fn, args=args, aad=aad: jax.block_until_ready(
            fn(*args, aad_const=aad)))
        for *_x, fn, args, _g, aad in jobs])
    print(f"{len(jobs)} programs compiled in "
          f"{time.perf_counter() - t0:.0f}s", flush=True)
    out = []
    for rows, label, form, op, fn, args, gshape, aad in jobs:
        for _ in range(3):
            r = fn(*args, aad_const=aad)
        jax.block_until_ready(r)
        t = time.perf_counter()
        for _ in range(ITERS):
            r = fn(*args, aad_const=aad)
        jax.block_until_ready(r)
        ms = (time.perf_counter() - t) / ITERS * 1e3
        row = {"op": op, "rows": rows, "case": label, "form": form,
               "grid": list(gshape) if gshape else None,
               "ms_per_launch": round(ms, 4)}
        out.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/gcm_forms%s.json" % ("_part2" if part2 else ""),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"device": chip.device_kind, "width": WIDTH,
                      "table_rows": CAPACITY, "results": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
