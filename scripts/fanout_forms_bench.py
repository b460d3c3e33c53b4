#!/usr/bin/env python3
"""Device time a launch of the per-row fan-out under three alignments
of keystream to payload: the payload offset compiled in (`static20`:
the ladder warmed such a form twice a width, and a third, until
PR 43), the per-row
gathers (`gather`, the old `off_const=None` form) and the shift ladder
the fan-out runs now (`rows`: `kernel.srtp_protect_rows`,
`gcm.gcm_protect_rows`), CM and GCM, at the 256-, 512-, 1,024- and
4,096-row classes of the 224-byte width (512 is the fan-out's own
class, `core/packet.py:FANOUT_ROW_CLASSES`: PR 44 sized it here before
the ladder warmed it) and the 1,024-row class of the 1,536 one.

    chiprun -- python3 scripts/fanout_forms_bench.py [--tiny]

Every program is the served one's body on one packed plane over a
10,240-row key table (undonated, so one staged plane serves every
launch); the time is device 0's `XLA Modules` events of a traced run,
as `scripts/launch_cost.py` takes a program's, median over the
launches.  The three forms of a shape are also compared byte for byte.
One JSON line a (suite, rows, width), `<form>_us` and `<form>_vs_static`.
"""

import glob
import json
import os
import sys
import tempfile
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from libjitsi_tpu.core import staging  # noqa: E402
from libjitsi_tpu.kernels import gcm as gcm_kernel  # noqa: E402
from libjitsi_tpu.transform.srtp import kernel  # noqa: E402

CAPACITY = 10240
#: the payload offset of every row: the 12-byte header and the one
#: extension word (abs-send-time) the bridge's fan-out carries
OFFSET = 20
LAUNCHES = 24


def body(suite: str, form: str, shape: str):
    """The fan-out program's body (`sfu/translator.py:_fanout_protect`
    / `_fanout_protect_gcm`) under one alignment, named for the trace
    `bench_<suite>_<form>_<shape>`."""
    def cm(tab_rk, tab_aux, plane):
        data, w, iv = staging.unpack(plane)
        rk, mid = kernel.gather_keys(staging.as_i32(w[:, 0]), tab_rk,
                                     tab_aux)
        args = (data, staging.as_i32(w[:, 1]), staging.as_i32(w[:, 2]),
                rk, iv, mid, w[:, 3], 10, True)
        if form == "rows":
            out = kernel.srtp_protect_rows(*args)
        else:
            out = kernel.srtp_protect(
                *args,
                payload_off_const=OFFSET if form == "static20" else None)
        return staging.repack(*out)

    def gcm(tab_rk, tab_aux, plane):
        data, w, iv = staging.unpack(plane)
        rk, gm = kernel.gather_keys(staging.as_i32(w[:, 0]), tab_rk,
                                    tab_aux)
        args = (data, staging.as_i32(w[:, 1]), staging.as_i32(w[:, 2]),
                rk, gm, iv[:, :12])
        if form == "rows":
            out = gcm_kernel.gcm_protect_rows(*args)
        else:
            out = gcm_kernel.gcm_protect(
                *args, aad_const=OFFSET if form == "static20" else None)
        return staging.repack(*out)

    fn = cm if suite == "cm" else gcm
    fn.__name__ = fn.__qualname__ = f"bench_{suite}_{form}_{shape}"
    return jax.jit(fn)


def main() -> int:
    tiny = "--tiny" in sys.argv
    dev = jax.devices()[0]
    if not tiny and dev.platform != "tpu":
        print("not a TPU:", dev.platform, file=sys.stderr)
        return 1
    capacity = 512 if tiny else CAPACITY
    shapes = ([(16, 224)] if tiny else
              [(256, 224), (512, 224), (1024, 224), (4096, 224),
               (1024, 1536)])
    rng = np.random.default_rng(43)
    tabs = {
        "cm": (jnp.asarray(rng.integers(0, 256, (capacity, 11, 16),
                                        dtype=np.uint8)),
               jnp.asarray(rng.integers(0, 2 ** 32, (capacity, 2, 5),
                                        dtype=np.uint32))),
        "gcm": (jnp.asarray(rng.integers(0, 256, (capacity, 11, 16),
                                         dtype=np.uint8)),
                jnp.asarray(rng.integers(0, 2, (capacity, 128, 128),
                                         dtype=np.int8)))}
    jobs = []
    for suite in ("cm", "gcm"):
        for rows, width in shapes:
            plane = staging.alloc(rows, width)
            plane[:, :width] = rng.integers(0, 256, (rows, width),
                                            dtype=np.uint8)
            length = rng.integers(52, min(width - 32, 1400), rows)
            words = (rng.integers(0, capacity, rows), length,
                     np.full(rows, OFFSET))
            iv = rng.integers(0, 256, (rows, 16), dtype=np.uint8)
            if suite == "cm":
                iv[:, 14:] = 0
                words += (rng.integers(0, 2 ** 32, rows),)
            staging.pack(plane, words, iv[:, :12] if suite == "gcm" else iv)
            dev_plane = jax.device_put(plane)
            for form in ("static20", "gather", "rows"):
                jobs.append([suite, rows, width, form,
                             body(suite, form, f"{rows}x{width}"),
                             dev_plane, None])
    with ThreadPoolExecutor(6) as pool:
        compiled = list(pool.map(
            lambda j: j[4].lower(*tabs[j[0]], j[5]).compile(), jobs))
    for job, exe in zip(jobs, compiled):
        job[4] = exe
        job[6] = np.asarray(exe(*tabs[job[0]], job[5]))
    trace_dir = tempfile.mkdtemp(prefix="fanout_forms_")
    jax.profiler.start_trace(trace_dir)
    for suite, _r, _w, _f, exe, plane, _o in jobs:
        for _ in range(LAUNCHES):
            out = exe(*tabs[suite], plane)
        out.block_until_ready()
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    took = {}            # program name -> device us of each launch
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    took.setdefault(ev.name.split("(")[0], []).append(
                        ev.duration_ns / 1e3)
    by_shape = {}
    for suite, rows, width, form, _e, _p, out in jobs:
        rec = by_shape.setdefault(
            (suite, rows, width),
            {"suite": suite, "rows": rows, "width": width, "_out": out,
             "bytes_equal": True})
        rec["bytes_equal"] &= bool(np.array_equal(out, rec["_out"]))
        # under --tiny on a CPU there is no device plane and no time
        us = took.get(f"jit_bench_{suite}_{form}_{rows}x{width}")
        if us:
            rec[form + "_us"] = round(float(np.median(us)), 2)
    for rec in by_shape.values():
        rec.pop("_out")
        base = rec.get("static20_us")
        for form in ("gather", "rows"):
            if base and rec.get(form + "_us"):
                rec[form + "_vs_static"] = round(
                    rec[form + "_us"] / base, 4)
        rec["device"] = f"{dev.platform} {dev.device_kind}"
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
