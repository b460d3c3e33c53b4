#!/usr/bin/env python3
"""Checks of the yardstick itself; needs no chip and no JAX device.

    python3 benchmarks/check.py

1. `reduce.py` against the recorded v5e slice under `fixtures/`: busy
   and idle time, per-program device time and the naming of idle gaps
   are re-derived here by a second, slower method (a microsecond
   raster instead of interval merging) and held to the numbers read
   from the slice by hand when it was recorded (`fixtures/*.expect.json`).
2. `loadgen.py` against a local UDP reflector at a tiny rate: latency
   is counted from the DUE time, not the send time (every delivery's
   latency is at least the sender's recorded lateness for its source
   packet and within a few ms of it), and a stream with known gaps is
   counted lost exactly.
3. `opcount.py`: fixed points of the byte and operation counts.

Exits non-zero on the first difference.
"""

from __future__ import annotations

import glob
import json
import os
import socket
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import loadgen  # noqa: E402
import opcount  # noqa: E402
import reduce  # noqa: E402


def fail(msg: str) -> None:
    print(f"check: FAIL {msg}")
    raise SystemExit(1)


def near(a: float, b: float, rel: float = 1e-9, what: str = "") -> None:
    if abs(a - b) > rel * max(abs(a), abs(b), 1e-12):
        fail(f"{what}: {a!r} != {b!r}")


# ------------------------------------------------------------ 1. reduce

def raster_busy_ns(events, lo, hi, step=1000):
    """Busy time by painting a `step`-ns raster: a second method, exact
    to one cell per interval edge."""
    cells = np.zeros((hi - lo) // step + 2, dtype=bool)
    for _n, s, d in events:
        a = (s - lo) // step
        b = -(-(s + d - lo) // step)
        cells[a:b] = True
    return int(cells.sum()) * step


def check_reduce() -> None:
    fixtures = sorted(glob.glob(os.path.join(HERE, "fixtures",
                                             "*.trace.json.gz")))
    if not fixtures:
        fail("no recorded slice under fixtures/")
    for path in fixtures:
        expect = json.load(open(path.replace(".trace.json.gz",
                                             ".expect.json")))
        trace = reduce.load_fixture(path)
        red = reduce.reduce_trace(trace)
        # against the hand-read numbers
        near(red["window_s"], expect["window_s"], 1e-9, "window_s")
        near(red["busy_s"], expect["busy_s"], 1e-9, "busy_s")
        for prog, sec in expect["program_s"].items():
            near(red["program_s"].get(prog, 0.0), sec, 1e-9,
                 f"program_s[{prog}]")
        if red["breakdown"]["idle_gaps"][0][0] != expect["longest_gap"][0]:
            fail(f"longest gap named {red['breakdown']['idle_gaps'][0]}, "
                 f"by hand {expect['longest_gap']}")
        near(red["breakdown"]["idle_gaps"][0][1],
             expect["longest_gap"][1], 1e-9, "longest gap seconds")
        # against the second method
        plane = sorted(trace["device"])[0]
        ops = trace["device"][plane][reduce.OPS_LINE]
        lo = min(s for _n, s, _d in ops)
        hi = max(s + d for _n, s, d in ops)
        raster = raster_busy_ns(ops, lo, hi)
        merged = sum(e - s for s, e in reduce.merge_intervals(
            [s, s + d] for _n, s, d in ops))
        # the raster over-counts by at most one cell per interval edge
        n_iv = len(reduce.merge_intervals(
            [s, s + d] for _n, s, d in ops))
        if not merged <= raster <= merged + 2 * 1000 * n_iv:
            fail(f"busy by raster {raster} ns vs by merging {merged} ns "
                 f"({n_iv} intervals)")
        idle = 100.0 * (1 - red["busy_s"] / red["window_s"])
        near(red["idle_pct"], idle, 1e-12, "idle_pct")
        print(f"check: reduce ok on {os.path.basename(path)}: window "
              f"{red['window_s']:.6f}s busy {red['busy_s']:.6f}s idle "
              f"{red['idle_pct']:.2f}%, {len(red['program_s'])} programs")


# ----------------------------------------------------------- 2. loadgen

class Reflector(threading.Thread):
    """A stand-in bridge: latches each SSRC's source address and sends
    every datagram, unchanged, to the other members of its conference.
    Drops the (ssrc, seq) pairs in `drop`."""

    def __init__(self, conf_size: int, drop: set):
        super().__init__(daemon=True)
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.settimeout(0.05)
        self.port = self.sock.getsockname()[1]
        self.cs, self.drop = conf_size, drop
        self.addr = {}
        self.stop = False

    def run(self) -> None:
        while not self.stop:
            try:
                data, addr = self.sock.recvfrom(2048)
            except socket.timeout:
                continue
            ssrc = int.from_bytes(data[8:12], "big")
            seq = int.from_bytes(data[2:4], "big")
            self.addr[ssrc] = addr
            if (ssrc, seq) in self.drop:
                continue
            row = ssrc - loadgen.SSRC_BASE
            base = row - row % self.cs + loadgen.SSRC_BASE
            for peer in range(base, base + self.cs):
                if peer != ssrc and peer in self.addr:
                    self.sock.sendto(data, self.addr[peer])
        self.sock.close()


def check_loadgen() -> None:
    config = {"profile": "AES_CM_128_HMAC_SHA1_80", "capacity": 64,
              "conference_sizes": [8]}
    # 3 of a conference's 8 speak; the 5 others reach the reflector
    # with one packet in the first period and then only receive
    speakers = 3
    traffic = {"payload": {"min": 40, "max": 160}, "sample_target": 64,
               "speakers_per_conference": speakers}
    plan = loadgen.make_plan(config, traffic, seed=3, n_active=2,
                             duration_s=3.0)
    sched = loadgen.build_schedule(plan)
    # known gaps: drop every 10th packet of the first speaking socket
    eps = loadgen.plan_endpoints(plan)
    first = int(eps[0]) + loadgen.SSRC_BASE
    lead = 25                     # slots before the window (latch)
    window_slots = 100
    drop = {(first, plan["first_index"] + k)
            for k in range(lead, lead + window_slots, 10)}
    refl = Reflector(plan["conf_size"], drop)
    refl.start()
    work = os.path.join(HERE, "out", "check")
    gen = loadgen.Generator(plan, work, n_recv=2)
    gen.wait_ready()
    t0 = gen.go(refl.port, lead_s=0.2)
    period = sched["period_ns"]
    w0 = t0 + lead * period
    w1 = w0 + window_slots * period
    gen.until(w1 + 5 * period)
    time.sleep((w1 - time.time_ns()) / 1e9 + 0.6)
    got = gen.finish()
    refl.stop = True
    refl.join()
    res = loadgen.analyze(plan, sched, got, t0, w0, w1, int(0.5e9))
    cs = plan["conf_size"]
    n_speak = len(plan["active"]) * speakers
    want_offered = n_speak * window_slots * (cs - 1)
    if res["offered"] != want_offered:
        fail(f"offered {res['offered']} != {want_offered}")
    want_lost = len(drop) * (plan["conf_size"] - 1)
    if res["lost"] != want_lost:
        fail(f"lost {res['lost']} != {want_lost} (known gaps)")
    if res["foreign"] or res["duplicates"] or res["unknown"]:
        fail(f"foreign/duplicates/unknown {res['foreign']}/"
             f"{res['duplicates']}/{res['unknown']}")
    # the listeners' packets went out and what was reflected of them
    # matched the schedule (`unknown` 0 above); every listener received
    listening = np.nonzero(np.arange(len(eps)) % cs >= speakers)[0]
    listeners = eps[listening]
    from_listeners = int(np.isin(
        got["recs"]["ssrc"].astype(np.int64) - loadgen.SSRC_BASE,
        listeners).sum())
    if not from_listeners:
        fail("no delivery of a listener's packet: they were not sent")
    quiet = set(listening.tolist()) - set(got["recs"]["rx"].tolist())
    if quiet:
        fail(f"listeners {sorted(quiet)} received nothing")
    # due time, not send time: each delivery's latency is at least its
    # source packet's recorded lateness, and within 20 ms of it (a
    # Python reflector on shared cores)
    late_of = {}
    for i, (s, idx) in enumerate(zip(sched["sock"].tolist(),
                                     sched["index"].tolist())):
        if i < len(got["late_ns"]):
            late_of[(s, idx)] = int(got["late_ns"][i])
    recs = got["recs"]
    sock_of_row = {int(r): k for k, r in enumerate(eps)}
    checked = 0
    for r in recs[:20000]:
        s = sock_of_row[int(r["ssrc"]) - loadgen.SSRC_BASE]
        idx = int(r["seq"])
        due = t0 + int(sched["due_of"][s, idx - plan["first_index"]])
        lat = int(r["t_" + res["stamp"]]) - due
        late = late_of[(s, idx)]
        if not late <= lat <= late + 20_000_000:
            fail(f"delivery latency {lat} ns not within [lateness "
                 f"{late}, +20 ms]: latency is not counted from the due "
                 "time")
        checked += 1
    if res["lat_p50_ms"] is None or not 0 < res["lat_p50_ms"] < 20:
        fail(f"latency p50 {res['lat_p50_ms']}")
    print(f"check: loadgen ok: offered {res['offered']}, lost "
          f"{res['lost']} (= known gaps), {from_listeners} deliveries of "
          f"the {len(listeners)} listeners' packets, {checked} within "
          f"[lateness, +20 ms] of their due time, p50 "
          f"{res['lat_p50_ms']:.3f} ms, sender late p99 "
          f"{res['late_p99_ms']:.3f} ms, samples {len(got['samples'])}")


# ----------------------------------------------------------- 3. opcount

def check_opcount() -> None:
    c = opcount.call_cost("AES_CM_128_HMAC_SHA1_80", 1, 172.0)
    # 172-byte RTP: 160 payload = 10 AES blocks; HMAC inner over 176+9
    want_ops = 10 * opcount.AES_BIT_OPS_PER_BLOCK / 32 + \
        ((172 + 4 + 9) / 64 + 1) * opcount.SHA1_WORD_OPS_PER_BLOCK
    near(c["int_ops"], want_ops, 1e-12, "CM int_ops")
    near(c["bytes"], 2 * 172 + 10 + 11 * 16 + 40 + 32, 1e-12, "CM bytes")
    g = opcount.call_cost("AEAD_AES_128_GCM", 2, 172.0)
    near(g["bytes"], 2 * (2 * 172 + 16 + 11 * 16 + 16 + 32), 1e-12,
         "GCM bytes")
    peaks = json.load(open(os.path.join(HERE, "peaks.json")))
    near(opcount.least_time_s(c, peaks["TPU v5 lite"]),
         c["bytes"] / 819e9, 1e-12, "least time")
    print("check: opcount ok")


if __name__ == "__main__":
    check_opcount()
    check_loadgen()
    check_reduce()
    print("check: all ok")
