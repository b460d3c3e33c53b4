#!/usr/bin/env python3
"""Where does a fan-out datagram's 9 us go?  (ISSUE 38, satellite 5.)

`stage:egress` cost 9.0-10.2 us a datagram in every cell of the
benchmark (PERF.md section 5) and nobody had asked the kernel why.
This script times a loop of the engine's own `sendmmsg`
(`UdpEngine.send_batch`, inline on this thread) in bursts of 200 and
2,300 datagrams, the paced and the saturated cell's fan-out, toward
loopback sockets of the generator's kind and toward sockets that differ
from them in ONE thing each, so that the differences split the cost:

  read         the generator's kind: one socket a client (2 MiB
               SO_RCVBUF, SO_TIMESTAMPNS, SO_RXQ_OVFL), read by four
               child processes, each in a level-triggered `epoll`
  read-nots    the same without the timestamp options
  unread       the same sockets, nobody reading, drained by this
               process BETWEEN bursts: everything but the wake-up
  full         such sockets with a queue of a few datagrams, never
               drained: the queue is full, so the datagram is dropped
               at the socket after route, skb, loopback transmit,
               softirq receive and socket look-up
  one-unread   one socket for every datagram, drained between bursts
               (one destination: the route and the socket stay hot)
  worker       `read`, sent by the engine's egress worker
               (`send_batch_async`): the worker's own stamps

A loopback `sendmmsg` does the receive side inline: the datagram goes
down the stack, through `loopback_xmit` into the backlog, and the
softirq that delivers it to the receiving socket (and wakes its reader)
runs on this CPU before the syscall returns.  So: `full` is the path
without enqueue and wake-up, `unread` adds the enqueue, `read` adds the
wake-up of a sleeping reader (and, with four readers on other cores,
contention on the sockets' queues).

Beside the wall time of each burst the script books this thread's CPU
time (`time.thread_time()`: user + system), the readers' voluntary
context switches (wake-ups) and the kernel's UDP counters
(`/proc/net/snmp`: `InDatagrams`, counted when a reader takes a
datagram, and `RcvbufErrors`, counted when a full queue drops one).
Where `perf` is on the PATH it also runs `perf stat` and
`perf record -g` round the `read` loop, else `strace -c -f` where that
is; this sandbox and the chip machines have neither (PERF.md, PR 38),
and then the differences above are the answer.  It touches no code a
benchmark cell runs.

    chiprun -- python3 scripts/egress_cost.py          # ~40 s
    python3 scripts/egress_cost.py --bursts 20          # a rehearsal

Prints one JSON line a variant and burst size; the same lines go to
`chiprun_out/egress_cost.jsonl`.
"""

import argparse
import json
import multiprocessing
import os
import select
import shutil
import socket
import statistics
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

from libjitsi_tpu.core.packet import PacketBatch  # noqa: E402
from libjitsi_tpu.io.udp import UdpEngine  # noqa: E402

SO_TIMESTAMPNS = 35
SO_RXQ_OVFL = 40
LOOPBACK = 0x7F000001


def _client_socket(stamps: bool, rcvbuf: int = 1 << 21) -> socket.socket:
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    if stamps:
        s.setsockopt(socket.SOL_SOCKET, SO_TIMESTAMPNS, 1)
        s.setsockopt(socket.SOL_SOCKET, SO_RXQ_OVFL, 1)
    s.bind(("127.0.0.1", 0))
    s.setblocking(False)
    return s


def _reader(n_socks: int, stamps: bool, conn) -> None:
    """A receiver child of the generator's kind: its sockets in one
    level-triggered epoll, `recvmsg_into` until EAGAIN.  Reports its
    ports, then on every line from the parent its datagram count."""
    socks = [_client_socket(stamps) for _ in range(n_socks)]
    ep = select.epoll()
    recv = {}
    for s in socks:
        ep.register(s.fileno(), select.EPOLLIN)
        recv[s.fileno()] = s.recvmsg_into
    ep.register(conn.fileno(), select.EPOLLIN)
    conn.send([s.getsockname()[1] for s in socks])
    bufs = [bytearray(2048)]
    anc = socket.CMSG_SPACE(16) + socket.CMSG_SPACE(4)
    got = 0
    while True:
        for fd, _ev in ep.poll(0.05):
            if fd == conn.fileno():
                if conn.recv() == "stop":
                    return
                conn.send(got)
                continue
            r = recv[fd]
            while True:
                try:
                    r(bufs, anc)
                except BlockingIOError:
                    break
                got += 1


def _drain(socks) -> int:
    n = 0
    buf = bytearray(2048)
    for s in socks:
        while True:
            try:
                s.recv_into(buf)
            except BlockingIOError:
                break
            n += 1
    return n


def _udp_counters() -> dict:
    with open("/proc/net/snmp") as f:
        rows = [ln.split() for ln in f if ln.startswith("Udp:")]
    return dict(zip(rows[0][1:], map(int, rows[1][1:])))


def _wakeups(pid: int) -> int:
    with open(f"/proc/{pid}/status") as f:
        for ln in f:
            if ln.startswith("voluntary_ctxt_switches"):
                return int(ln.split()[1])
    return 0


#: this thread's CPU time, user + system (CLOCK_THREAD_CPUTIME_ID:
#: `getrusage` counts in scheduler ticks, too coarse for a burst)
_cpu_s = time.thread_time


def _plane(rows: int, seed: int) -> PacketBatch:
    """A fan-out plane as the device returns it: [rows, 256] uint8,
    wire lengths of an Opus packet under SRTP (12 + 8 + 40..160 + 10)."""
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, (rows, 256), dtype=np.uint8)
    length = rng.integers(70, 191, rows).astype(np.int32)
    return PacketBatch(data, length, np.full(rows, -1, dtype=np.int32))


def measure(variant: str, rows: int, bursts: int, n_socks: int,
            gap_s: float, seed: int) -> dict:
    stamps = variant not in ("read-nots",)
    readers, conns, local = [], [], []
    if variant in ("read", "read-nots", "worker"):
        ctx = multiprocessing.get_context("spawn")
        per = [n_socks // 4 + (1 if k < n_socks % 4 else 0)
               for k in range(4)]
        ports = []
        for k in range(4):
            mine, theirs = ctx.Pipe()
            p = ctx.Process(target=_reader, args=(per[k], stamps, theirs),
                            daemon=True)
            p.start()
            readers.append(p)
            conns.append(mine)
        for c in conns:
            ports += c.recv()
    else:
        # `full`: a queue of a few datagrams, filled by the warm-up
        # bursts and never drained
        rcvbuf = 4096 if variant == "full" else 1 << 21
        local = [_client_socket(stamps, rcvbuf)
                 for _ in range(1 if variant == "one-unread" else n_socks)]
        ports = [s.getsockname()[1] for s in local]
    tx = UdpEngine(port=0)
    # row r goes to receiver r mod n: a packet's seven legs lie side by
    # side in the fan-out plane, each toward another client
    dst_port = np.asarray(ports, dtype=np.uint16)[
        np.arange(rows) % len(ports)]
    dst_ip = np.full(rows, LOOPBACK, dtype=np.uint32)
    planes = [_plane(rows, seed + k) for k in range(4)]
    wall, cpu, sent_total = [], [], 0
    try:
        for k in range(3):                       # warm: pages, routes
            tx.send_batch(planes[k % 4], dst_ip, dst_port)
            if variant != "full":
                _drain(local)
        time.sleep(0.1)
        udp0 = _udp_counters()
        wake0 = sum(_wakeups(p.pid) for p in readers)
        got0 = 0
        for c in conns:
            c.send("count")
            got0 += c.recv()
        for k in range(bursts):
            plane = planes[k % 4]
            if variant == "worker":
                c0 = _cpu_s()
                tx.send_batch_async(plane, dst_ip, dst_port)
                tx.flush()
                (done,) = tx.reap()
                wall.append(done.t1 - done.t0)
                cpu.append(_cpu_s() - c0)        # the hand-over's CPU
                sent = done.sent
            else:
                c0, t0 = _cpu_s(), time.perf_counter()
                sent = tx.send_batch(plane, dst_ip, dst_port)
                wall.append(time.perf_counter() - t0)
                cpu.append(_cpu_s() - c0)
            sent_total += sent
            if variant in ("unread", "one-unread"):
                _drain(local)
            time.sleep(gap_s)                    # readers fall asleep
        time.sleep(0.1)
        udp1 = _udp_counters()
        wake1 = sum(_wakeups(p.pid) for p in readers)
        got = None
        if readers:
            for c in conns:
                c.send("count")
            got = sum(c.recv() for c in conns) - got0
    finally:
        tx.close()
        for c in conns:
            c.send("stop")
        for p in readers:
            p.join(timeout=5)
        for s in local:
            s.close()
    n = rows * bursts
    q = statistics.quantiles(wall, n=4)
    return {
        "variant": variant, "rows": rows, "bursts": bursts,
        "sockets": len(ports), "sent": sent_total,
        "us_per_datagram_p50": 1e6 * statistics.median(wall) / rows,
        "us_per_datagram_q1": 1e6 * q[0] / rows,
        "us_per_datagram_q3": 1e6 * q[2] / rows,
        "burst_ms_p50": 1e3 * statistics.median(wall),
        "sender_cpu_us_per_datagram": 1e6 * sum(cpu) / n,
        "reader_wakeups_per_datagram":
            (wake1 - wake0) / n if readers else None,
        "readers_got": got,
        "udp_in_datagrams": udp1["InDatagrams"] - udp0["InDatagrams"],
        "udp_rcvbuf_errors": udp1["RcvbufErrors"] - udp0["RcvbufErrors"],
    }


def _profile(argv_tail, out_dir: str) -> dict:
    """`perf stat` + `perf record -g` round one `read` loop where perf
    is installed, else `strace -c -f`; says which it found."""
    me = [sys.executable, os.path.abspath(__file__), "--variants", "read",
          "--no-profile"] + argv_tail
    perf, strace = shutil.which("perf"), shutil.which("strace")
    found = {"perf": perf, "strace": strace}
    try:
        if perf:
            data = os.path.join(out_dir, "egress_cost.perf.data")
            for cmd, name in (
                    ([perf, "stat", "-e", "task-clock,context-switches,"
                      "cpu-migrations,cycles,instructions", "--"] + me,
                     "perf_stat.txt"),
                    ([perf, "record", "-g", "-o", data, "--"] + me,
                     "perf_record.txt")):
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=300)
                with open(os.path.join(out_dir, name), "w") as f:
                    f.write(r.stdout + r.stderr)
            r = subprocess.run([perf, "report", "-i", data, "--stdio",
                                "--no-children", "--percent-limit", "1"],
                               capture_output=True, text=True, timeout=300)
            with open(os.path.join(out_dir, "perf_report.txt"), "w") as f:
                f.write(r.stdout[:200_000])
            found["wrote"] = ["perf_stat.txt", "perf_report.txt"]
        elif strace:
            r = subprocess.run([strace, "-c", "-f", "-o", os.path.join(
                out_dir, "strace_c.txt")] + me, capture_output=True,
                text=True, timeout=300)
            found["wrote"] = ["strace_c.txt"]
            found["rc"] = r.returncode
    except (OSError, subprocess.SubprocessError) as e:
        found["error"] = repr(e)
    return found


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--variants", default="read,read-nots,unread,full,"
                    "one-unread,worker")
    ap.add_argument("--rows", default="200,2300")
    ap.add_argument("--bursts", type=int, default=150)
    ap.add_argument("--sockets", type=int, default=56,
                    help="client sockets (A x 8: 56 in CM talk-paced)")
    ap.add_argument("--gap-ms", type=float, default=8.0,
                    help="pause between bursts (a tick's other work)")
    ap.add_argument("--seed", type=int, default=38)
    ap.add_argument("--no-profile", action="store_true")
    args = ap.parse_args()
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           os.pardir, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    lines = [{"host": {"cpus": len(os.sched_getaffinity(0)),
                       "kernel": os.uname().release}}]
    if not args.no_profile:
        lines[0]["profilers"] = _profile(
            ["--rows", args.rows, "--bursts", str(args.bursts),
             "--sockets", str(args.sockets)], out_dir)
    print(json.dumps(lines[0]), flush=True)
    for rows in (int(r) for r in args.rows.split(",")):
        # fewer of the long bursts: the same number of datagrams
        bursts = max(10, args.bursts * 200 // rows) if rows > 200 \
            else args.bursts
        for variant in args.variants.split(","):
            line = measure(variant, rows, bursts, args.sockets,
                           args.gap_ms / 1e3, args.seed)
            lines.append(line)
            print(json.dumps(line), flush=True)
    if not args.no_profile:
        with open(os.path.join(out_dir, "egress_cost.jsonl"), "w") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
