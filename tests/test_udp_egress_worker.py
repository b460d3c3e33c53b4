"""The UDP engine's egress worker: `send_batch_async` / `reap` / `flush`.

One native thread a socket runs the fan-out's `sendmmsg` while the
caller goes on.  What these tests hold it to: ORDER (one FIFO, and the
synchronous calls queue behind it), each completion once with its
stamps, the caller's arrays referenced until the reap, a bounded queue
that waits and loses nothing, errors at the reap as the synchronous
call raises them, and a close that sends everything and joins."""

import errno
import gc
import os
import socket
import struct
import subprocess
import sys
import time
import weakref

import numpy as np
import pytest

from libjitsi_tpu.core.packet import PacketBatch
from libjitsi_tpu.io import udp as udp_mod
from libjitsi_tpu.io.udp import MAX_QUEUED_JOBS, UdpEngine

WIDTH = 64


def _sink(rcvbuf=8 << 20):
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    s.bind(("127.0.0.1", 0))
    s.settimeout(2.0)
    return s


def _burst(job, n, width=WIDTH):
    """n datagrams of 12 bytes: (job, row) big-endian + 4 of filler."""
    data = np.zeros((n, width), dtype=np.uint8)
    for i in range(n):
        data[i, :12] = np.frombuffer(
            struct.pack("!II4s", job, i, b"eggs"), dtype=np.uint8)
    return PacketBatch(data, np.full(n, 12, dtype=np.int32),
                       np.full(n, -1, dtype=np.int32))


def _read(sock, n):
    """The next n datagrams of `sock` as (job, row) in arrival order."""
    got = []
    for _ in range(n):
        got.append(struct.unpack("!II", sock.recv(256)[:8]))
    return got


def _tasks():
    return len(os.listdir("/proc/self/task"))


@pytest.mark.parametrize("jobs,dsts,rows", [(3, 2, 40), (6, 3, 24),
                                            (12, 1, 7)])
def test_datagrams_of_several_jobs_arrive_in_handover_order(jobs, dsts,
                                                            rows):
    tx = UdpEngine(port=0)
    sinks = [_sink() for _ in range(dsts)]
    try:
        ports = np.asarray([s.getsockname()[1] for s in sinks],
                           dtype=np.uint16)
        for j in range(jobs):
            # row i of every job goes to destination i mod dsts
            tx.send_batch_async(_burst(j, rows), "127.0.0.1",
                                ports[np.arange(rows) % dsts])
        tx.flush()
        for d, s in enumerate(sinks):
            mine = [(j, i) for j in range(jobs) for i in range(rows)
                    if i % dsts == d]
            assert _read(s, len(mine)) == mine
        assert [d.sent for d in tx.reap()] == [rows] * jobs
    finally:
        tx.close()
        for s in sinks:
            s.close()


@pytest.mark.parametrize("call", ["send_batch", "send_rows"])
def test_synchronous_send_queues_behind_a_job_and_runs_inline_when_none(
        call):
    tx = UdpEngine(port=0)
    sink = _sink()
    port = sink.getsockname()[1]

    def sync(job):
        b = _burst(job, 3)
        if call == "send_batch":
            return tx.send_batch(b, "127.0.0.1", port)
        return tx.send_rows(b, [0, 1, 2], "127.0.0.1", port)

    try:
        # none queued, no worker yet: inline, on this thread
        t = _tasks()
        assert sync(7) == 3
        assert _tasks() == t and not tx._jobs
        assert _read(sink, 3) == [(7, 0), (7, 1), (7, 2)]
        # several queued: the synchronous datagrams come after them all
        for j in range(4):
            tx.send_batch_async(_burst(j, 500), "127.0.0.1", port)
        assert sync(9) == 3
        # ... which means every job had left when the call returned
        assert [d.sent for d in tx.reap()] == [500] * 4
        want = [(j, i) for j in range(4) for i in range(500)]
        assert _read(sink, 2003) == want + [(9, 0), (9, 1), (9, 2)]
        # the worker idle again: inline once more
        assert sync(11) == 3
        assert _read(sink, 3) == [(11, 0), (11, 1), (11, 2)]
    finally:
        tx.close()
        sink.close()


def test_reap_gives_sent_and_both_stamps_and_each_job_once():
    tx = UdpEngine(port=0)
    sink = _sink()
    try:
        assert tx.reap() == []
        t_before = time.perf_counter()
        jobs = [tx.send_batch_async(_burst(j, 30 + j), "127.0.0.1",
                                    sink.getsockname()[1])
                for j in range(3)]
        assert [j.rows for j in jobs] == [30, 31, 32]
        assert jobs[0].behind is False
        tx.flush()
        t_after = time.perf_counter()
        done = tx.reap()
        assert [d.id for d in done] == [j.id for j in jobs]
        assert [d.sent for d in done] == [30, 31, 32]
        for d in done:
            # time.perf_counter's clock, inside this test's wall time
            assert t_before <= d.t0 <= d.t1 <= t_after
        assert [a.t1 <= b.t0 for a, b in zip(done, done[1:])] == \
            [True, True]
        assert tx.reap() == [] and not tx._jobs
        # an empty batch is no job
        assert tx.send_batch_async(_burst(0, 0), "127.0.0.1", 9) is None
    finally:
        tx.close()
        sink.close()


def test_plane_and_addresses_stay_referenced_until_the_reap():
    tx = UdpEngine(port=0)
    sink = _sink()
    try:
        batch = _burst(1, 16)
        ips = np.full(16, udp_mod.ip_to_u32("127.0.0.1"), dtype=np.uint32)
        ports = np.full(16, sink.getsockname()[1], dtype=np.uint16)
        refs = [weakref.ref(a) for a in (batch.data, ips, ports)]
        job = tx.send_batch_async(batch, ips, ports)
        # the plane is handed over, not copied
        assert tx._jobs[job.id][0] is batch.data
        del batch, ips, ports
        gc.collect()
        assert all(r() is not None for r in refs)
        tx.flush()
        gc.collect()
        assert all(r() is not None for r in refs)   # sent, not reaped
        assert [d.sent for d in tx.reap()] == [16]
        gc.collect()
        assert all(r() is None for r in refs)
        assert _read(sink, 16) == [(1, i) for i in range(16)]
    finally:
        tx.close()
        sink.close()


@pytest.mark.parametrize("call", ["send_batch", "send_batch_async"])
def test_column_slice_of_a_wider_plane_is_sent_without_a_copy(call):
    """The fan-out's wire plane is `[rows, :width]` of the wider plane
    the device returned: rows contiguous, the matrix not.  It goes to
    the kernel under its own row stride, as it is."""
    tx = UdpEngine(port=0)
    sink = _sink()
    try:
        plane = np.zeros((9, WIDTH + 32), dtype=np.uint8)
        plane[:, :WIDTH] = _burst(3, 9).data
        plane[:, WIDTH:] = 0xEE                  # the tail: never sent
        wire = PacketBatch(plane[:, :WIDTH], np.full(9, 12, np.int32),
                           np.full(9, -1, dtype=np.int32))
        assert not wire.data.flags["C_CONTIGUOUS"]
        assert tx._stage_send(wire, "127.0.0.1", 9)[0] is wire.data
        getattr(tx, call)(wire, "127.0.0.1", sink.getsockname()[1])
        tx.flush()
        assert _read(sink, 9) == [(3, i) for i in range(9)]
        # any other layout is materialized
        odd = PacketBatch(plane[:, :WIDTH].astype(np.int16),
                          np.full(9, 12, np.int32),
                          np.full(9, -1, dtype=np.int32))
        assert tx._stage_send(odd, "127.0.0.1", 9)[0].dtype == np.uint8
    finally:
        tx.close()
        sink.close()


def test_full_queue_makes_the_handover_wait_and_loses_nothing():
    tx = UdpEngine(port=0)
    sink = _sink()      # unread: the kernel drops what overflows it
    try:
        port = sink.getsockname()[1]
        n_jobs, rows = 4 * MAX_QUEUED_JOBS, 2048
        jobs = [tx.send_batch_async(_burst(j, rows), "127.0.0.1", port)
                for j in range(n_jobs)]
        # the first found the worker idle; the last, handed over while
        # a full queue stood before it, did not
        assert jobs[0].behind is False and jobs[-1].behind is True
        # at most MAX_QUEUED_JOBS wait and one is in flight, so the
        # last hand-over returned only after the others had completed
        early = tx.reap()
        assert len(early) >= n_jobs - MAX_QUEUED_JOBS - 1
        tx.flush()
        done = early + tx.reap()
        assert [d.id for d in done] == list(range(1, n_jobs + 1))
        assert [d.sent for d in done] == [rows] * n_jobs
    finally:
        tx.close()
        sink.close()


def test_failing_send_surfaces_at_the_reap_as_the_synchronous_raises():
    tx = UdpEngine(port=0)
    try:
        # port 0: sendmmsg refuses the first datagram with EINVAL
        with pytest.raises(OSError) as exc:
            tx.send_batch(_burst(0, 4), "127.0.0.1", 0)
        assert exc.value.errno == errno.EINVAL
        job = tx.send_batch_async(_burst(0, 4), "127.0.0.1", 0)
        tx.flush()
        (done,) = tx.reap()
        assert done.id == job.id and done.sent == -errno.EINVAL
        # the worker goes on after a failure
        sink = _sink()
        tx.send_batch_async(_burst(5, 2), "127.0.0.1",
                            sink.getsockname()[1])
        tx.flush()
        assert [d.sent for d in tx.reap()] == [2]
        assert _read(sink, 2) == [(5, 0), (5, 1)]
        sink.close()
    finally:
        tx.close()


def test_close_with_jobs_in_flight_sends_them_all_and_joins():
    t = _tasks()
    tx = UdpEngine(port=0)
    sink = _sink()
    try:
        port = sink.getsockname()[1]
        for j in range(5):
            tx.send_batch_async(_burst(j, 300), "127.0.0.1", port)
        assert _tasks() == t + 1
        tx.close()
        assert _tasks() == t and tx._fd == -1 and not tx._jobs
        want = [(j, i) for j in range(5) for i in range(300)]
        assert _read(sink, 1500) == want
        tx.close()                       # a second close is a no-op
        assert _tasks() == t
    finally:
        sink.close()


def test_engine_that_never_sent_asynchronously_owns_no_thread():
    t = _tasks()
    tx = UdpEngine(port=0)
    sink = _sink()
    try:
        tx.send_batch(_burst(0, 8), "127.0.0.1", sink.getsockname()[1])
        tx.flush()
        assert tx.reap() == []
        assert _tasks() == t
        tx.send_batch_async(_burst(1, 8), "127.0.0.1",
                            sink.getsockname()[1])
        assert _tasks() == t + 1
        # one worker a socket, however many jobs
        tx.send_batch_async(_burst(2, 8), "127.0.0.1",
                            sink.getsockname()[1])
        assert _tasks() == t + 1
    finally:
        tx.close()
        sink.close()
    assert _tasks() == t


def test_interleaved_async_and_sync_sends_keep_one_order_under_load():
    """A second of hand-overs, synchronous sends and reaps, read by a
    client as it goes: one strictly increasing sequence, nothing lost
    between the engine and the kernel (`sent` of every completion)."""
    tx = UdpEngine(port=0)
    sink = _sink(rcvbuf=32 << 20)
    sink.setblocking(False)
    got, handed, sent_async = [], 0, 0

    def drain():
        try:
            while True:
                got.append(struct.unpack("!II", sink.recv(256)[:8]))
        except BlockingIOError:
            pass

    try:
        port = sink.getsockname()[1]
        end = time.monotonic() + 1.0
        seq = 0
        while time.monotonic() < end:
            for _ in range(3):
                tx.send_batch_async(_burst(seq, 64), "127.0.0.1", port)
                handed += 64
                seq += 1
            assert tx.send_batch(_burst(seq, 2), "127.0.0.1", port) == 2
            seq += 1
            sent_async += sum(d.sent for d in tx.reap())
            drain()
        tx.flush()
        sent_async += sum(d.sent for d in tx.reap())
        time.sleep(0.05)
        drain()
        assert sent_async == handed > 0
        assert got == sorted(got)
        assert len(set(got)) == len(got)
    finally:
        tx.close()
        sink.close()


_TSAN_SCRIPT = """
import socket, numpy as np
from libjitsi_tpu.core.packet import PacketBatch
from libjitsi_tpu.io.udp import UdpEngine
sink = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
sink.bind(("127.0.0.1", 0))
port = sink.getsockname()[1]
tx = UdpEngine(port=0)
def burst(n):
    return PacketBatch(np.zeros((n, 64), np.uint8),
                       np.full(n, 20, np.int32), np.full(n, -1, np.int32))
total = 0
for k in range(40):
    for _ in range(3):
        tx.send_batch_async(burst(50), "127.0.0.1", port)
    tx.send_batch(burst(2), "127.0.0.1", port)
    total += sum(d.sent for d in tx.reap())
tx.flush()
total += sum(d.sent for d in tx.reap())
tx.send_batch_async(burst(50), "127.0.0.1", port)   # close with one in flight
tx.close()
assert total == 40 * 150, total
print("tsan-ok")
"""


def test_worker_is_clean_under_thread_sanitizer():
    native = os.path.join(os.path.dirname(udp_mod.__file__), os.pardir,
                          "native")
    runtime = "/lib/x86_64-linux-gnu/libtsan.so.2"
    if not os.path.exists(runtime):
        pytest.skip(f"no ThreadSanitizer runtime at {runtime}")
    build = subprocess.run(["sh", os.path.join(native, "build.sh"), "tsan"],
                           capture_output=True, text=True)
    lib = os.path.join(native, "libudp_engine_tsan.so")
    if build.returncode != 0 or not os.path.exists(lib):
        pytest.skip("`build.sh tsan` builds no library here: "
                    + build.stderr.strip()[-200:])
    env = dict(os.environ, LD_PRELOAD=runtime,
               LIBJITSI_TPU_UDP_ENGINE=os.path.abspath(lib),
               TSAN_OPTIONS="exitcode=66 halt_on_error=0",
               PYTHONPATH=os.path.abspath(os.path.join(native, os.pardir,
                                                       os.pardir)))
    run = subprocess.run([sys.executable, "-c", _TSAN_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=120)
    assert "ThreadSanitizer" not in run.stderr, run.stderr[-4000:]
    assert run.returncode == 0 and "tsan-ok" in run.stdout, \
        (run.returncode, run.stderr[-2000:])
