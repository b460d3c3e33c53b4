"""Python face of the native batched UDP engine.

Receives land directly in a PacketBatch-shaped buffer ([max_pkts,
capacity] uint8 + int32 lengths) — the C engine scatters datagrams with
recvmmsg into exactly the struct-of-arrays the device consumes, so the
host's only per-batch work is the ssrc demux.  Reference analog:
RTPConnectorUDPImpl's connector threads, collapsed into one
batch-per-syscall loop (SURVEY §2.6 item 12).
"""

from __future__ import annotations

import ctypes
import os
import socket
import struct
import subprocess
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from libjitsi_tpu.core.packet import DEFAULT_CAPACITY, PacketBatch

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), os.pardir, "native")
_lib = None


def _load():
    global _lib
    if _lib is not None:
        return _lib
    so = os.environ.get("LIBJITSI_TPU_UDP_ENGINE")  # e.g. a tsan build
    if so is None:
        so = os.path.join(_NATIVE_DIR, "libudp_engine.so")
        src = os.path.join(_NATIVE_DIR, "udp_engine.cpp")
        if (not os.path.exists(so)
                or os.path.getmtime(so) < os.path.getmtime(src)):
            subprocess.run(["sh", os.path.join(_NATIVE_DIR, "build.sh")],
                           check=True, capture_output=True)
    lib = ctypes.CDLL(so)
    lib.udp_create.restype = ctypes.c_int
    lib.udp_create.argtypes = [ctypes.c_char_p, ctypes.c_uint16,
                               ctypes.c_int, ctypes.c_int]
    lib.udp_close.argtypes = [ctypes.c_int]
    lib.udp_local_port.restype = ctypes.c_int
    lib.udp_local_port.argtypes = [ctypes.c_int]
    lib.udp_recv_batch.restype = ctypes.c_int
    lib.udp_recv_batch.argtypes = [
        ctypes.c_int, ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int]
    lib.udp_send_batch.restype = ctypes.c_int
    lib.udp_send_batch.argtypes = [
        ctypes.c_int, ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int]
    # every entry point below is in udp_engine.cpp as committed (the
    # udp_uring_* ones stub to ENOSYS without the kernel header), and
    # the library is rebuilt whenever the source is newer
    lib.udp_send_batch_idx.restype = ctypes.c_int
    lib.udp_send_batch_idx.argtypes = [
        ctypes.c_int, ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int]
    # the egress worker (one thread a socket, started by the first
    # udp_send_async, joined in udp_close)
    lib.udp_send_async.restype = ctypes.c_int64
    lib.udp_send_async.argtypes = [
        ctypes.c_int, ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p]
    lib.udp_send_reap.restype = ctypes.c_int
    lib.udp_send_reap.argtypes = [
        ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_int]
    lib.udp_send_flush.restype = ctypes.c_int
    lib.udp_send_flush.argtypes = [ctypes.c_int]
    lib.udp_enable_timestamps.restype = ctypes.c_int
    lib.udp_enable_timestamps.argtypes = [ctypes.c_int]
    lib.udp_recv_batch_ts.restype = ctypes.c_int
    lib.udp_recv_batch_ts.argtypes = [
        ctypes.c_int, ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_int]
    lib.udp_uring_supported.restype = ctypes.c_int
    lib.udp_uring_create.restype = ctypes.c_void_p
    lib.udp_uring_create.argtypes = [ctypes.c_int] * 4
    lib.udp_uring_arm.restype = ctypes.c_int
    lib.udp_uring_arm.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p]
    lib.udp_uring_recv.restype = ctypes.c_int
    lib.udp_uring_recv.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_void_p]
    lib.udp_uring_stat.restype = ctypes.c_long
    lib.udp_uring_stat.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.udp_uring_destroy.restype = None
    lib.udp_uring_destroy.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


#: C-side sentinel: every row of the armed arena has been delivered
_URING_ARENA_EXHAUSTED = -9999


def _uring_env_disabled() -> bool:
    """io_uring force-disabled by environment — the fallback-proof
    switch (LIBJITSI_TPU_NO_IOURING=1) and the explicit mode pin
    (LIBJITSI_TPU_ENGINE_MODE=recvmmsg) both count."""
    if os.environ.get("LIBJITSI_TPU_NO_IOURING", ""):
        return True
    mode = os.environ.get("LIBJITSI_TPU_ENGINE_MODE", "").strip().lower()
    return mode == "recvmmsg"


def uring_available() -> bool:
    """Capability probe: the loaded .so carries the ring engine, the
    kernel accepts io_uring_setup, and the environment does not force
    it off.  Cached C-side; cheap to call repeatedly."""
    if _uring_env_disabled():
        return False
    return bool(_load().udp_uring_supported())


def probe_engine_mode() -> str:
    """The engine mode a fresh ``UdpEngine(engine_mode="auto")`` picks
    right now.  "auto" resolves to the environment pin
    (LIBJITSI_TPU_ENGINE_MODE) when set and available, else to the
    measured default for this box class: **recvmmsg**.  The ring
    engine is fully built and probe-selectable, but on loopback — the
    only fabric this box can measure — a sender pays the armed chain's
    per-packet completion work inline inside its own send syscall, and
    the 3-run loop-echo median loses ~30% to recvmmsg (the zero-syscall
    win is real only where softirq context fills the chain, i.e. NIC
    ingest).  Flipping the default needs a NIC-box median, not vibes.
    Exported so gates and tooling label measurements with the mode
    they actually ran."""
    mode = os.environ.get("LIBJITSI_TPU_ENGINE_MODE", "").strip().lower()
    if mode == "io_uring" and uring_available():
        return "io_uring"
    return "recvmmsg"


class _ArenaToken:
    """Pin receipt handed out with every zero-copy view.  Idempotent:
    `release_arena` flips `released` on first use, so a double release
    can never steal a pin that another live view still holds (the old
    (arena, gen) tuple only caught doubles AFTER the arena re-armed)."""

    __slots__ = ("arena", "gen", "released")

    def __init__(self, arena: "_Arena", gen: int):
        self.arena = arena
        self.gen = gen
        self.released = False

    def __iter__(self):  # legacy (arena, gen) unpacking
        return iter((self.arena, self.gen))


class _Arena:
    """One pinned recv arena: the PacketBatch SoA the kernel scatters
    into.  `gen` tags the arena's current occupancy; `pins` counts live
    zero-copy views — the ring never hands a pinned arena back to the
    kernel, so a view is never overwritten while in flight."""

    __slots__ = ("buf", "len", "sip", "sport", "ats", "gen", "pins")

    def __init__(self, rows: int, capacity: int):
        self.buf = np.zeros((rows, capacity), dtype=np.uint8)
        self.len = np.zeros(rows, dtype=np.int32)
        self.sip = np.zeros(rows, dtype=np.uint32)
        self.sport = np.zeros(rows, dtype=np.uint16)
        self.ats = np.zeros(rows, dtype=np.int64)
        self.gen = 0
        self.pins = 0


def ip_to_u32(ip: str) -> int:
    return struct.unpack("!I", socket.inet_aton(ip))[0]


def u32_to_ip(v: int) -> str:
    return socket.inet_ntoa(struct.pack("!I", v & 0xFFFFFFFF))


#: jobs the native egress worker lets wait behind the one in flight
#: before a hand-over blocks (`kEgressQueueJobs`, native/udp_engine.cpp)
MAX_QUEUED_JOBS = 4


class SendJob(NamedTuple):
    """Receipt of `UdpEngine.send_batch_async`."""

    id: int
    rows: int
    #: an earlier job was still queued or in flight at the hand-over:
    #: the worker, not the caller, is the pace
    behind: bool


class SendDone(NamedTuple):
    """One completion from `UdpEngine.reap`."""

    id: int
    sent: int        # datagrams sent, or -errno
    t0: float        # `time.perf_counter()` seconds: the send began
    t1: float        # ... and ended


class UdpEngine:
    """One batched UDP socket (rtcp-mux style single port per engine).

    SO_REUSEPORT lets several engines (host threads/processes) share a
    port for kernel-sharded ingest — the 10k-stream single-port design
    (SURVEY §7 "10k-socket ingest").
    """

    def __init__(self, port: int = 0, bind_ip: str = "0.0.0.0",
                 reuseport: bool = False, capacity: int = DEFAULT_CAPACITY,
                 max_batch: int = 1024, rcvbuf: int = 4 << 20,
                 kernel_timestamps: bool = False, arenas: int = 4,
                 engine_mode: str = "auto"):
        if engine_mode not in ("auto", "io_uring", "recvmmsg"):
            raise ValueError(f"engine_mode: {engine_mode!r}")
        # egress is sendmmsg in every mode (one sendmmsg beats N SENDMSG
        # SQEs: ~127 vs ~226 us per 64-pkt burst); the ring is INGEST
        # only
        lib = _load()
        self.capacity = capacity
        #: live batching knob — recv windows honor the CURRENT value
        #: (adaptive batching tunes it tick to tick); arena allocation
        #: is sized once from the construction-time value
        self.max_batch = max_batch
        fd = lib.udp_create(bind_ip.encode(), port, int(reuseport), rcvbuf)
        if fd < 0:
            raise OSError(-fd, os.strerror(-fd))
        self._fd = fd
        self.port = lib.udp_local_port(fd)
        self.kernel_timestamps = False
        if kernel_timestamps:
            self.kernel_timestamps = lib.udp_enable_timestamps(fd) == 0
            if not self.kernel_timestamps:
                from libjitsi_tpu.utils.logging import get_logger

                # the feature was explicitly requested: degrading to
                # userspace stamps must not be silent
                get_logger("io.udp").warn(
                    "kernel_timestamps_unavailable", port=self.port)
        # rotating ring of pinned receive arenas (each one IS a
        # PacketBatch SoA); `recv_batch_view` hands out in-place views
        # and pins the arena until `release_arena`, so deep-pipelined
        # callers can hold tick N's bytes while tick N+1 receives
        self._rows = max_batch
        self._ring = [_Arena(max_batch, capacity)
                      for _ in range(max(1, arenas))]
        self._ring_pos = 0
        #: times the ring grew because every arena was pinned — a
        #: pipeline holding views longer than the ring depth
        self.arena_grows = 0
        self._alias_arena(self._ring[0])
        #: kernel entries made from Python (one per recvmmsg/sendmmsg
        #: native call); the io_uring engine's own enter count adds in
        #: via the `syscall_enters` property
        self._py_enters = 0
        # asynchronous sends handed to the native egress worker and not
        # reaped yet: job id -> the arrays the worker reads (the plane
        # is NOT copied, so the reference is what keeps it alive)
        self._jobs: Dict[int, tuple] = {}
        self._reap_out = None        # scratch of `reap`, made on first use
        self._u = None  # io_uring engine handle (None => recvmmsg)
        self._uring_arena: Optional[_Arena] = None
        # mode resolution: "auto" follows the probe (env pin or the
        # measured recvmmsg default — see probe_engine_mode); an
        # explicit "io_uring" request takes the ring whenever the
        # capability probe passes, and degrades loudly when it can't
        want_uring = (engine_mode == "io_uring"
                      or (engine_mode == "auto"
                          and probe_engine_mode() == "io_uring"))
        self.engine_mode = "recvmmsg"
        if want_uring and uring_available():
            # ring sized to one arena: arming an arena is one chain of
            # `rows` linked recvs, so steady state reaps ring-side
            self._u = lib.udp_uring_create(
                fd, self._rows, 0, int(self.kernel_timestamps))
            if self._u:
                self.engine_mode = "io_uring"
                self._uring_arm(self._ring[0])
        if engine_mode == "io_uring" and self.engine_mode != "io_uring":
            from libjitsi_tpu.utils.logging import get_logger

            # explicit request degraded: must not be silent (mirrors
            # the kernel_timestamps contract above)
            get_logger("io.udp").warn(
                "io_uring_unavailable_fallback", port=self.port)

    def _uring_arm(self, a: _Arena) -> None:
        """Hand a whole (unpinned) arena to the kernel as ONE linked
        chain of recvs.  The gen bump invalidates any stale token from
        the arena's previous occupancy — same contract as the recvmmsg
        path's per-window bump, at arena granularity."""
        a.gen += 1
        rc = _load().udp_uring_arm(
            self._u, a.buf.ctypes.data, self._rows, self.capacity,
            a.len.ctypes.data, a.sip.ctypes.data, a.sport.ctypes.data,
            a.ats.ctypes.data)
        if rc < 0:
            raise OSError(-rc, os.strerror(-rc))
        self._uring_arena = a
        self._alias_arena(a)

    @property
    def syscall_enters(self) -> int:
        """Batches that entered the kernel: native recvmmsg/sendmmsg
        calls plus actual io_uring_enter syscalls (ring-side reaps and
        in-kernel chain cascades cost zero)."""
        if self._u is not None:
            return self._py_enters + int(_load().udp_uring_stat(self._u, 0))
        return self._py_enters

    @property
    def ring_reaps(self) -> int:
        """Completions reaped ring-side without entering the kernel."""
        if self._u is not None:
            return int(_load().udp_uring_stat(self._u, 1))
        return 0

    def _alias_arena(self, a: _Arena) -> None:
        # legacy aliases: the most recently used arena's raw arrays
        self._buf, self._len = a.buf, a.len
        self._sip, self._sport, self._ats = a.sip, a.sport, a.ats

    def _next_arena(self) -> _Arena:
        """Unpinned arena at the ring cursor, growing the ring when
        every arena still has a live view in flight (the invariant: a
        pinned arena is NEVER handed back to the kernel)."""
        ring = self._ring
        for _ in range(len(ring)):
            a = ring[self._ring_pos]
            if a.pins == 0:
                return a
            self._ring_pos = (self._ring_pos + 1) % len(ring)
        a = _Arena(self._rows, self.capacity)
        ring.insert(self._ring_pos, a)
        self.arena_grows += 1
        return a

    def release_arena(self, token) -> None:
        """Drop the pin a `recv_batch_view` placed; `token` is the
        batch's `arena_token`.  Idempotent — a second release of the
        same token is a no-op, it can never steal another view's pin."""
        if token is None:
            return
        if isinstance(token, _ArenaToken):
            if token.released:
                return
            token.released = True
            a, gen = token.arena, token.gen
        else:  # legacy (arena, gen) tuple: generation-checked only
            a, gen = token
        if a.gen == gen and a.pins > 0:
            a.pins -= 1

    @classmethod
    def create_with_retry(cls, retries: int = 5, backoff_s: float = 0.05,
                          sleep=None, **kwargs) -> "UdpEngine":
        """Bind with bounded retry + exponential backoff.

        The crash-restart path: a just-killed worker's socket can linger
        briefly (or an init race holds the port), and the restarted
        process must ride that out instead of dying — but boundedly, so
        a genuinely-taken port still fails loudly."""
        import time as _time

        from libjitsi_tpu.utils.health import retrying

        return retrying(lambda: cls(**kwargs), retries=retries,
                        backoff_s=backoff_s,
                        sleep=_time.sleep if sleep is None else sleep)

    def _recv_arena(self, timeout_ms: int, want_ts: bool):
        """Receive one batching window.  Returns (arena, lo, n): the
        window's packets live in arena rows [lo, lo+n).  recvmmsg mode
        scatters into a fresh (unpinned) arena at lo=0; io_uring mode
        delivers the next completed prefix of the armed arena, so lo
        advances across windows until the arena is exhausted.  Either
        way the arena's gen was bumped when its occupancy began, so any
        stale token from a previous occupancy is invalidated."""
        lib = _load()
        limit = max(1, min(int(self.max_batch), self._rows))
        if self._u is not None:
            start = ctypes.c_int32(0)
            n = lib.udp_uring_recv(self._u, limit, timeout_ms,
                                   ctypes.byref(start))
            if n == _URING_ARENA_EXHAUSTED:
                # every row delivered => the kernel holds no reference;
                # re-arm through the ring (grow-never-reuse: a pinned
                # arena is skipped, the ring grows if all are pinned)
                self._uring_arm(self._next_arena())
                n = lib.udp_uring_recv(self._u, limit, timeout_ms,
                                       ctypes.byref(start))
            if n < 0:
                raise OSError(-n, os.strerror(-n))
            return self._uring_arena, int(start.value), n
        a = self._next_arena()
        a.gen += 1
        self._alias_arena(a)
        self._py_enters += 1
        if want_ts:
            n = lib.udp_recv_batch_ts(
                self._fd, a.buf.ctypes.data, self.capacity, limit,
                a.len.ctypes.data, a.sip.ctypes.data,
                a.sport.ctypes.data, a.ats.ctypes.data, timeout_ms)
        else:
            n = lib.udp_recv_batch(
                self._fd, a.buf.ctypes.data, self.capacity, limit,
                a.len.ctypes.data, a.sip.ctypes.data,
                a.sport.ctypes.data, timeout_ms)
        if n < 0:
            raise OSError(-n, os.strerror(-n))
        return a, 0, n

    def recv_batch(self, timeout_ms: int = 1
                   ) -> Tuple[PacketBatch, np.ndarray, np.ndarray]:
        """One batching window: up to max_batch datagrams.

        Returns (batch, src_ip_u32, src_port); batch_size 0 on timeout.
        The batching window (timeout for the first packet + drain) is
        the latency/throughput knob from SURVEY §7 step 4.  Copy
        semantics: callers may hold the batch indefinitely.  Hot paths
        use `recv_batch_view` instead.
        """
        a, lo, n = self._recv_arena(timeout_ms, want_ts=False)
        hi = lo + n
        batch = PacketBatch(a.buf[lo:hi].copy(),  # jitlint: disable=hotpath-alloc
                            a.len[lo:hi].copy(),
                            np.full(n, -1, dtype=np.int32))
        # jitlint: disable=hotpath-alloc — copy-semantics API by contract
        return batch, a.sip[lo:hi].copy(), a.sport[lo:hi].copy()

    def recv_batch_view(self, timeout_ms: int = 1
                        ) -> Tuple[PacketBatch, np.ndarray, np.ndarray]:
        """Zero-copy `recv_batch`: the returned batch's data/length are
        in-place VIEWS of the recv arena, tagged with `arena_token`.
        The arena stays pinned (never re-handed to the kernel) until
        the caller passes that token to `release_arena` — exactly once
        per returned batch."""
        a, lo, n = self._recv_arena(timeout_ms, want_ts=False)
        hi = lo + n
        batch = PacketBatch(a.buf[lo:hi], a.len[lo:hi],
                            np.full(n, -1, dtype=np.int32))
        if n > 0:
            a.pins += 1
            batch.arena_token = _ArenaToken(a, a.gen)
        return batch, a.sip[lo:hi], a.sport[lo:hi]

    def recv_batch_ts(self, timeout_ms: int = 1
                      ) -> Tuple[PacketBatch, np.ndarray, np.ndarray,
                                 np.ndarray]:
        """`recv_batch` plus per-packet KERNEL arrival times (ns,
        CLOCK_REALTIME; skb-receive stamps when `kernel_timestamps` is
        enabled, else a per-batch syscall-time fallback).  Feed these to
        the GCC inter-arrival filters — userspace arrival times carry
        scheduler jitter the kernel stamp does not."""
        a, lo, n = self._recv_arena(timeout_ms, want_ts=True)
        hi = lo + n
        batch = PacketBatch(a.buf[lo:hi].copy(),  # jitlint: disable=hotpath-alloc
                            a.len[lo:hi].copy(),
                            np.full(n, -1, dtype=np.int32))
        # jitlint: disable=hotpath-alloc — copy-semantics API by contract
        return (batch, a.sip[lo:hi].copy(), a.sport[lo:hi].copy(),
                a.ats[lo:hi].copy())  # jitlint: disable=hotpath-alloc

    def recv_batch_ts_view(self, timeout_ms: int = 1
                           ) -> Tuple[PacketBatch, np.ndarray, np.ndarray,
                                      np.ndarray]:
        """Zero-copy `recv_batch_ts` (see `recv_batch_view` for the
        arena-pinning contract)."""
        a, lo, n = self._recv_arena(timeout_ms, want_ts=True)
        hi = lo + n
        batch = PacketBatch(a.buf[lo:hi], a.len[lo:hi],
                            np.full(n, -1, dtype=np.int32))
        if n > 0:
            a.pins += 1
            batch.arena_token = _ArenaToken(a, a.gen)
        return batch, a.sip[lo:hi], a.sport[lo:hi], a.ats[lo:hi]

    @staticmethod
    def _rows_u8(arr: np.ndarray) -> np.ndarray:
        # a uint8 matrix whose ROWS are contiguous goes to the C ABI as
        # it is, under its own row stride: a column slice of a wider
        # plane (the fan-out's `[rows, :width]` of the plane the device
        # returned) is not copied.  Only other layouts are materialized
        if (arr.dtype == np.uint8 and arr.ndim == 2
                and arr.strides[1] == 1 and arr.strides[0] >= arr.shape[1]):
            return arr
        return np.ascontiguousarray(arr, dtype=np.uint8)  # jitlint: disable=hotpath-alloc

    def _stage_send(self, batch: PacketBatch, dst_ip, dst_port):
        """(data, lens, ips, ports) as the C ABI takes them, for all
        rows of `batch`; dst_ip (u32 or dotted str) / dst_port
        broadcast."""
        n = batch.batch_size
        if isinstance(dst_ip, str):
            dst_ip = ip_to_u32(dst_ip)
        ips = np.broadcast_to(np.asarray(dst_ip, dtype=np.uint32), (n,))
        ports = np.broadcast_to(np.asarray(dst_port, dtype=np.uint16), (n,))
        data = self._rows_u8(batch.data)
        # O(n) metadata staging for the C ABI (int32/u32/u16 arrays),
        # not O(n*capacity) payload bytes
        lens = np.ascontiguousarray(  # jitlint: disable=hotpath-alloc
            batch.length, dtype=np.int32)
        ips = np.ascontiguousarray(ips)  # jitlint: disable=hotpath-alloc
        ports = np.ascontiguousarray(ports)  # jitlint: disable=hotpath-alloc
        return data, lens, ips, ports

    def send_batch(self, batch: PacketBatch, dst_ip, dst_port) -> int:
        """Send all rows; dst_ip (u32 or dotted str) / dst_port broadcast.

        Synchronous: runs inline on the caller when nothing is queued
        on the egress worker, and behind whatever is (the native call
        waits for the worker to fall idle first), so a leg never sees
        this datagram before one handed over earlier."""
        n = batch.batch_size
        if n == 0:
            return 0
        data, lens, ips, ports = self._stage_send(batch, dst_ip, dst_port)
        self._py_enters += 1
        sent = _load().udp_send_batch(
            self._fd, data.ctypes.data, data.strides[0],
            lens.ctypes.data, ips.ctypes.data, ports.ctypes.data, n)
        if sent < 0:
            raise OSError(-sent, os.strerror(-sent))
        return sent

    def send_batch_async(self, batch: PacketBatch, dst_ip, dst_port
                         ) -> Optional["SendJob"]:
        """Hand all rows to the socket's egress worker (one native
        thread, started by the first call) and return at once: the
        `sendmmsg` runs there, in hand-over order, while the caller
        goes on.  Returns the job (None for an empty batch); its result
        comes from `reap()`.

        The caller's promise: `batch.data` is not written to until the
        job is reaped (the plane is not copied; the engine holds the
        references).  A caller that reuses its buffer (an arena view,
        scratch) uses `send_batch`.  Waits only when `MAX_QUEUED_JOBS`
        are already queued: overload shows as the caller's time, not as
        memory."""
        n = batch.batch_size
        if n == 0:
            return None
        arrays = self._stage_send(batch, dst_ip, dst_port)
        data, lens, ips, ports = arrays
        behind = ctypes.c_int(0)
        self._py_enters += 1
        job = _load().udp_send_async(
            self._fd, data.ctypes.data, data.strides[0], lens.ctypes.data,
            ips.ctypes.data, ports.ctypes.data, n, ctypes.byref(behind))
        if job < 0:
            raise OSError(-job, os.strerror(-job))
        self._jobs[job] = arrays
        return SendJob(int(job), n, bool(behind.value))

    def reap(self) -> List["SendDone"]:
        """Completions of asynchronous sends so far, oldest first; never
        blocks.  Each job comes back once, and its arrays are released
        here.  `sent` is the datagrams sent or `-errno`; `t0` / `t1`
        are `time.perf_counter()` seconds at which the worker began and
        ended the job's `sendmmsg`."""
        if not self._jobs:
            return []
        out = self._reap_out
        if out is None:
            k = MAX_QUEUED_JOBS + 2
            out = self._reap_out = (
                np.zeros(k, dtype=np.int64), np.zeros(k, dtype=np.int32),
                np.zeros(k, dtype=np.int64), np.zeros(k, dtype=np.int64))
        ids, sent, t0, t1 = out
        done: List[SendDone] = []
        lib = _load()
        while True:
            k = lib.udp_send_reap(self._fd, ids.ctypes.data,
                                  sent.ctypes.data, t0.ctypes.data,
                                  t1.ctypes.data, len(ids))
            for i in range(k):
                self._jobs.pop(int(ids[i]), None)
                done.append(SendDone(int(ids[i]), int(sent[i]),
                                     int(t0[i]) * 1e-9, int(t1[i]) * 1e-9))
            if k < len(ids):
                return done

    def flush(self) -> None:
        """Wait until every asynchronous send handed over has been sent
        (their completions stay to be reaped)."""
        if self._jobs:
            _load().udp_send_flush(self._fd)

    def send_rows(self, batch: PacketBatch, rows, dst_ip, dst_port) -> int:
        """Gather-send selected rows in ONE multi-destination sendmmsg.

        `rows` indexes into `batch`; `dst_ip`/`dst_port` are scalars or
        per-selected-row arrays (in `rows` order).  The native iovec
        gather IS the row selection — the host never materializes a
        contiguous copy of the egress subset.  Synchronous, and ordered
        behind the egress worker as `send_batch` is."""
        rows = np.asarray(rows, dtype=np.int32)
        n = int(rows.shape[0])
        if n == 0:
            return 0
        if isinstance(dst_ip, str):
            dst_ip = ip_to_u32(dst_ip)
        lib = _load()
        data = batch.data
        if data.dtype != np.uint8 or not data.flags["C_CONTIGUOUS"]:
            sub = PacketBatch(data[rows],  # jitlint: disable=hotpath-alloc
                              np.asarray(batch.length)[rows],
                              np.asarray(batch.stream)[rows])
            return self.send_batch(sub, dst_ip, dst_port)
        # O(n) metadata staging for the C ABI; the payload rows
        # themselves go out via iovec gather
        lens = np.ascontiguousarray(  # jitlint: disable=hotpath-alloc
            np.asarray(batch.length, dtype=np.int32)[rows])
        ips = np.ascontiguousarray(np.broadcast_to(  # jitlint: disable=hotpath-alloc
            np.asarray(dst_ip, dtype=np.uint32), (n,)))
        ports = np.ascontiguousarray(np.broadcast_to(  # jitlint: disable=hotpath-alloc
            np.asarray(dst_port, dtype=np.uint16), (n,)))
        idx = np.ascontiguousarray(rows)  # jitlint: disable=hotpath-alloc
        self._py_enters += 1
        sent = lib.udp_send_batch_idx(
            self._fd, data.ctypes.data, data.shape[1],
            lens.ctypes.data, ips.ctypes.data, ports.ctypes.data,
            idx.ctypes.data, n)
        if sent < 0:
            raise OSError(-sent, os.strerror(-sent))
        return sent

    def close(self) -> None:
        """Send what was handed over, join the egress worker, close the
        socket.  A second call is a no-op."""
        # the worker still reads the jobs' arrays: everything out
        # first, then the references may go
        self.flush()
        self.reap()
        if self._u is not None:
            # cancels any armed recvs and drains before the arenas can
            # be collected — MUST precede closing the socket fd
            _load().udp_uring_destroy(self._u)
            self._u = None
            self._uring_arena = None
        if self._fd >= 0:
            _load().udp_close(self._fd)
            self._fd = -1

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
