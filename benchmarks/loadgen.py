"""Open-loop talk generator and client-side bookkeeping.

One general generator, driven by a traffic file's parameters: which
conferences are active, who speaks in them, the packet schedule (a
fixed period per speaking endpoint, phases spread over the period,
optional talk spurts, bursts, loss and reorder on the client legs) and
the payload sizes.  Every member of an active conference, speaking or
not, sends one packet in the plan's first period: the bridge learns a
leg's address from that leg's own packets alone, and a member that
never reached it would receive nothing.  Nothing here knows a cell's
name.

Three kinds of process use this module, none of which imports JAX:

* the harness (`run.py`) calls `make_plan` / `build_schedule` /
  `analyze` — numpy only;
* one sender child (`python loadgen.py sender`) protects the whole
  schedule under the scalar oracle beforehand, then emits every packet
  at its DUE time from the speaking endpoint's own socket, open loop:
  a late bridge never slows the offered stream;
* receiver children (`python loadgen.py receiver`) read the same
  sockets and keep, for every datagram, the receiving endpoint, the
  sender's SSRC and sequence number and two receive stamps: the
  kernel's (`SO_TIMESTAMPNS`: taken when the bridge's send reaches the
  socket, so a starved receiver process does not read as a slow
  bridge) and the process's own clock read right after the recv.  A
  run uses the kernel's where EVERY datagram carries one, else its own
  (the chip machine's kernel gives none) and says which.  Plus the raw
  bytes of a seeded sample for the oracle.

Clock: `CLOCK_REALTIME` everywhere — it is the clock of the kernel's
receive stamp, and all processes of a run share it.  Added latency of a
delivery is `t_rx - due`, where `due` is when the schedule said the
source packet had to leave, NOT when the sender got round to it; how
late the sender ran is reported beside it.
"""

from __future__ import annotations

import hashlib
import json
import os
import select
import socket
import struct
import subprocess
import sys
import time

import numpy as np

SSRC_BASE = 0x10000
FIRST_INDEX = 1000
RTP_PT = 96
# faults the generator plays (the harness plays `bridge-bitflip`)
CLIENT_FAULTS = ("client-key-bit", "no-latch")
TS_PER_PACKET = 960           # Opus at 48 kHz, 20 ms (RFC 7587)
SO_TIMESTAMPNS = 35           # asm-generic/socket.h (the _OLD value)
SO_RXQ_OVFL = 40
REC = np.dtype([("rx", "<u2"), ("ssrc", "<u4"), ("seq", "<u2"),
                ("b1", "u1"), ("pad", "u1"), ("len", "<u2"),
                ("t_kernel", "<i8"), ("t_user", "<i8")])
_REC_FMT = "<HIHBBHqq"
assert struct.calcsize(_REC_FMT) == REC.itemsize


# ---------------------------------------------------------------- plan

def endpoint_keys(seed: int, rows: int, salt_len: int):
    """Master keys of every endpoint of the deployment, from the seed:
    `[rows, 2]` (client->bridge, bridge->client) of (key16, salt)."""
    rng = np.random.default_rng([int(seed), 0x6B657973])
    raw = rng.integers(0, 256, (rows, 2, 16 + salt_len), dtype=np.uint8)
    return raw


def key_pair(raw_row) -> tuple:
    b = bytes(raw_row)
    return b[:16], b[16:]


def active_conferences(n_conf: int, a: int) -> list:
    """`a` conferences spread evenly over the table: first, last and
    evenly between."""
    a = max(1, min(int(a), n_conf))
    return sorted({int(c) for c in np.linspace(0, n_conf - 1, a)})


def resolve_rate(traffic: dict, config_name: str) -> int:
    """Active conferences of a mix under a configuration: an absolute
    `active_conferences`, or a share of the knee recorded for that
    configuration (rounded down below 1.0, up above)."""
    rate = traffic["rate"]
    if "active_conferences" in rate:
        return int(rate["active_conferences"])
    knee = rate["knee_active_conferences"][config_name]
    x = float(rate["share_of_knee"]) * knee
    return int(np.floor(x + 1e-9)) if rate["share_of_knee"] < 1 \
        else int(np.ceil(x - 1e-9))


def make_plan(config: dict, traffic: dict, seed: int, n_active: int,
              duration_s: float, first_index: int = FIRST_INDEX,
              fault: str = "", sample_over_s: float = None) -> dict:
    """Everything the children and the analysis derive a window from.
    JSON-serialisable; the same plan gives the same schedule."""
    sizes = config["conference_sizes"]
    if len(set(sizes)) != 1:
        raise ValueError("the generator takes one conference size")
    conf_size = int(sizes[0])
    n_conf = int(config["capacity"]) // conf_size
    return {
        "seed": int(seed), "suite": config["profile"],
        "rows": int(config["capacity"]), "conf_size": conf_size,
        "active": active_conferences(n_conf, n_active),
        "speakers": int(traffic.get("speakers_per_conference")
                        or conf_size),
        "period_ms": float(traffic.get("packet_period_ms", 20.0)),
        "duration_s": float(duration_s),
        "first_index": int(first_index),
        "payload": traffic.get("payload", {"min": 40, "max": 160}),
        "talk_spurt": traffic.get("talk_spurt"),
        "burst_factor": float(traffic.get("burst_factor", 1.0)),
        "client_loss_pct": float(traffic.get("client_loss_pct", 0.0)),
        "client_reorder_pct": float(
            traffic.get("client_reorder_pct", 0.0)),
        "sample_target": int(traffic.get("sample_target", 8192)),
        # the seconds of traffic the sample is spread over (the plan
        # itself is longer: the lead-in may stretch)
        "sample_over_s": float(sample_over_s or duration_s),
        "fault": fault,
    }


def plan_endpoints(plan: dict) -> np.ndarray:
    """Global endpoint rows (= ssrc - SSRC_BASE) holding a socket, in
    socket order: every endpoint of every active conference."""
    cs = plan["conf_size"]
    return np.concatenate([np.arange(c * cs, (c + 1) * cs)
                           for c in plan["active"]]).astype(np.int64)


def build_schedule(plan: dict) -> dict:
    """The offered stream: arrays sorted by due time.

    `sock` (index into `plan_endpoints`), `index` (SRTP packet index,
    also the RTP sequence number: windows stay under 2**16), `due_ns`
    (offset from the window's t0).  Also `due_of` [sockets, slots]: due
    offset of (socket, index - first_index), -1 where nothing is sent.

    Every socket sends index `first_index` inside the first period, at
    a phase of its own: it stands for what ICE and DTLS do before media
    (the generator has neither) and is how the bridge learns the leg's
    address.  A speaker goes on from there; one that a `talk_spurt`
    starts "off" then keeps silent until its first spurt; a member
    outside `speakers_per_conference` (a listener) sends nothing more.
    The fault `no-latch` withholds the listeners' packets.  The draws
    keep their order and the listeners' phases come last, so a plan in
    which every member speaks and no spurt is set gives the schedule it
    always gave.
    """
    rng = np.random.default_rng([plan["seed"], 0x7363686564])
    cs, sp = plan["conf_size"], plan["speakers"]
    n_sock = len(plan["active"]) * cs
    speaking = np.zeros(n_sock, dtype=bool)
    for k in range(len(plan["active"])):
        speaking[k * cs:k * cs + sp] = True
    spk = np.nonzero(speaking)[0]
    e = len(spk)
    period = int(plan["period_ms"] * 1e6)
    slots = int(np.ceil(plan["duration_s"] * 1e9 / period))
    # phases: spread evenly over the period (a smooth offered stream),
    # in an order drawn from the seed; burst_factor > 1 squeezes them
    # into the first 1/burst_factor of the period
    phase = (rng.permutation(e).astype(np.int64) * period
             // int(e * plan["burst_factor"]))
    on = np.ones((e, slots), dtype=bool)
    ts = plan["talk_spurt"]
    if ts:
        # alternating exponential on/off times per endpoint
        for i in range(e):
            t, state = 0.0, bool(rng.integers(0, 2))
            row = np.zeros(slots, dtype=bool)
            while t < slots * period / 1e9:
                d = rng.exponential(ts["on_s"] if state else ts["off_s"])
                if state:
                    a = int(t * 1e9 // period)
                    b = int((t + d) * 1e9 // period) + 1
                    row[a:b] = True
                t, state = t + d, not state
            on[i] = row
        on[:, 0] = True
    index = plan["first_index"] + np.cumsum(on, axis=1) - 1
    due = phase[:, None] + np.arange(slots, dtype=np.int64)[None, :] \
        * period
    if plan["client_reorder_pct"] > 0:
        swap = rng.random((e, slots - 1)) < plan["client_reorder_pct"] / 100
        swap[:, 1:] &= ~swap[:, :-1]
        a, b = np.nonzero(swap)
        due[a, b], due[a, b + 1] = due[a, b + 1], due[a, b].copy()
    sent = on.copy()
    if plan["client_loss_pct"] > 0:
        sent &= rng.random((e, slots)) >= plan["client_loss_pct"] / 100
        sent[:, 0] = True       # ICE and DTLS retransmit; media does not
    if int(index.max(initial=0)) >= 1 << 16:
        raise ValueError("window would wrap the 16-bit sequence space")
    rr, kk = np.nonzero(sent)
    order = np.argsort(due[rr, kk], kind="stable")
    rr, kk = rr[order], kk[order]
    sock, idx, due_ns = spk[rr], index[rr, kk], due[rr, kk]
    lis = np.nonzero(~speaking)[0]
    if len(lis) and plan["fault"] != "no-latch":
        lphase = (rng.permutation(len(lis)).astype(np.int64) * period
                  // len(lis))
        sock = np.concatenate([sock, lis])
        idx = np.concatenate([idx, np.full(len(lis), plan["first_index"])])
        due_ns = np.concatenate([due_ns, lphase])
        order = np.argsort(due_ns, kind="stable")
        sock, idx, due_ns = sock[order], idx[order], due_ns[order]
    due_of = np.full((n_sock, slots), -1, dtype=np.int64)
    due_of[sock, idx - plan["first_index"]] = due_ns
    return {"sock": sock.astype(np.int32), "index": idx.astype(np.int64),
            "due_ns": due_ns.astype(np.int64),
            "due_of": due_of, "period_ns": period}


def payload_of(plan_seed: int, spec: dict, ssrc: int, index: int) -> bytes:
    """Payload bytes of one packet, from the seed.  Length: `fixed`, or
    40 + the smaller of two uniform draws on [0, max - min] (mean about
    a third of the span above `min`: Opus VBR leans short)."""
    h = hashlib.sha256(b"%d/%d/%d" % (plan_seed, ssrc, index)).digest()
    if "fixed" in spec:
        n = int(spec["fixed"])
    else:
        span = int(spec["max"]) - int(spec["min"]) + 1
        u1 = int.from_bytes(h[0:4], "big") % span
        u2 = int.from_bytes(h[4:8], "big") % span
        n = int(spec["min"]) + min(u1, u2)
    return (h * (n // 32 + 1))[:n]


def plain_packet(plan: dict, ssrc: int, index: int) -> bytes:
    hdr = (bytes([0x80, RTP_PT]) + (index & 0xFFFF).to_bytes(2, "big")
           + ((index * TS_PER_PACKET) & 0xFFFFFFFF).to_bytes(4, "big")
           + ssrc.to_bytes(4, "big"))
    return hdr + payload_of(plan["seed"], plan["payload"], ssrc, index)


def client_keys(plan: dict):
    """Keys as the CLIENTS hold them.  The fault `client-key-bit` flips
    one bit of every client key against what the bridge was given: the
    documented way to see `correct` come out false."""
    from oracle import SUITES

    raw = endpoint_keys(plan.get("key_seed", plan["seed"]), plan["rows"],
                        SUITES[plan["suite"]][2]).copy()
    if plan["fault"] == "client-key-bit":
        raw[:, :, 3] ^= 0x10
    return raw


# ------------------------------------------------------------ children

def _raise_nofile() -> None:
    import resource

    _soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))


def _read_line(fd_file) -> dict:
    line = fd_file.readline()
    if not line:
        raise SystemExit(0)             # parent went away
    return json.loads(line)


def _say(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def sender_main() -> None:
    """stdin: the spec line, then `{"go": t0_ns, "port": p}`, then
    optionally `{"until": t_ns}`.  stdout: `ready`, then `done`."""
    from oracle import SUITES

    _raise_nofile()
    spec = _read_line(sys.stdin)
    plan, fds, out = spec["plan"], spec["fds"], spec["out"]
    protect = SUITES[plan["suite"]][0]
    socks = [socket.socket(fileno=fd) for fd in fds]
    sched = build_schedule(plan)
    keys = client_keys(plan)
    eps = plan_endpoints(plan)
    t_prep = time.perf_counter()
    pkts = []
    for s, idx in zip(sched["sock"].tolist(), sched["index"].tolist()):
        row = int(eps[s])
        pkts.append(protect(*key_pair(keys[row, 0]),
                            plain_packet(plan, SSRC_BASE + row, idx),
                            idx))
    sends = [socks[s].sendto for s in sched["sock"].tolist()]
    due = sched["due_ns"].tolist()
    n = len(due)
    late = np.zeros(n, dtype=np.int64)
    _say({"ready": True, "packets": n,
          "protect_s": time.perf_counter() - t_prep})
    go = _read_line(sys.stdin)
    t0, addr = int(go["go"]), ("127.0.0.1", int(go["port"]))
    until = None
    i, now_ns, sleep = 0, time.time_ns, time.sleep
    poll = select.poll()
    poll.register(sys.stdin.fileno(), select.POLLIN)
    next_ctl = 0
    errors = 0
    while i < n:
        now = now_ns()
        d = t0 + due[i]
        if until is not None and d >= until:
            break
        if now >= next_ctl:
            next_ctl = now + 50_000_000
            if poll.poll(0):
                msg = _read_line(sys.stdin)
                if "until" in msg:
                    until = int(msg["until"])
                continue
        if d > now:
            if d - now > 400_000:
                sleep((d - now - 250_000) / 1e9)
            continue
        try:
            sends[i](pkts[i], addr)
        except OSError:
            errors += 1
        late[i] = now - d
        i += 1
    np.save(out, late[:i])
    _say({"done": True, "sent": i, "send_errors": errors})


def _proc_udp_drops(socks) -> int:
    """Datagrams the kernel dropped at these sockets' receive queues,
    from `/proc/net/udp` (a second source beside `SO_RXQ_OVFL`, which
    only shows on a datagram that is received afterwards); 0 where the
    file cannot be read."""
    ports = {s.getsockname()[1] for s in socks}
    total = 0
    try:
        with open("/proc/net/udp") as f:
            next(f)
            for line in f:
                col = line.split()
                if int(col[1].rsplit(":", 1)[1], 16) in ports:
                    total += int(col[-1])
    except (OSError, ValueError, IndexError, StopIteration):
        return 0
    return total


def receiver_main() -> None:
    """stdin: the spec line, then `{"stop": true}`.  Records every
    datagram until told to stop, then writes them to `out` (npz)."""
    _raise_nofile()
    spec = _read_line(sys.stdin)
    fds, rx_ids, out = spec["fds"], spec["rx"], spec["out"]
    seed, thresh = int(spec["seed"]) & 0xFFFF, int(spec["sample_thresh"])
    cap = int(spec["max_records"])
    socks = [socket.socket(fileno=fd) for fd in fds]
    ep = select.epoll()
    by_fd = {}
    for s, rx in zip(socks, rx_ids):
        s.setblocking(False)
        s.setsockopt(socket.SOL_SOCKET, SO_TIMESTAMPNS, 1)
        s.setsockopt(socket.SOL_SOCKET, SO_RXQ_OVFL, 1)
        ep.register(s.fileno(), select.EPOLLIN)
        by_fd[s.fileno()] = (s.recvmsg_into, rx)
    ctl = sys.stdin.fileno()
    ep.register(ctl, select.EPOLLIN)
    recbuf = bytearray(cap * REC.itemsize)
    buf = bytearray(2048)
    bufs = [buf]
    anc = socket.CMSG_SPACE(16) + socket.CMSG_SPACE(4)
    unpack_hdr = struct.Struct("!BBHII").unpack_from
    pack_rec = struct.Struct(_REC_FMT).pack_into
    unpack_ts = struct.Struct("qq").unpack
    unpack_u32 = struct.Struct("I").unpack
    n = 0
    drops = {}
    now_ns = time.time_ns
    sample_no, sample_raw = [], []
    stop = False
    _say({"ready": True})

    def drain(fd):
        nonlocal n
        recv, rx = by_fd[fd]
        while True:
            try:
                nb, cmsgs, _fl, _ad = recv(bufs, anc)
            except BlockingIOError:
                return
            t = 0
            for _lvl, typ, data in cmsgs:
                if typ == SO_TIMESTAMPNS:
                    sec, nsec = unpack_ts(data[:16])
                    t = sec * 1_000_000_000 + nsec
                elif typ == SO_RXQ_OVFL:
                    drops[fd] = unpack_u32(data[:4])[0]
            if nb < 12 or n >= cap:
                continue
            _b0, b1, seq, _ts, ssrc = unpack_hdr(buf)
            pack_rec(recbuf, n * REC.itemsize, rx, ssrc, seq, b1, 0, nb,
                     t, now_ns())
            if ((ssrc * 2654435761 + seq * 40503 + rx * 97 + seed)
                    & 0xFFFF) < thresh:
                sample_no.append(n)
                sample_raw.append(bytes(buf[:nb]))
            n += 1

    while not stop:
        for fd, _ev in ep.poll(0.05):
            if fd == ctl:
                _read_line(sys.stdin)
                stop = True
            else:
                drain(fd)
    for fd in by_fd:       # whatever reached the sockets before the stop
        drain(fd)
    recs = np.frombuffer(bytes(recbuf[:n * REC.itemsize]), dtype=REC)
    lens = np.array([len(b) for b in sample_raw], dtype=np.int64)
    np.savez(out, recs=recs,
             sample_no=np.array(sample_no, dtype=np.int64),
             sample_len=lens,
             sample_blob=np.frombuffer(b"".join(sample_raw),
                                       dtype=np.uint8))
    _say({"done": True, "records": n, "overflow": n >= cap,
          "rx_drops": max(int(sum(drops.values())),
                          _proc_udp_drops(socks))})


# -------------------------------------------------------- the harness side

class Generator:
    """The children of one window, seen from the harness: sockets made
    here (before JAX is touched), handed to one sender and `n_recv`
    receivers by file descriptor."""

    def __init__(self, plan: dict, workdir: str, n_recv: int = 4):
        _raise_nofile()
        self.plan = plan
        self.workdir = workdir
        os.makedirs(workdir, exist_ok=True)
        eps = plan_endpoints(plan)
        self.socks = []
        for _ in eps:
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 21)
            s.bind(("127.0.0.1", 0))
            self.socks.append(s)
        fds = [s.fileno() for s in self.socks]
        here = os.path.dirname(os.path.abspath(__file__))
        script = os.path.join(here, "loadgen.py")
        env = dict(os.environ, PYTHONPATH=here)

        def spawn(role, child_fds, spec):
            p = subprocess.Popen(
                [sys.executable, script, role], stdin=subprocess.PIPE,
                stdout=subprocess.PIPE, pass_fds=child_fds, env=env,
                text=True, bufsize=1)
            p.stdin.write(json.dumps(spec) + "\n")
            p.stdin.flush()
            return p

        self.sender_out = os.path.join(workdir, "late.npy")
        self.sender = spawn("sender", fds, {
            "plan": plan, "fds": fds, "out": self.sender_out})
        # the sender child builds the same schedule from the same plan
        self.sched = build_schedule(plan)
        sched_n = self.expected_events()
        cs = plan["conf_size"]
        deliveries = sched_n * (cs - 1)
        thresh = int(min(65536, np.ceil(
            65536 * plan["sample_target"] / max(1, deliveries)
            * plan["duration_s"] / plan["sample_over_s"])))
        self.receivers, self.recv_out = [], []
        n_recv = max(1, min(n_recv, len(plan["active"])))
        for r in range(n_recv):
            mine = [k for k in range(len(eps))
                    if (k // cs) % n_recv == r]
            out = os.path.join(workdir, f"rx{r}.npz")
            self.recv_out.append(out)
            self.receivers.append(spawn("receiver",
                                        [fds[k] for k in mine], {
                "fds": [fds[k] for k in mine], "rx": mine, "out": out,
                "seed": plan["seed"], "sample_thresh": thresh,
                "max_records": int(deliveries * len(mine)
                                   / max(1, len(eps)) * 1.3) + 100_000}))
        for s in self.socks:        # the children hold them now
            s.close()
        self.t0_ns = None
        self.ready_info = None

    def expected_events(self) -> int:
        """Packets the plan really sends (spurts, listeners and client
        loss counted): the receivers' sampling rate follows from it."""
        return len(self.sched["due_ns"])

    def wait_ready(self) -> dict:
        for p in self.receivers:
            _read_line(p.stdout)
        self.ready_info = _read_line(self.sender.stdout)
        return self.ready_info

    def go(self, port: int, lead_s: float = 0.05) -> int:
        self.t0_ns = time.time_ns() + int(lead_s * 1e9)
        self.sender.stdin.write(json.dumps(
            {"go": self.t0_ns, "port": port}) + "\n")
        self.sender.stdin.flush()
        return self.t0_ns

    def until(self, t_ns: int) -> None:
        self.sender.stdin.write(json.dumps({"until": int(t_ns)}) + "\n")
        self.sender.stdin.flush()

    def finish(self) -> dict:
        """Stop everyone, wait for each, return what they wrote."""
        sender_done = _read_line(self.sender.stdout)
        recv_done = []
        for p in self.receivers:
            p.stdin.write('{"stop": true}\n')
            p.stdin.flush()
        for p in self.receivers:
            recv_done.append(_read_line(p.stdout))
        self.close()
        late = np.load(self.sender_out)
        recs, samples = [], []
        for out in self.recv_out:
            with np.load(out) as z:
                r = z["recs"]
                base = sum(len(x) for x in recs)
                off = np.concatenate([[0], np.cumsum(z["sample_len"])])
                blob = z["sample_blob"].tobytes()
                for k, no in enumerate(z["sample_no"].tolist()):
                    samples.append((base + no,
                                    blob[off[k]:off[k + 1]]))
                recs.append(r)
        return {"late_ns": late, "recs": np.concatenate(recs),
                "samples": samples, "sender": sender_done,
                "receivers": recv_done}

    def close(self) -> None:
        for p in [self.sender] + self.receivers:
            for f in (p.stdin, p.stdout):
                try:
                    f.close()
                except OSError:
                    pass
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def analyze(plan: dict, sched: dict, got: dict, t0_ns: int,
            w0_ns: int, w1_ns: int, grace_ns: int) -> dict:
    """Client-side truth of one window `[w0, w1)`.

    Deliveries are matched to the schedule by (sender ssrc, seq): added
    latency is receive stamp minus DUE time.  Latency and loss are over
    source packets DUE in the window (a delivery later than `w1 +
    grace` is lost); `delivered` counts deliveries RECEIVED in the
    window.  Also checks that can fail a run: a delivery from outside
    the receiver's conference or to the sender itself (`foreign`), the
    same delivery twice (`duplicates`), an SSRC/seq nobody sent
    (`unknown`).
    """
    cs = plan["conf_size"]
    eps = plan_endpoints(plan)
    recs = got["recs"]
    media = (recs["b1"] & 0x7F) == RTP_PT
    rtcp = int((~media).sum())
    recs = recs[media]
    n_sock = len(eps)
    sock_of_row = np.full(plan["rows"], -1, dtype=np.int64)
    sock_of_row[eps] = np.arange(n_sock)
    src_row = recs["ssrc"].astype(np.int64) - SSRC_BASE
    in_table = (src_row >= 0) & (src_row < plan["rows"])
    s_sock = np.where(in_table,
                      sock_of_row[np.clip(src_row, 0, plan["rows"] - 1)],
                      -1)
    rx_sock = recs["rx"].astype(np.int64)
    slot = recs["seq"].astype(np.int64) - plan["first_index"]
    known = (s_sock >= 0) & (slot >= 0) & (slot < sched["due_of"].shape[1])
    due_off = np.full(len(recs), -1, dtype=np.int64)
    due_off[known] = sched["due_of"][s_sock[known], slot[known]]
    known &= due_off >= 0
    unknown = int((~known).sum())
    foreign = int((known & ((s_sock // cs != rx_sock // cs)
                            | (s_sock == rx_sock))).sum())
    due = t0_ns + due_off
    # the kernel's receive stamp where the machine gives one on every
    # datagram; else the receiver's own clock read after the recv
    stamp = "kernel" if len(recs) and (recs["t_kernel"] > 0).all() \
        else "user"
    t_rx = recs["t_" + stamp].astype(np.int64)
    key = (rx_sock * n_sock + s_sock) * sched["due_of"].shape[1] + slot
    _u, first, counts = np.unique(key[known], return_index=True,
                                  return_counts=True)
    duplicates = int((counts > 1).sum())
    uniq = np.zeros(len(recs), dtype=bool)
    uniq[np.nonzero(known)[0][first]] = True
    # offered: source packets due in the window
    d_abs = t0_ns + sched["due_ns"]
    in_w = (d_abs >= w0_ns) & (d_abs < w1_ns)
    offered_pkts = int(in_w.sum())
    offered = offered_pkts * (cs - 1)
    due_in_w = uniq & (due >= w0_ns) & (due < w1_ns)
    sel = due_in_w & (t_rx <= w1_ns + grace_ns)
    lat_ns = (t_rx - due)[sel]
    received_due = int(sel.sum())
    # forwarded: source packets of the window with at least one
    # delivery, however late (a tick's deliveries leave in one burst,
    # so no cut-off in time may fall inside it)
    src_key = s_sock[due_in_w] * sched["due_of"].shape[1] + slot[due_in_w]
    fwd_pkts = int(len(np.unique(src_key)))
    delivered = int((uniq & (t_rx >= w0_ns) & (t_rx < w1_ns)).sum())
    q = (lambda a, p: float(np.percentile(a, p)) if len(a) else None)
    # backlog trend: median latency of the last quarter of the window
    # against the first quarter's
    quarter = (w1_ns - w0_ns) // 4
    first_q = lat_ns[(due[sel] < w0_ns + quarter)]
    last_q = lat_ns[(due[sel] >= w1_ns - quarter)]
    late = got["late_ns"]
    return {
        "offered_packets": offered_pkts, "offered": offered,
        "received_due": received_due, "lost": offered - received_due,
        "forwarded_packets": fwd_pkts,
        "forwarded_due": fwd_pkts * (cs - 1),
        "forwarded_received": int(due_in_w.sum()),
        "delivered_in_window": delivered,
        "latency_ns": lat_ns,
        "lat_percentiles_ms": {str(p): q(lat_ns, p) and q(lat_ns, p) / 1e6
                               for p in (50, 75, 90, 95, 98, 99, 99.9,
                                         100)},
        "lat_p50_ms": q(lat_ns, 50) and q(lat_ns, 50) / 1e6,
        "lat_p99_ms": q(lat_ns, 99) and q(lat_ns, 99) / 1e6,
        "lat_first_quarter_p50_ms":
            q(first_q, 50) and q(first_q, 50) / 1e6,
        "lat_last_quarter_p50_ms":
            q(last_q, 50) and q(last_q, 50) / 1e6,
        "stamp": stamp,
        "foreign": foreign, "duplicates": duplicates,
        "unknown": unknown, "rtcp": rtcp,
        "late_p99_ms": q(late, 99) and q(late, 99) / 1e6,
        "late_max_ms": float(late.max() / 1e6) if len(late) else None,
        "rx_drops": int(sum(r["rx_drops"] for r in got["receivers"])),
        "rx_overflow": any(r["overflow"] for r in got["receivers"]),
        "send_errors": int(got["sender"]["send_errors"]),
        "sent": int(got["sender"]["sent"]),
    }


def verify_sample(plan: dict, got: dict) -> dict:
    """Open the seeded sample under the scalar oracle with the CLIENTS'
    keys and hold every opened delivery to the sender's plaintext: the
    fixed header fields past the X bit and the whole payload (the
    bridge stamps abs-send-time, a header extension, on egress)."""
    from oracle import SUITES, payload_off

    _p, unprotect, _sl, _grow = SUITES[plan["suite"]]
    keys = client_keys(plan)
    eps = plan_endpoints(plan)
    recs = got["recs"]
    checked = bad_tag = bad_bytes = 0
    receivers = set()
    for no, wire in got["samples"]:
        rec = recs[no]
        if (int(rec["b1"]) & 0x7F) != RTP_PT:
            continue
        rx_row = int(eps[int(rec["rx"])])
        ssrc, seq = int(rec["ssrc"]), int(rec["seq"])
        checked += 1
        receivers.add(rx_row)
        plain = unprotect(*key_pair(keys[rx_row, 1]), wire, seq)
        if plain is None:
            bad_tag += 1
            continue
        sent = plain_packet(plan, ssrc, seq)
        off = payload_off(plain)
        if (plain[1:12] != sent[1:12] or plain[off:] != sent[12:]
                or (plain[0] & 0xEF) != sent[0]):
            bad_bytes += 1
    return {"checked": checked, "bad_tag": bad_tag,
            "bad_bytes": bad_bytes, "receivers": len(receivers)}


if __name__ == "__main__":
    {"sender": sender_main, "receiver": receiver_main}[sys.argv[1]]()
