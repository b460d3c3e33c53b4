#!/usr/bin/env python3
"""chip_smoke.py — the served SFU bridge, end to end, on one TPU chip.

The quickest proof that the system still starts on the accelerator:
one process drives the path a user drives —

    libjitsi_tpu.init() -> SfuBridge -> BridgeSupervisor ->
    StreamLifecycleManager.request_join -> sup.tick() over loopback UDP

— at the north-star width (BASELINE.json: 10k concurrent SRTP streams;
here 10,240 installed rows), and checks every byte that comes out
against a scalar OpenSSL oracle that shares no code with the device
path.

    python chip_smoke.py              one chip: phases A, B, C
    python chip_smoke.py --chips 4    four chips: the mesh path and its
                                      single-device twin, nothing else

Phases (default run):
  A  full-width crypto: 10,240-row CM and GCM tables, one batch each
     way, >= 256 rows byte-compared with the oracle, a tampered row
     rejected;
  B  kernels: mixer vs NumPy, every Pallas provider compiled for the
     chip (interpret=False) and bit-compared with its XLA twin, then
     the registry report — any provider error fails the run;
  C  the served bridge: 10,240 endpoints admitted through
     `request_join` in conferences of 8, loopback clients in 16 of
     those conferences exchange a few dozen 20 ms ticks of audio.

There is no CPU fallback: without a TPU the script says why and exits
non-zero before phase A.  `--rows N` is for rehearsing the control flow
off the chip at a small size; it lifts the device gate and makes the
last line say `"ok": false`, so a rehearsal can never pass for a chip
run.  No phase is wrapped in a catch: any failure is an exception and a
non-zero exit.

The last line of stdout is one JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import functools
import hashlib
import hmac as hmac_mod
import json
import os
import socket
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
FULL_ROWS = 10_240            # BASELINE.json north star: >= 10k streams
CONF_SIZE = 8                 # endpoints per conference
ACTIVE_CONFS = 16             # conferences with live loopback clients
PAYLOAD = 160                 # 20 ms G.711 / typical Opus: 172 B RTP
FIRST_SEQ = 1000


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# -- scalar SRTP oracle (OpenSSL via `cryptography`; RFC 3711 / RFC 7714;
#    no shared code with the device path) ----------------------------------

def _aes_ctr(key: bytes, iv16: bytes, data: bytes) -> bytes:
    from cryptography.hazmat.primitives.ciphers import (Cipher, algorithms,
                                                        modes)

    enc = Cipher(algorithms.AES(key), modes.CTR(iv16)).encryptor()
    return enc.update(data) + enc.finalize()


def _kdf(mk: bytes, ms: bytes, label: int, n: int) -> bytes:
    x = int.from_bytes(ms.ljust(14, b"\x00"), "big") ^ (label << 48)
    return _aes_ctr(mk, (x << 16).to_bytes(16, "big"), b"\x00" * n)


@functools.lru_cache(maxsize=None)
def _cm_session(mk: bytes, ms: bytes):
    """(cipher key, auth key, salt as int) of one master key pair."""
    return (_kdf(mk, ms, 0, len(mk)), _kdf(mk, ms, 1, 20),
            int.from_bytes(_kdf(mk, ms, 2, 14), "big"))


def _payload_off(pkt: bytes) -> int:
    off = 12 + 4 * (pkt[0] & 0x0F)
    if pkt[0] & 0x10:                       # RFC 3550 §5.3.1 extension
        off += 4 + 4 * int.from_bytes(pkt[off + 2:off + 4], "big")
    return off


def _cm_iv(ksalt: int, pkt: bytes, index: int) -> bytes:
    ssrc = int.from_bytes(pkt[8:12], "big")
    return ((ksalt << 16) ^ (ssrc << 64) ^ (index << 16)).to_bytes(16, "big")


def protect_oracle(mk: bytes, ms: bytes, pkt: bytes, index: int,
                   tag_len: int = 10) -> bytes:
    """AES_CM_128_HMAC_SHA1 protect of one RTP packet (RFC 3711 §3.1)."""
    ke, ka, ksalt = _cm_session(mk, ms)
    off = _payload_off(pkt)
    ct = pkt[:off] + _aes_ctr(ke, _cm_iv(ksalt, pkt, index), pkt[off:])
    tag = hmac_mod.new(ka, ct + (index >> 16).to_bytes(4, "big"),
                       hashlib.sha1).digest()
    return ct + tag[:tag_len]


def unprotect_oracle(mk: bytes, ms: bytes, wire: bytes, index: int,
                     tag_len: int = 10):
    """Inverse of `protect_oracle`; None when the tag does not verify."""
    ke, ka, ksalt = _cm_session(mk, ms)
    ct, tag = wire[:-tag_len], wire[-tag_len:]
    want = hmac_mod.new(ka, ct + (index >> 16).to_bytes(4, "big"),
                        hashlib.sha1).digest()[:tag_len]
    if not hmac_mod.compare_digest(tag, want):
        return None
    off = _payload_off(ct)
    return ct[:off] + _aes_ctr(ke, _cm_iv(ksalt, ct, index), ct[off:])


def protect_oracle_gcm(mk: bytes, ms: bytes, pkt: bytes,
                       index: int) -> bytes:
    """AEAD_AES_128_GCM protect of one RTP packet (RFC 7714 §8-9)."""
    from cryptography.hazmat.primitives.ciphers.aead import AESGCM

    ke, ks = _kdf(mk, ms, 0, len(mk)), _kdf(mk, ms, 2, 12)
    ssrc = int.from_bytes(pkt[8:12], "big")
    iv = (int.from_bytes(ks, "big") ^ (ssrc << 48) ^ index).to_bytes(
        12, "big")
    off = _payload_off(pkt)
    return pkt[:off] + AESGCM(ke).encrypt(iv, pkt[off:], pkt[:off])


def unprotect_oracle_gcm(mk: bytes, ms: bytes, wire: bytes, index: int):
    """Inverse of `protect_oracle_gcm`; None when the tag does not
    verify."""
    from cryptography.exceptions import InvalidTag
    from cryptography.hazmat.primitives.ciphers.aead import AESGCM

    ke, ks = _kdf(mk, ms, 0, len(mk)), _kdf(mk, ms, 2, 12)
    ssrc = int.from_bytes(wire[8:12], "big")
    iv = (int.from_bytes(ks, "big") ^ (ssrc << 48) ^ index).to_bytes(
        12, "big")
    off = _payload_off(wire)
    try:
        return wire[:off] + AESGCM(ke).decrypt(iv, wire[off:], wire[:off])
    except InvalidTag:
        return None


# -- pre-JAX set-up --------------------------------------------------------

def build_native() -> None:
    """Rebuild the UDP engine from the committed source so the run uses
    a library made from files git tracks (the .so is git-ignored and
    `io/udp.py` trusts file times).  No compiler is an error."""
    script = os.path.join(ROOT, "libjitsi_tpu", "native", "build.sh")
    if not os.path.exists(script):
        raise SystemExit(f"chip_smoke: {script} is missing — run from a "
                         "checkout of the repository")
    t0 = time.perf_counter()
    out = subprocess.run(["sh", script], check=True, capture_output=True,
                         text=True)
    say(f"native: {out.stdout.strip()} ({time.perf_counter() - t0:.1f}s)")


def device_gate(rehearsal: bool, chips: int):
    import jax

    devs = jax.devices()
    dev = devs[0]
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    if dev.platform != "tpu" and not rehearsal:
        raise SystemExit(
            f"chip_smoke: JAX found platform {dev.platform!r}, not 'tpu' "
            "— this script proves the program on the accelerator and "
            "has no CPU mode (rehearse with --rows N)")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"devices, JAX reports {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def _peak_bytes() -> dict:
    import jax

    out = {}
    for d in jax.devices():
        st = d.memory_stats() or {}
        out[d.id] = int(st.get("peak_bytes_in_use", 0))
    return out


# -- phase A: full-width crypto --------------------------------------------

def _keys(rng, n: int, salt_len: int):
    import numpy as np

    return (rng.integers(0, 256, (n, 16), dtype=np.uint8),
            rng.integers(0, 256, (n, salt_len), dtype=np.uint8))


def _audio_batch(rng, rows: int, seq: int, ssrc0: int = 0x100000):
    import numpy as np

    from libjitsi_tpu.rtp import header as rtp_header

    pay = rng.integers(0, 256, (rows, PAYLOAD), dtype=np.uint8)
    return rtp_header.build(
        [bytes(p) for p in pay], [seq] * rows, [seq * 160] * rows,
        (ssrc0 + np.arange(rows)).tolist(), [96] * rows,
        stream=list(range(rows)))


def _crypto_roundtrip(name: str, profile, oracle, rows: int, seed: int
                      ) -> None:
    """One table pair at full width: protect a `rows`-packet batch,
    compare >= 256 spread rows with the oracle, unprotect (auth-ok on
    every row, plaintext back), then a tampered row is rejected."""
    import numpy as np

    from libjitsi_tpu.transform.srtp import SrtpStreamTable

    rng = np.random.default_rng(seed)
    salt_len = profile.policy.salt_len
    mks, mss = _keys(rng, rows, salt_len)
    t0 = time.perf_counter()
    tx = SrtpStreamTable(capacity=rows, profile=profile)
    rx = SrtpStreamTable(capacity=rows, profile=profile)
    tx.add_streams(np.arange(rows), mks, mss)
    rx.add_streams(np.arange(rows), mks, mss)
    t_install = time.perf_counter() - t0

    plain = _audio_batch(rng, rows, FIRST_SEQ)
    t0 = time.perf_counter()
    wire = tx.protect_rtp(plain)
    t_protect_cold = time.perf_counter() - t0
    step = max(1, rows // 256)
    checked = 0
    for i in range(0, rows, step):
        want = oracle(bytes(mks[i]), bytes(mss[i]), plain.to_bytes(i),
                      FIRST_SEQ)
        got = wire.to_bytes(i)
        if got != want:
            raise AssertionError(
                f"{name}: device protect != OpenSSL oracle at row {i}: "
                f"{got.hex()[:48]} vs {want.hex()[:48]}")
        checked += 1
    t0 = time.perf_counter()
    dec, ok = rx.unprotect_rtp(wire)
    t_unprotect_cold = time.perf_counter() - t0
    ok = np.asarray(ok)
    if not ok.all():
        raise AssertionError(f"{name}: auth failed on rows "
                             f"{np.nonzero(~ok)[0][:8].tolist()}")
    ln = np.asarray(plain.length)
    if not (np.array_equal(np.asarray(dec.length), ln)
            and np.array_equal(dec.data[:, :ln.max()],
                               plain.data[:, :ln.max()])):
        raise AssertionError(f"{name}: unprotect did not return the "
                             "plaintext")
    # second batch, warm programs: one flipped ciphertext bit must
    # reject exactly that row
    plain2 = _audio_batch(rng, rows, FIRST_SEQ + 1)
    t0 = time.perf_counter()
    wire2 = tx.protect_rtp(plain2)
    t_protect_warm = time.perf_counter() - t0
    bad = rows // 2 + 1
    wire2.data[bad, 40] ^= 0x01
    t0 = time.perf_counter()
    _, ok2 = rx.unprotect_rtp(wire2)
    t_unprotect_warm = time.perf_counter() - t0
    ok2 = np.asarray(ok2)
    if ok2[bad] or not np.delete(ok2, bad).all():
        raise AssertionError(
            f"{name}: tampered row {bad} accepted={bool(ok2[bad])}, "
            f"clean rows rejected={int((~np.delete(ok2, bad)).sum())}")
    say(f"A {name}: {rows} rows installed in {t_install:.1f}s; "
        f"{checked} rows byte-identical to OpenSSL; auth ok on all "
        f"{rows}; tampered row rejected; protect cold "
        f"{t_protect_cold:.1f}s warm {t_protect_warm * 1e3:.0f}ms, "
        f"unprotect cold {t_unprotect_cold:.1f}s warm "
        f"{t_unprotect_warm * 1e3:.0f}ms (host wall, table call)")


def phase_a(rows: int, seed: int) -> None:
    from libjitsi_tpu.transform.srtp import SrtpProfile

    _crypto_roundtrip("AES_CM_128_HMAC_SHA1_80",
                      SrtpProfile.AES_CM_128_HMAC_SHA1_80,
                      protect_oracle, rows, seed)
    _crypto_roundtrip("AEAD_AES_128_GCM", SrtpProfile.AEAD_AES_128_GCM,
                      protect_oracle_gcm, rows, seed + 1)


# -- phase B: kernels ------------------------------------------------------

def _check_mixer(rng, n: int, f: int = 960) -> None:
    """A mixer frame through the product object — its constructor races
    the providers on the real shape, as a bridge's would — vs NumPy."""
    import numpy as np

    from libjitsi_tpu.conference.mixer import AudioMixer

    mixer = AudioMixer(capacity=n, frame_samples=f)
    pcm = rng.integers(-20000, 20000, (n, f)).astype(np.int16)
    for sid in range(n):
        mixer.add_participant(sid)
    mixer.push_batch(np.arange(n), pcm)
    mixed, levels = mixer.mix()
    want = np.clip(pcm.astype(np.int64).sum(axis=0)[None, :]
                   - pcm.astype(np.int64), -32768, 32767)
    if not np.array_equal(np.asarray(mixed, np.int64), want):
        raise AssertionError(f"B mixer [{n},{f}]: mix-minus != NumPy "
                             "reference")
    if np.asarray(levels).shape != (n,):
        raise AssertionError(f"B mixer [{n},{f}]: levels shape")
    say(f"B mixer [{n},{f}] matches NumPy")


def _check_pallas_twins(rng, rows: int, interpret: bool) -> None:
    """The Pallas mixer, compiled for the device, bit-compared with
    its XLA twin from one block up to a grid of row tiles at the
    table's width."""
    import numpy as np

    from libjitsi_tpu.conference.mixer import _mix_jit
    from libjitsi_tpu.kernels.pallas_ops import mix_minus_pallas

    for n, f in ((256, 960), (8, 160), (4096, 960), (rows, 960)):
        pcm = rng.integers(-20000, 20000, (n, f)).astype(np.int16)
        act = rng.random(n) < 0.8
        out_p, lvl_p = mix_minus_pallas(pcm, act, interpret=interpret)
        out_x, lvl_x = _mix_jit(pcm, act)
        if not (np.array_equal(np.asarray(out_p), np.asarray(out_x))
                and np.array_equal(np.asarray(lvl_p), np.asarray(lvl_x))):
            raise AssertionError(f"B pallas mixer [{n},{f}] != XLA twin")
    say(f"B pallas mixer bit-identical to its XLA twin "
        f"(interpret={interpret})")


def _check_registry() -> None:
    """Print what the registry chose; any provider that failed —
    anywhere in the process so far — fails the run."""
    from libjitsi_tpu.kernels import registry

    report = registry.report()
    for op, r in sorted(report.items()):
        if r["choices"] or r["errors"]:
            say(f"B registry {op}: choices={r['choices']} "
                f"timings_ms={r['timings_ms']} errors={r['errors']}")
    errors = {op: r["errors"] for op, r in report.items() if r["errors"]}
    if errors:
        raise AssertionError(f"B registry: provider errors {errors}")


def phase_b(rows: int, seed: int, on_chip: bool) -> None:
    import numpy as np

    rng = np.random.default_rng(seed)
    for n in sorted({256, rows}):     # a conference, and a full bridge
        _check_mixer(rng, n)
    # Mosaic lowers for the TPU only: a rehearsal interprets
    _check_pallas_twins(rng, rows, interpret=not on_chip)
    _check_registry()


# -- phase C: the served bridge --------------------------------------------

class _Client:
    """One loopback endpoint: a UDP socket and the scalar oracle — the
    client side touches no device code, so what it accepts is evidence
    about the bridge alone."""

    def __init__(self, ssrc: int, conf: int, rx_key, tx_key,
                 bridge_port: int):
        self.ssrc, self.conf = ssrc, conf
        self.rx_key, self.tx_key = rx_key, tx_key
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 21)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.setblocking(False)
        self.dst = ("127.0.0.1", bridge_port)
        self.seq = FIRST_SEQ
        self.sent = {}                    # seq -> plaintext RTP
        self.got = {}                     # (sender ssrc, seq) -> count

    def send(self) -> None:
        pay = hashlib.sha256(b"%d/%d" % (self.ssrc, self.seq)).digest() * 5
        hdr = (bytes([0x80, 96]) + self.seq.to_bytes(2, "big")
               + ((self.seq * 160) & 0xFFFFFFFF).to_bytes(4, "big")
               + self.ssrc.to_bytes(4, "big"))
        pkt = hdr + pay[:PAYLOAD]
        self.sent[self.seq] = pkt
        self.sock.sendto(protect_oracle(*self.rx_key, pkt, self.seq),
                         self.dst)
        self.seq += 1

    def drain(self, peers: dict) -> None:
        """Open everything waiting on the socket under the oracle and
        hold it to the sender's plaintext.  `peers`: ssrc -> _Client of
        this client's own conference."""
        while True:
            try:
                wire, _ = self.sock.recvfrom(2048)
            except BlockingIOError:
                return
            if not (wire[0] >> 6 == 2 and (wire[1] & 0x7F) == 96):
                continue                  # RTCP from the bridge
            ssrc = int.from_bytes(wire[8:12], "big")
            seq = int.from_bytes(wire[2:4], "big")
            sender = peers.get(ssrc)
            if sender is None or sender is self:
                raise AssertionError(
                    f"C client {self.ssrc:#x} (conf {self.conf}) got "
                    f"ssrc {ssrc:#x} from outside its conference")
            plain = unprotect_oracle(*self.tx_key, wire, seq)
            if plain is None:
                raise AssertionError(
                    f"C client {self.ssrc:#x}: tag of {ssrc:#x}/{seq} "
                    "does not verify under the oracle")
            sent = sender.sent[seq]
            off = _payload_off(plain)
            # the bridge stamps abs-send-time (a header extension) on
            # egress: fixed fields past the X bit and the whole payload
            # must be the sender's
            if (plain[1:12] != sent[1:12] or plain[off:] != sent[12:]
                    or (plain[0] & 0xEF) != sent[0]):
                raise AssertionError(
                    f"C client {self.ssrc:#x}: {ssrc:#x}/{seq} opened "
                    "to bytes the sender never sent")
            self.got[(ssrc, seq)] = self.got.get((ssrc, seq), 0) + 1

    def close(self) -> None:
        self.sock.close()


def phase_c(rows: int, seed: int, on_chip: bool, latch_ticks: int = 6,
            warm_ticks: int = 6, media_ticks: int = 30) -> None:
    import numpy as np

    import libjitsi_tpu
    from libjitsi_tpu.service.lifecycle import StreamLifecycleManager
    from libjitsi_tpu.service.sfu_bridge import SfuBridge
    from libjitsi_tpu.service.supervisor import (BridgeSupervisor,
                                                 SupervisorConfig)
    from libjitsi_tpu.utils.compile_cache import compile_stats
    from libjitsi_tpu.utils.health import HEALTHY

    if rows % CONF_SIZE:
        raise SystemExit(f"--rows must be a multiple of {CONF_SIZE}")
    n_conf = rows // CONF_SIZE
    active = min(ACTIVE_CONFS, n_conf)
    rng = np.random.default_rng(seed)
    stats = compile_stats()

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    cfg = libjitsi_tpu.configuration_service()
    t0 = time.perf_counter()
    bridge = SfuBridge(cfg, port=0, capacity=rows, recv_window_ms=0)
    reg = bridge.loop.metrics
    # the soak's watchdog deadline, NOT the product default of
    # `SupervisorConfig()` (a 20 ms tick): "healthy" below means "no
    # tick over 1 s", and the line that reports it says so.  XLA:CPU
    # runs a tick's crypto far over any real-time budget, so a
    # rehearsal must not trip the overload ladder (which sheds
    # streams, by design)
    deadline_ms = 1000.0 if on_chip else 60_000.0
    sup = BridgeSupervisor(
        bridge, SupervisorConfig(deadline_ms=deadline_ms), metrics=reg)
    lc = StreamLifecycleManager(bridge, supervisor=sup, metrics=reg)
    lc.enable_placement(1)         # conference-scoped forwarding
    clients = []
    try:
        # ---- admission: every endpoint through request_join, a wave
        # per tick (the install stage drains `install_batch` per
        # between-ticks window), ticked until all are live
        keys = rng.integers(0, 256, (rows, 2, 30), dtype=np.uint8)
        specs = []
        for i in range(rows):
            rx = (bytes(keys[i, 0, :16]), bytes(keys[i, 0, 16:]))
            tx = (bytes(keys[i, 1, :16]), bytes(keys[i, 1, 16:]))
            specs.append((0x10000 + i, i // CONF_SIZE, rx, tx))
        now, dt = 100.0, 0.02
        states = set()
        queued = 0

        def tick():
            nonlocal now
            sup.tick(now=now)
            now += dt
            states.add(sup.watchdog.state)

        while lc.admits < rows:
            for ssrc, conf, rx, tx in specs[
                    queued:queued + lc.cfg.install_batch]:
                accepted, why = lc.request_join(ssrc, rx, tx,
                                                conference=conf)
                if not accepted:
                    raise AssertionError(
                        f"C admission refused {ssrc:#x}: {why}")
                queued += 1
            tick()
            if sup.ticks > 4 * rows // lc.cfg.install_batch + 64:
                raise AssertionError(
                    f"C only {lc.admits}/{rows} live after "
                    f"{sup.ticks} ticks")
        t_admit = time.perf_counter() - t0
        live = len(bridge._ssrc_of) - len(bridge._staged)
        if live != rows:
            raise AssertionError(f"C {live} live rows, wanted {rows}")
        say(f"C {rows} endpoints live in {n_conf} conferences after "
            f"{sup.ticks} ticks, {t_admit:.1f}s wall (warm ladder "
            f"included); compile events so far {stats.compile_events}, "
            f"{stats.compile_seconds:.0f}s")

        # ---- loopback clients in `active` conferences spread over the
        # table (first, last, and evenly between)
        confs = sorted({int(c) for c in
                        np.linspace(0, n_conf - 1, active)})
        by_conf = {}
        for c in confs:
            members = {}
            for ssrc, conf, rx, tx in specs[c * CONF_SIZE:
                                            (c + 1) * CONF_SIZE]:
                cl = _Client(ssrc, conf, rx, tx, bridge.port)
                members[ssrc] = cl
                clients.append(cl)
            by_conf[c] = members

        def media_tick():
            for cl in clients:
                cl.send()
            tick()
            for cl in clients:
                cl.drain(by_conf[cl.conf])

        # address latch: fan-out toward a receiver starts once its
        # source address is learnt from its first inbound packet
        for _ in range(latch_ticks):
            media_tick()
        floor = clients[0].seq
        for _ in range(warm_ticks):
            media_tick()
        compiles0 = stats.compile_events
        recompiles0 = lc.datapath_recompiles
        tick_s = []
        for _ in range(media_ticks):
            media_tick()
            tick_s.append(sup.last_tick_s)
        for _ in range(2):                # flush anything in flight
            tick()
            bridge.flush_egress()         # ... the egress worker's too
            for cl in clients:
                cl.drain(by_conf[cl.conf])

        # ---- checks
        if states != {HEALTHY}:
            raise AssertionError(f"C supervisor health left OK: {states}")
        new_compiles = stats.compile_events - compiles0
        if new_compiles or lc.datapath_recompiles != recompiles0:
            raise AssertionError(
                f"C {new_compiles} compile events after the warm-up "
                f"ticks ({lc.datapath_recompiles - recompiles0} inside "
                "tick windows)")
        h = sup.health()
        if (h["shed"] or h["quarantined"] or h["level"]
                or sup.quarantine_total or lc.admit_rejected):
            raise AssertionError(
                f"C shed={h['shed']} quarantined={h['quarantined']} "
                f"level={h['level']} refused={lc.admit_rejected}")
        last = clients[0].seq
        missing = dupes = 0
        for cl in clients:
            for peer in by_conf[cl.conf].values():
                if peer is cl:
                    continue
                for seq in range(floor, last):
                    n = cl.got.get((peer.ssrc, seq), 0)
                    missing += n == 0
                    dupes += n > 1
        want = len(clients) * (CONF_SIZE - 1) * (last - floor)
        if missing or dupes:
            raise AssertionError(
                f"C delivery: {missing} missing, {dupes} duplicated of "
                f"{want} expected after address latch")
        ts = np.asarray(tick_s) * 1e3
        say(f"C {len(clients)} clients in {len(confs)} conferences: "
            f"{want} forwarded packets each opened to the sender's "
            f"plaintext under the oracle, none missing, none foreign; "
            f"health {HEALTHY} on all {sup.ticks} ticks under a "
            f"{deadline_ms:.0f} ms watchdog deadline (product default "
            f"{SupervisorConfig().deadline_ms:.0f} ms: "
            f"{int((ts > SupervisorConfig().deadline_ms).sum())} of "
            f"{media_ticks} media ticks were over it); 0 compiles in "
            f"{media_ticks} media ticks; tick wall ms median "
            f"{np.median(ts):.2f} max {ts.max():.2f} "
            f"({len(clients)} pkts in, {len(clients) * (CONF_SIZE - 1)} "
            f"out per tick); last tick's stage ledger ms "
            f"{ {k: round(v * 1e3, 2) for k, v in sup.last_ledger.items()} }")
    finally:
        for cl in clients:
            cl.close()
        bridge.close()


# -- --chips 4: the mesh path and its single-device twin -------------------

def phase_mesh(rows: int, seed: int, n_dev: int = 4) -> None:
    import jax
    import numpy as np

    import libjitsi_tpu
    from libjitsi_tpu.mesh import ShardedSrtpTable, make_media_mesh
    from libjitsi_tpu.mesh import parity
    from libjitsi_tpu.service.sfu_bridge import SfuBridge

    devs = jax.devices()[:n_dev]
    mesh = make_media_mesh(devs)
    say(f"mesh: {mesh}")

    t0 = time.perf_counter()
    parity.assert_table_parity(mesh, capacity=rows,
                               batch_size=min(rows, 4096))
    say(f"mesh ShardedSrtpTable protect/unprotect == single-device "
        f"table at {rows} rows ({time.perf_counter() - t0:.1f}s)")
    t0 = time.perf_counter()
    parity.assert_affinity_parity(mesh, n_dev,
                                  b_shard=min(rows // n_dev, 1024),
                                  part=CONF_SIZE, seed=seed)
    say(f"mesh affinity_tick == affinity_step_ref shard by shard "
        f"({time.perf_counter() - t0:.1f}s)")

    # every table's device state must live on n_dev distinct devices
    rng = np.random.default_rng(seed)
    mks, mss = _keys(rng, rows, 14)
    tab = ShardedSrtpTable(rows, mesh)
    tab.add_streams(np.arange(rows), mks, mss)
    for group in ("rtp", "rtcp"):
        _assert_spread(f"ShardedSrtpTable[{group}]",
                       tab._sharded_device(group), n_dev)

    # mesh-mode bridge for a few ticks at full rows, egress byte-equal
    # to the single-device bridge
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    cfg = libjitsi_tpu.configuration_service()
    t0 = time.perf_counter()
    parity.assert_sfu_parity(cfg, mesh, capacity=rows)
    say(f"mesh SfuBridge(mesh=...) egress == single-device bridge at "
        f"capacity {rows} ({time.perf_counter() - t0:.1f}s)")
    bridge = SfuBridge(cfg, port=0, capacity=rows, recv_window_ms=0,
                       mesh=mesh)
    try:
        bridge.stage_endpoints(
            [(0x20000 + i, (bytes(mks[i]), bytes(mss[i])),
              (bytes(mks[-1 - i]), bytes(mss[-1 - i])), None)
             for i in range(rows)],
            sids=list(range(rows)),
            conferences=[i // CONF_SIZE for i in range(rows)])
        bridge.commit_endpoints(list(range(rows)))
        for k in range(3):
            bridge.tick(now=200.0 + 0.02 * k)
        held = {}
        for name in ("rx_table", "tx_table", "translator"):
            for dev_id, nbytes in _assert_spread(
                    f"bridge.{name}",
                    getattr(bridge, name)._sharded_device(),
                    n_dev).items():
                held[dev_id] = held.get(dev_id, 0) + nbytes
        # ... and every device must report at least its share of that
        # state as memory in use (the CPU backend of a rehearsal keeps
        # no such statistics)
        stats = {d.id: d.memory_stats() for d in devs}
        used = {i: int(st["bytes_in_use"]) for i, st in stats.items()
                if st is not None}
        say(f"mesh bytes_in_use per device: {used}; of it the bridge's "
            f"table shards: {held}")
        if len(used) != n_dev and devs[0].platform == "tpu":
            raise AssertionError("mesh: a device reports no memory "
                                 f"statistics: {stats}")
        short = {i: (used[i], held[i]) for i in used if used[i] < held[i]}
        if short:
            raise AssertionError("mesh: a device reports less memory in "
                                 f"use than the shards it holds: {short}")
    finally:
        bridge.close()


def _assert_spread(name: str, arrays, n_dev: int) -> dict:
    """Every leaf is row-sharded over `n_dev` distinct devices: read
    from the buffers (`addressable_shards`: which device holds which
    rows), not only from the sharding they were asked to take.
    Returns device id -> bytes of these arrays it holds."""
    import jax

    held = {}
    leaves = jax.tree_util.tree_leaves(arrays)
    if not leaves:
        raise AssertionError(f"mesh: {name} has no device arrays")
    for a in leaves:
        if len(a.sharding.device_set) != n_dev:
            raise AssertionError(
                f"mesh: {name} array {a.shape} is sharded over "
                f"{len(a.sharding.device_set)} device(s), wanted {n_dev}")
        shards = a.addressable_shards
        if len({sh.device.id for sh in shards}) != n_dev:
            raise AssertionError(
                f"mesh: {name} array {a.shape} has buffers on devices "
                f"{sorted(sh.device.id for sh in shards)}, wanted "
                f"{n_dev} distinct")
        for sh in shards:
            if sh.data.shape[0] * n_dev != a.shape[0]:
                raise AssertionError(
                    f"mesh: {name} device {sh.device.id} holds "
                    f"{sh.data.shape[0]} of {a.shape[0]} rows, wanted "
                    f"a {n_dev}th")
            held[sh.device.id] = held.get(sh.device.id, 0) + sh.data.nbytes
    say(f"mesh {name}: {len(leaves)} arrays, each device holds a "
        f"{n_dev}th of the rows ({n_dev} distinct devices)")
    return held


# -- main ------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the multi-chip path and its "
                         "single-device twin")
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--rows", type=int, default=None,
                    help="REHEARSAL ONLY: smaller table, lifts the "
                         "device gate, last line says ok=false")
    args = ap.parse_args(argv)
    rehearsal = args.rows is not None
    rows = args.rows if rehearsal else FULL_ROWS
    t_start = time.perf_counter()

    build_native()
    from libjitsi_tpu.utils.compile_cache import (compile_stats,
                                                  enable_compile_cache)

    cache_dir = enable_compile_cache()
    stats = compile_stats()
    device = device_gate(rehearsal, args.chips)
    on_chip = device["platform"] == "tpu"

    from libjitsi_tpu.kernels import aes

    say(f"compile cache: {cache_dir}; AES core: {aes.get_core()}; "
        f"rows: {rows}{' (REHEARSAL)' if rehearsal else ''}")

    phases = ([("mesh", lambda: phase_mesh(rows, args.seed))]
              if args.chips == 4 else
              [("A", lambda: phase_a(rows, args.seed)),
               ("B", lambda: phase_b(rows, args.seed, on_chip)),
               ("C", lambda: phase_c(rows, args.seed, on_chip))])
    for name, fn in phases:
        t0 = time.perf_counter()
        c0 = stats.compile_seconds
        fn()
        say(f"phase {name}: {time.perf_counter() - t0:.1f}s wall, "
            f"{stats.compile_seconds - c0:.1f}s of it in "
            f"trace/lower/compile; persistent cache hits {stats.hits} "
            f"misses {stats.misses}; peak_bytes_in_use {_peak_bytes()}")
    say(f"total {time.perf_counter() - t_start:.1f}s wall; compile "
        f"events {stats.compile_events} ({stats.compile_seconds:.1f}s); "
        f"persistent cache hits {stats.hits} misses {stats.misses}")
    print(json.dumps({"ok": not rehearsal, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
