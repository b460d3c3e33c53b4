"""Runnable demo: a secure conference bridge on one TPU chip.

Three participants connect over loopback UDP, each with its own SDES-
keyed SRTP session. Every 20 ms tick the bridge:

  1. drains the socket into a PacketBatch (recvmmsg path),
  2. runs the batched SRTP reverse transform on device,
  3. decodes G.711 and deposits PCM into the conference mixer,
  4. mixes everyone (mix-minus + RFC 6465 levels, one device launch),
  5. re-encodes and SRTP-protects each participant's personalized mix,
  6. sends it back over UDP.

Run:  PYTHONPATH=. python examples/conference_bridge.py
(runs on whatever platform JAX gives; the first compiles take a while,
then the demo runs 50 ticks and prints per-participant stats.)
"""

import time

import numpy as np

import libjitsi_tpu
from libjitsi_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()      # re-runs start warm

from libjitsi_tpu.conference import AudioMixer
from libjitsi_tpu.core.packet import PacketBatch
from libjitsi_tpu.device import ToneSource
from libjitsi_tpu.io import UdpEngine
from libjitsi_tpu.rtp import header as rtp_header
from libjitsi_tpu.service.pump import g711_codec
from libjitsi_tpu.transform.srtp import SrtpStreamTable

N, FRAME = 3, 160              # participants; 20 ms @ 8 kHz (G.711)
TICKS = 50


def main():
    libjitsi_tpu.init()
    codec = g711_codec(ulaw=True)

    # --- bridge side: one rx/tx SRTP row + mixer row per participant
    rx = SrtpStreamTable(capacity=N)
    tx = SrtpStreamTable(capacity=N)
    mixer = AudioMixer(capacity=N, frame_samples=FRAME)
    bridge = UdpEngine(port=0, max_batch=64)
    keys = [(bytes([i + 1] * 16), bytes([i + 101] * 14)) for i in range(N)]
    for sid, (mk, ms) in enumerate(keys):
        rx.add_stream(sid, mk, ms)
        tx.add_stream(sid, mk, ms)
        mixer.add_participant(sid)
    ssrc_to_sid = {0xD000 + i: i for i in range(N)}

    # --- participant side: a tone source + its own SRTP view
    class Peer:
        def __init__(self, sid):
            self.sid = sid
            self.sock = UdpEngine(port=0, max_batch=16)
            self.tone = ToneSource(300.0 + 200 * sid, sample_rate=8000)
            self.tab = SrtpStreamTable(capacity=1)
            self.tab.add_stream(0, *keys[sid])
            self.seq = 100
            self.heard = 0

        def send_frame(self):
            payload = codec.encode(self.tone.read(FRAME))
            batch = rtp_header.build(
                [payload], [self.seq], [self.seq * FRAME],
                [0xD000 + self.sid], [0], stream=[0])
            self.seq += 1
            self.sock.send_batch(self.tab.protect_rtp(batch),
                                 "127.0.0.1", bridge.port)

        def drain(self):
            batch, _, _ = self.sock.recv_batch(timeout_ms=1)
            if batch.batch_size:
                # the socket doesn't know stream rows; this peer has one
                sub = PacketBatch(batch.data, np.asarray(batch.length),
                                  np.zeros(batch.batch_size, np.int32))
                dec, ok = self.tab.unprotect_rtp(sub)
                self.heard += int(ok.sum())

    peers = [Peer(i) for i in range(N)]
    addr = {}                   # sid -> (ip, port) learned from traffic

    t0 = time.time()
    for tick in range(TICKS):
        for p in peers:
            p.send_frame()
        # bridge tick: drain -> unprotect -> decode -> mix
        batch, sip, sport = bridge.recv_batch(timeout_ms=5)
        if batch.batch_size:
            hdr = rtp_header.parse(batch)
            sids = np.array([ssrc_to_sid.get(int(s), -1)
                             for s in hdr.ssrc])
            keep = sids >= 0
            sub = PacketBatch(batch.data[keep],
                              np.asarray(batch.length)[keep], sids[keep])
            dec, ok = rx.unprotect_rtp(sub)
            hdr2 = rtp_header.parse(dec)
            for j in np.nonzero(ok)[0]:
                sid = int(dec.stream[j])
                addr[sid] = (int(sip[keep][j]), int(sport[keep][j]))
                payload = dec.to_bytes(int(j))[int(hdr2.payload_off[j]):]
                mixer.push(sid, codec.decode(payload))
        out, levels = mixer.mix()
        # personalized mixes: ONE batched protect for all participants
        # (per-row key gather), then per-destination send
        if addr:
            sids = sorted(addr)
            b = rtp_header.build(
                [codec.encode(out[s]) for s in sids],
                [tick] * len(sids), [tick * FRAME] * len(sids),
                [0xB00] * len(sids), [0] * len(sids), stream=sids)
            wire = tx.protect_rtp(b)
            for j, s in enumerate(sids):
                ip, port = addr[s]
                one = PacketBatch(wire.data[j:j + 1],
                                  np.asarray(wire.length)[j:j + 1],
                                  wire.stream[j:j + 1])
                bridge.send_batch(one, ip, port)
        for p in peers:
            p.drain()
        time.sleep(0.002)

    dt = time.time() - t0
    print(f"{TICKS} ticks in {dt:.2f}s "
          f"({TICKS * N} frames mixed, levels now {levels.tolist()})")
    for p in peers:
        print(f"  participant {p.sid}: sent {TICKS}, "
              f"heard {p.heard} personalized mix frames")
    assert all(p.heard > TICKS // 2 for p in peers), "media did not flow"
    print("OK: every participant heard their mix-minus over SRTP/UDP")


if __name__ == "__main__":
    main()
