"""Runnable demo: a secure video SFU with simulcast on one chip.

One sender publishes a 3-layer VP8 simulcast track (real libvpx
encoders at 160x96 / 320x192 / 640x384); two receivers join with their
own SRTP leg keys.  Each tick the bridge:

  1. drains loopback UDP, demuxes the layer SSRCs to their rows,
  2. runs one batched SRTP unprotect for every layer's packets,
  3. projects ONE layer per receiver through its SimulcastForwarder
     (SSRC/seq/ts/picture-id rewritten into a single coherent stream),
  4. re-protects all receivers' projections in one launch and sends.

Receiver B advertises a small REMB, receiver A a large one — so A is
upswitched to the top layer on its next keyframe while B stays on the
base layer.  (The NACK->RTX path is exercised by the slow-tier e2e in
tests/test_sfu_bridge.py.)

Run:  PYTHONPATH=. python examples/sfu_video.py
(runs on whatever platform JAX gives; the first compiles take a while,
then the demo runs ~30 ticks and prints the per-receiver
layer/forwarding stats.)
"""

import numpy as np

import libjitsi_tpu
from libjitsi_tpu.codecs import vp8
from libjitsi_tpu.codecs.vpx import VpxEncoder, vpx_available
from libjitsi_tpu.core.packet import PacketBatch
from libjitsi_tpu.io import UdpEngine
from libjitsi_tpu.rtp import header as rtp_header
from libjitsi_tpu.rtp import rtcp
from libjitsi_tpu.service.sfu_bridge import SfuBridge
from libjitsi_tpu.transform.srtp import SrtpStreamTable
from libjitsi_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()

LAYER_SSRCS = [0xB00 + k for k in range(3)]
DIMS = [(160, 96), (320, 192), (640, 384)]


def main() -> None:
    if not vpx_available():
        raise SystemExit("libvpx not present; this demo needs it")
    libjitsi_tpu.init()
    sfu = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                    capacity=32, recv_window_ms=0)
    print(f"SFU listening on 127.0.0.1:{sfu.port}")

    def keys(seed):
        return (bytes([seed]) * 16, bytes([seed + 1]) * 14)

    # sender + two receivers, SDES-style static leg keys
    send_rx, send_tx = keys(0x10), keys(0x20)
    sid_s = sfu.add_endpoint(0xA0, send_rx, send_tx)
    recvs = {}
    for name, ssrc, seed in (("A", 0xA1, 0x30), ("B", 0xA2, 0x40)):
        rx, tx = keys(seed), keys(seed + 0x10)
        sid = sfu.add_endpoint(ssrc, rx, tx)
        eng = UdpEngine(port=0, max_batch=64)
        # latch the receiver's address with one (any) packet
        hello = rtp_header.build([b"hello"], [1], [0], [ssrc], [96],
                                 stream=[0])
        prot = SrtpStreamTable(capacity=1)
        prot.add_stream(0, *rx)
        eng.send_batch(prot.protect_rtp(hello), "127.0.0.1", sfu.port)
        open_tab = SrtpStreamTable(capacity=1)
        open_tab.add_stream(0, *tx)          # projected video stream
        recvs[name] = dict(sid=sid, ssrc=ssrc, eng=eng, prot=prot,
                           open=open_tab, got=0, frames=0)

    track = sfu.add_video_track(sid_s, LAYER_SSRCS,
                                layer_bps=[100e3, 500e3, 2e6])

    # sender: one SRTP row + encoder per layer
    tx_tab = SrtpStreamTable(capacity=4)
    for k in range(3):
        tx_tab.add_stream(k, *send_rx)
    encs = [VpxEncoder(w, h) for w, h in DIMS]
    send_eng = UdpEngine(port=0, max_batch=64)
    seqs, pids = [100, 200, 300], [1, 2, 3]

    def planes(k, t):
        w, h = DIMS[k]
        yy, xx = np.mgrid[0:h, 0:w].astype(np.float64)
        y = (128 + 60 * np.sin(xx / 17 + t * 0.7)
             + 40 * np.cos(yy / 11 + t)).clip(0, 255).astype(np.uint8)
        c = np.full(((h + 1) // 2, (w + 1) // 2), 128, np.uint8)
        return y, c, c

    def send_frame(t):
        for k in range(3):
            for data, _key in encs[k].encode(*planes(k, t)):
                pls = vp8.packetize(data, picture_id=pids[k],
                                    max_payload=1100)
                pids[k] = (pids[k] + 1) & 0x7FFF
                n = len(pls)
                b = rtp_header.build(
                    pls, [(seqs[k] + i) & 0xFFFF for i in range(n)],
                    [t * 3000] * n, [LAYER_SSRCS[k]] * n, [96] * n,
                    marker=[0] * (n - 1) + [1], stream=[k] * n)
                seqs[k] = (seqs[k] + n) & 0xFFFF
                send_eng.send_batch(tx_tab.protect_rtp(b), "127.0.0.1",
                                    sfu.port)

    def send_remb(r, bps):
        blob = rtcp.build_compound([rtcp.build_remb(
            rtcp.Remb(r["ssrc"], int(bps), [0xA0]))])
        b = PacketBatch.from_payloads([blob], stream=[0])
        r["eng"].send_batch(r["prot"].protect_rtcp(b), "127.0.0.1",
                            sfu.port)

    fbs = {"A": 3_000_000, "B": 150_000}     # A rich, B starved
    fa = {n: vp8.FrameAssembler() for n in recvs}
    fb_tab = SrtpStreamTable(capacity=1)     # bridge SRTCP toward the
    fb_tab.add_stream(0, *send_tx)           # sender (PLI drain)
    now = 10.0
    for t in range(30):
        send_frame(t)
        for name, r in recvs.items():
            send_remb(r, fbs[name])
        for _ in range(10):
            sfu.tick(now=now)
        sfu.emit_feedback(now=now)
        # the sender answers PLIs with a keyframe (fresh encoder)
        back, _, _ = send_eng.recv_batch(timeout_ms=2)
        if back.batch_size:
            back.stream[:] = 0
            dec, ok = fb_tab.unprotect_rtcp(back)
            for i in np.nonzero(np.asarray(ok))[0]:
                try:
                    pkts = rtcp.parse_compound(dec.to_bytes(int(i)))
                except ValueError:
                    continue
                for p in pkts:
                    if isinstance(p, rtcp.Pli) and \
                            p.media_ssrc in LAYER_SSRCS:
                        k = LAYER_SSRCS.index(p.media_ssrc)
                        encs[k].close()
                        encs[k] = VpxEncoder(*DIMS[k])
        for name, r in recvs.items():
            back, _, _ = r["eng"].recv_batch(timeout_ms=2)
            if not back.batch_size:
                continue
            hdr0 = rtp_header.parse(back)
            keep = np.nonzero(hdr0.ssrc == 0xA0)[0]
            if len(keep) == 0:
                continue
            sub = PacketBatch(back.data[keep],
                              np.asarray(back.length)[keep],
                              np.zeros(len(keep), np.int64))
            dec, ok = r["open"].unprotect_rtp(sub)
            rows = np.nonzero(ok)[0]
            r["got"] += len(rows)
            if len(rows):
                fa[name].push_batch(PacketBatch(
                    dec.data[rows], np.asarray(dec.length)[rows],
                    dec.stream[rows]))
        now += 0.1                            # see PLI limiter note

    for name, r in recvs.items():
        fwd = track.fwd[r["sid"]]
        frames = fa[name].pop_frames()
        print(f"receiver {name}: layer={fwd.current_layer} "
              f"switches={fwd.switches} packets={r['got']} "
              f"frames={len(frames)} (REMB {fbs[name]/1e3:.0f} kbps)")
    a, b = track.fwd[recvs["A"]["sid"]], track.fwd[recvs["B"]["sid"]]
    assert a.current_layer > b.current_layer, "A should outrank B"
    print("demo ok: REMB-driven per-receiver simulcast projection")
    sfu.close()
    send_eng.close()
    for r in recvs.values():
        r["eng"].close()


if __name__ == "__main__":
    main()
