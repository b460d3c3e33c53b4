"""Mesh-vs-single-chip parity rehearsals for the PRODUCT objects.

One harness, two consumers: the driver's multi-chip dry run
(`__graft_entry__.dryrun_multichip`) and the pytest suite
(tests/test_mesh_table.py) both assert that the sharded
`ShardedSrtpTable` and the mesh-mode `ConferenceBridge` are
bit-identical to their single-chip twins — keeping the harness here
means the dryrun and CI can never drift apart on what "parity" means.
"""

from __future__ import annotations

import numpy as np

from libjitsi_tpu.rtp import header as rtp_header
from libjitsi_tpu.transform.srtp import SrtpStreamTable


def assert_table_parity(mesh, capacity: int, batch_size: int,
                        rounds: int = 2, profile=None) -> None:
    """Sharded table protect/unprotect must match the plain table byte
    for byte, including the host replay planes (any supported profile:
    CM and GCM both ride this)."""
    from libjitsi_tpu.mesh import ShardedSrtpTable
    from libjitsi_tpu.transform.srtp import SrtpProfile

    if profile is None:
        profile = SrtpProfile.AES_CM_128_HMAC_SHA1_80
    salt_len = profile.policy.salt_len
    rng = np.random.default_rng(23)
    mks = rng.integers(0, 256, (capacity, 16), dtype=np.uint8)
    mss = rng.integers(0, 256, (capacity, salt_len), dtype=np.uint8)

    def build_pair():
        sh = ShardedSrtpTable(capacity, mesh, profile)
        sh.add_streams(np.arange(capacity), mks, mss)
        pl = SrtpStreamTable(capacity, profile)
        pl.add_streams(np.arange(capacity), mks, mss)
        return sh, pl

    def batch(seq0):
        # own generator per call: both tables must see IDENTICAL batches
        r = np.random.default_rng(seq0)
        streams = r.integers(0, capacity, batch_size)
        pls = [bytes([seq0 & 0xFF]) * 40 for _ in range(batch_size)]
        return rtp_header.build(
            pls, [(seq0 + i) & 0xFFFF for i in range(batch_size)],
            [0] * batch_size, (0x7000 + streams).tolist(),
            [96] * batch_size, stream=streams.tolist())

    sh_tx, pl_tx = build_pair()
    sh_rx, pl_rx = build_pair()
    for k in range(rounds):
        # a round's seqs start past the previous round's: a stream hit
        # late in one round and early in the next must still see its
        # seq advance, or the 64-packet replay window rejects it at
        # any batch wider than the window
        seq0 = 100 + k * (batch_size + 100)
        w_sh = sh_tx.protect_rtp(batch(seq0))
        w_pl = pl_tx.protect_rtp(batch(seq0))
        for i in range(w_sh.batch_size):
            if w_sh.to_bytes(i) != w_pl.to_bytes(i):
                raise AssertionError(
                    f"sharded TABLE protect != single-chip at row {i}")
        if not np.array_equal(sh_tx.tx_ext, pl_tx.tx_ext):
            raise AssertionError("sharded TABLE tx state diverged")
        d_sh, ok_sh = sh_rx.unprotect_rtp(w_sh)
        d_pl, ok_pl = pl_rx.unprotect_rtp(w_pl)
        if not (bool(np.all(ok_sh)) and bool(np.all(ok_pl))):
            raise AssertionError("sharded TABLE unprotect auth failed")
        for i in range(d_sh.batch_size):
            if d_sh.to_bytes(i) != d_pl.to_bytes(i):
                raise AssertionError(
                    f"sharded TABLE unprotect != single-chip at row {i}")
        if not (np.array_equal(sh_rx.rx_max, pl_rx.rx_max)
                and np.array_equal(sh_rx.rx_mask, pl_rx.rx_mask)):
            raise AssertionError("sharded TABLE replay state diverged")


def run_bridge_once(cfg, mesh, capacity: int, rounds: int = 2,
                    pipelined: bool = False) -> dict:
    """One tiny G.711 conference through a ConferenceBridge (mesh-mode
    when `mesh` is not None; pipelined dispatch when `pipelined`) over
    real loopback UDP with pinned TX counters; returns
    {(client, seq): wire_bytes} for comparison."""
    from libjitsi_tpu.io import UdpEngine
    from libjitsi_tpu.kernels import g711
    from libjitsi_tpu.service.bridge import ConferenceBridge

    bridge = ConferenceBridge(cfg, port=0, capacity=capacity,
                              recv_window_ms=0, mesh=mesh,
                              pipelined=pipelined)
    clis = []
    for ssrc in (10, 20):
        prot = SrtpStreamTable(capacity=1)
        rx_key = (bytes([ssrc]) * 16, bytes([ssrc + 1]) * 14)
        prot.add_stream(0, *rx_key)
        eng = UdpEngine(port=0, max_batch=16)
        bridge.add_participant(
            ssrc, rx_key, (bytes([ssrc + 2]) * 16,
                           bytes([ssrc + 3]) * 14))
        clis.append((ssrc, prot, eng))
    # pin the randomized TX counters so two runs' egress is comparable
    bridge._tx_seq[:] = 300
    bridge._tx_ts[:] = 7000
    got = {}
    now = 50.0
    try:
        for k in range(rounds):
            for ssrc, prot, eng in clis:
                pcm = ((1000 + 500 * ssrc)
                       * np.ones(160)).astype(np.int16)
                pay = np.asarray(g711.ulaw_encode(pcm[None]))[0]
                b = rtp_header.build([pay.tobytes()], [50 + k],
                                     [k * 160], [ssrc], [0],
                                     stream=[0])
                eng.send_batch(prot.protect_rtp(b), "127.0.0.1",
                               bridge.port)
            for _ in range(10):
                if bridge.tick(now=now)["rx"]:
                    break
            bridge.tick(now=now + 0.001)
            for j, (_ssrc, _prot, eng) in enumerate(clis):
                back, _, _ = eng.recv_batch(timeout_ms=2)
                if back.batch_size:
                    hdr = rtp_header.parse(back)
                    for i in range(back.batch_size):
                        got[(j, int(hdr.seq[i]))] = back.to_bytes(i)
            now += 0.020
        # pipelined mode holds the final frame's protect in flight; ship
        # it so sync and pipelined runs are compared on the same frames
        # (flush_sends is a no-op for the sync loop)
        bridge.loop.flush_sends()
        for j, (_ssrc, _prot, eng) in enumerate(clis):
            back, _, _ = eng.recv_batch(timeout_ms=2)
            if back.batch_size:
                hdr = rtp_header.parse(back)
                for i in range(back.batch_size):
                    got[(j, int(hdr.seq[i]))] = back.to_bytes(i)
    finally:
        for _ssrc, _prot, eng in clis:
            eng.close()
        bridge.close()
    return got


def assert_bridge_parity(cfg, mesh, capacity: int,
                        pipelined: bool = False) -> None:
    """Assembled mesh-mode ConferenceBridge egress must be byte-
    identical to the single-chip SYNC bridge for the same conference
    (with `pipelined`, the overlapped-dispatch mesh bridge rides the
    same contract)."""
    plain = run_bridge_once(cfg, None, capacity)
    meshed = run_bridge_once(cfg, mesh, capacity, pipelined=pipelined)
    if len(plain) < 2:
        raise AssertionError("bridge parity run produced no egress")
    if plain != meshed:
        raise AssertionError(
            "assembled mesh ConferenceBridge egress != single-chip")


def run_sfu_once(cfg, mesh, capacity: int, rounds: int = 3) -> dict:
    """One tiny 3-endpoint audio SFU conference over loopback UDP
    (mesh-mode when `mesh` is not None), deterministic tick clock;
    returns {(endpoint, sender_ssrc, seq): wire_bytes}."""
    from libjitsi_tpu.io import UdpEngine
    from libjitsi_tpu.service.sfu_bridge import SfuBridge

    sfu = SfuBridge(cfg, port=0, capacity=capacity, recv_window_ms=0,
                    mesh=mesh)
    eps = []
    for k in range(3):
        ssrc = 0x600 + 9 * k
        rx_key = (bytes([ssrc & 0xFF]) * 16,
                  bytes([(ssrc + 1) & 0xFF]) * 14)
        tx_key = (bytes([(ssrc + 2) & 0xFF]) * 16,
                  bytes([(ssrc + 3) & 0xFF]) * 14)
        prot = SrtpStreamTable(capacity=1)
        prot.add_stream(0, *rx_key)
        eng = UdpEngine(port=0, max_batch=64)
        sfu.add_endpoint(ssrc, rx_key, tx_key)
        eps.append((ssrc, prot, eng))
    got = {}
    now = 60.0
    try:
        for r in range(rounds):
            for ssrc, prot, eng in eps:
                b = rtp_header.build(
                    [b"sfu-%08x-%d" % (ssrc, r)], [400 + r], [r * 960],
                    [ssrc], [96], stream=[0])
                eng.send_batch(prot.protect_rtp(b), "127.0.0.1",
                               sfu.port)
            for _ in range(12):
                sfu.tick(now=now)
            sfu.flush_egress()      # the fan-out leaves on a worker
            for j, (_ssrc, _prot, eng) in enumerate(eps):
                back, _, _ = eng.recv_batch(timeout_ms=2)
                if back.batch_size:
                    hdr = rtp_header.parse(back)
                    for i in range(back.batch_size):
                        got[(j, int(hdr.ssrc[i]), int(hdr.seq[i]))] = \
                            back.to_bytes(i)
            now += 0.020
    finally:
        for _ssrc, _prot, eng in eps:
            eng.close()
        sfu.close()
    return got


def assert_sfu_parity(cfg, mesh, capacity: int) -> None:
    """Assembled mesh-mode SfuBridge fan-out must be byte-identical to
    the single-chip bridge for the same conference (both dispatch a
    tick's fan-out and collect it in the next: the bridge's one
    shape)."""
    plain = run_sfu_once(cfg, None, capacity)
    meshed = run_sfu_once(cfg, mesh, capacity)
    if len(plain) < 6:
        raise AssertionError("sfu parity run produced too little egress")
    if plain != meshed:
        raise AssertionError(
            "assembled mesh SfuBridge egress != single-chip")


# ------------------------------------------------- conference affinity

def build_affinity_workload(batch: int, n_conf: int, rng,
                            part: int = 4, width: int = 128,
                            frame: int = 160, tag_len: int = 10):
    """Argument tuple for `affinity_tick`/`affinity_step_ref`: rx rows
    are authentic ciphertext (protected off-line so unprotect's auth
    passes), `conf` numbers conferences within each shard slice."""
    from libjitsi_tpu.kernels.aes import expand_key
    from libjitsi_tpu.kernels.sha1 import hmac_precompute
    from libjitsi_tpu.transform.srtp import kernel as k

    def dense_args():
        # dense per-row SRTP inputs, keys pre-gathered per row (the
        # same shape family as __graft_entry__'s example args)
        rk = np.stack([
            expand_key(rng.integers(0, 256, 16,
                                    dtype=np.uint8).tobytes())
            for _ in range(batch)])
        mid = np.stack([
            hmac_precompute(rng.integers(0, 256, 20,
                                         dtype=np.uint8).tobytes())
            for _ in range(batch)])
        data = rng.integers(0, 256, (batch, width), dtype=np.uint8)
        data[:, 0] = 0x80
        length = np.full(batch, width - 16, dtype=np.int32)
        payload_off = np.full(batch, 12, dtype=np.int32)
        iv = rng.integers(0, 256, (batch, 16), dtype=np.uint8)
        roc = np.zeros(batch, dtype=np.uint32)
        return data, length, payload_off, rk, iv, mid, roc

    rx = dense_args()
    enc, enc_len = k.srtp_protect(*rx, tag_len=tag_len, encrypt=True)
    rx = (np.asarray(enc), np.asarray(enc_len, np.int32)) + rx[2:]
    tx = dense_args()
    pcm = rng.integers(-2000, 2000, (batch, frame)).astype(np.int16)
    active = np.ones(batch, dtype=bool)
    conf = ((np.arange(batch) // part) % n_conf).astype(np.int32)
    return rx + (pcm, active, conf) + tx


def assert_affinity_parity(mesh, n_devices: int, b_shard: int = 32,
                           part: int = 4, tag_len: int = 10,
                           seed: int = 11) -> None:
    """`affinity_tick` on the mesh must be bit-identical, shard by
    shard, to `affinity_step_ref` (the same body under plain jit) —
    the structural proof that the tick is shard-local: if anything
    leaked across the mesh axis, some shard's slice would differ from
    the single-device run of that slice alone."""
    import jax

    from libjitsi_tpu.mesh.placement import (affinity_step_ref,
                                             affinity_tick)

    rng = np.random.default_rng(seed)
    n_conf = b_shard // part
    args = build_affinity_workload(n_devices * b_shard, n_conf, rng,
                                   part=part, tag_len=tag_len)
    got = affinity_tick(mesh, n_conf, tag_len)(*args)
    jax.block_until_ready(got[3])
    if not bool(np.all(np.asarray(got[2]))):
        raise AssertionError("affinity tick failed SRTP auth")
    ref = affinity_step_ref(n_conf, tag_len)
    for s in range(n_devices):
        sl = slice(s * b_shard, (s + 1) * b_shard)
        want = ref(*[a[sl] for a in args])
        for got_a, want_a in zip(got, want):
            if not np.array_equal(np.asarray(got_a)[sl],
                                  np.asarray(want_a)):
                raise AssertionError(
                    f"affinity tick != per-shard reference on shard {s}")


# ------------------------------------------------- hierarchical broadcast

def build_broadcast_workload(n_devices: int, rows_per_shard: int,
                             n_conf: int, rng, frame: int = 160):
    """Argument tuple for `broadcast_bus_fanout`/`broadcast_step_ref`:
    each broadcast conference's ACTIVE speaker rows live only on its
    home shard (conference c homes on shard c % n_devices); all other
    rows are inactive padding — exactly the layout `ConferencePlacer.
    place_broadcast` produces for the speaker leg."""
    batch = n_devices * rows_per_shard
    pcm = rng.integers(-2000, 2000, (batch, frame)).astype(np.int16)
    active = np.zeros(batch, dtype=bool)
    conf = np.zeros(batch, dtype=np.int32)
    for c in range(n_conf):
        home = c % n_devices
        # a handful of speaker rows in the home shard's row range
        k = int(rng.integers(2, min(8, rows_per_shard // n_conf) + 1))
        base = home * rows_per_shard + (c // n_devices) * 8
        rows = np.arange(base, base + k)
        active[rows] = True
        conf[rows] = c
    return pcm, active, conf


def assert_hierarchy_parity(mesh, n_devices: int,
                            rows_per_shard: int = 32, n_conf: int = 4,
                            frame: int = 160, seed: int = 17) -> None:
    """`broadcast_bus_fanout` on the mesh must be bit-identical to
    `broadcast_step_ref` on one device: the speaker-shard segment-sum
    mix is exact, and the one-psum bus fan-out is exact because int32
    addition is associative — psum-of-per-shard-partial-sums equals the
    flat sum.  Any second collective, any listener-side mix, or any
    float path sneaking in would break bit equality."""
    import jax

    from libjitsi_tpu.mesh.hierarchy import (broadcast_bus_fanout,
                                             broadcast_step_ref)

    rng = np.random.default_rng(seed)
    args = build_broadcast_workload(n_devices, rows_per_shard, n_conf,
                                    rng, frame=frame)
    got = broadcast_bus_fanout(mesh, n_conf)(*args)
    jax.block_until_ready(got[0])
    want = broadcast_step_ref(n_conf)(*args)
    names = ("speaker mix-minus", "bus", "levels")
    for got_a, want_a, name in zip(got, want, names):
        if not np.array_equal(np.asarray(got_a), np.asarray(want_a)):
            raise AssertionError(
                f"hierarchical tick {name} != single-device reference")
    # the bus really is the per-conference speaker sum (numpy oracle)
    pcm, active, conf = args
    for c in range(n_conf):
        rows = active & (conf == c)
        flat = np.clip(pcm[rows].astype(np.int64).sum(axis=0),
                       -32768, 32767).astype(np.int16)
        if not np.array_equal(np.asarray(got[1])[c], flat):
            raise AssertionError(f"bus {c} != numpy speaker sum")
