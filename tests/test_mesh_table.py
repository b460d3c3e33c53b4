"""ShardedSrtpTable: the PRODUCT table sharded over the mesh must be
bit-identical to the single-chip SrtpStreamTable (shard
the product objects, not just the kernels)."""

import numpy as np
import pytest

from libjitsi_tpu.mesh import ShardedSrtpTable, make_media_mesh
from libjitsi_tpu.rtp import header as rtp_header
from libjitsi_tpu.transform.srtp import SrtpProfile, SrtpStreamTable

CAP = 16


def _tables(profile=SrtpProfile.AES_CM_128_HMAC_SHA1_80):
    rng = np.random.default_rng(41)
    mks = rng.integers(0, 256, (CAP, 16), dtype=np.uint8)
    mss = rng.integers(0, 256, (CAP, 14), dtype=np.uint8)
    mesh = make_media_mesh()
    sh = ShardedSrtpTable(CAP, mesh, profile)
    sh.add_streams(np.arange(CAP), mks, mss)
    plain = SrtpStreamTable(CAP, profile)
    plain.add_streams(np.arange(CAP), mks, mss)
    return sh, plain


def _batch(rng, n, seq0, sizes=(160,)):   # one size class: one compile pair
    streams = rng.integers(0, CAP, n)
    lens = rng.choice(sizes, n)
    payloads = [rng.integers(0, 256, l, dtype=np.uint8).tobytes()
                for l in lens]
    return rtp_header.build(
        payloads, [seq0 + i for i in range(n)], [i * 160 for i in range(n)],
        (0x5000 + streams).tolist(), [96] * n, stream=streams.tolist())


def test_sharded_protect_unprotect_bit_identical():
    sh_tx, plain_tx = _tables()
    sh_rx, plain_rx = _tables()
    rng = np.random.default_rng(42)
    for k in range(2):
        b = _batch(np.random.default_rng(100 + k), 24, 100 + 24 * k)
        b2 = _batch(np.random.default_rng(100 + k), 24, 100 + 24 * k)
        w_sh = sh_tx.protect_rtp(b)
        w_pl = plain_tx.protect_rtp(b2)
        for i in range(w_sh.batch_size):
            assert w_sh.to_bytes(i) == w_pl.to_bytes(i), f"row {i}"
        # host tx plane advanced identically
        np.testing.assert_array_equal(sh_tx.tx_ext, plain_tx.tx_ext)

        d_sh, ok_sh = sh_rx.unprotect_rtp(w_sh)
        d_pl, ok_pl = plain_rx.unprotect_rtp(w_pl)
        assert bool(np.all(ok_sh)) and bool(np.all(ok_pl))
        for i in range(d_sh.batch_size):
            assert d_sh.to_bytes(i) == d_pl.to_bytes(i)
        np.testing.assert_array_equal(sh_rx.rx_max, plain_rx.rx_max)
        np.testing.assert_array_equal(sh_rx.rx_mask, plain_rx.rx_mask)


def test_sharded_replay_and_tamper_rejection():
    sh_tx, _ = _tables()
    sh_rx, _ = _tables()
    b = _batch(np.random.default_rng(7), 16, 500)
    w = sh_tx.protect_rtp(b)
    d, ok = sh_rx.unprotect_rtp(w)
    assert bool(np.all(ok))
    # replay: same wire again must be rejected by the (host) windows
    w2 = sh_tx.protect_rtp(_batch(np.random.default_rng(7), 16, 500))
    _, ok2 = sh_rx.unprotect_rtp(w2)
    assert not bool(np.any(ok2))
    # tamper: flip one payload byte on a fresh batch -> that row fails
    w3 = sh_tx.protect_rtp(_batch(np.random.default_rng(8), 16, 600))
    w3.data[3, 20] ^= 0xFF
    _, ok3 = sh_rx.unprotect_rtp(w3)
    assert not ok3[3] and bool(np.sum(ok3) >= 14)


def test_sharded_table_rejects_indivisible_capacity():
    mesh = make_media_mesh()
    with pytest.raises(ValueError):
        ShardedSrtpTable(CAP + 1, mesh)


def test_sharded_f8_parity():
    """AES-F8 on the sharded table: the second key
    schedule shards on the same row partition — protect/unprotect must
    be bit-identical to the single-chip F8 table."""
    from libjitsi_tpu.mesh.parity import assert_table_parity

    assert_table_parity(make_media_mesh(), capacity=CAP, batch_size=24,
                        rounds=1,
                        profile=SrtpProfile.F8_128_HMAC_SHA1_80)


@pytest.mark.parametrize("profile,salt", [
    (SrtpProfile.AES_CM_128_HMAC_SHA1_80, 14),
    (SrtpProfile.F8_128_HMAC_SHA1_80, 14),
    (SrtpProfile.AEAD_AES_128_GCM, 12),
])
def test_sharded_srtcp_parity(profile, salt):
    """SRTCP runs SHARDED on the mesh table's RTCP key tables (control
    traffic must not silently hop to a single-chip path) — wire and decrypt byte-identical to the plain table."""
    from libjitsi_tpu.core.packet import PacketBatch

    rng = np.random.default_rng(3)
    mks = rng.integers(0, 256, (CAP, 16), dtype=np.uint8)
    mss = rng.integers(0, 256, (CAP, salt), dtype=np.uint8)
    mesh = make_media_mesh()

    def build(cls, *extra):
        tx = cls(CAP, *extra, profile)
        tx.add_streams(np.arange(CAP), mks, mss)
        rx = cls(CAP, *extra, profile)
        rx.add_streams(np.arange(CAP), mks, mss)
        return tx, rx

    sh_tx, sh_rx = build(ShardedSrtpTable, mesh)
    pl_tx, pl_rx = build(SrtpStreamTable)
    blobs = [b"\x81\xc8\x00\x06" + int(0x1000 + s).to_bytes(4, "big")
             + bytes([s]) * 20 for s in (2, 9, 2, 13)]
    b1 = PacketBatch.from_payloads(blobs, stream=[2, 9, 2, 13])
    b2 = PacketBatch.from_payloads(blobs, stream=[2, 9, 2, 13])
    w_sh = sh_tx.protect_rtcp(b1)
    w_pl = pl_tx.protect_rtcp(b2)
    for i in range(w_sh.batch_size):
        assert w_sh.to_bytes(i) == w_pl.to_bytes(i), f"rtcp row {i}"
    d_sh, ok_sh = sh_rx.unprotect_rtcp(w_sh)
    d_pl, ok_pl = pl_rx.unprotect_rtcp(w_pl)
    assert bool(np.all(ok_sh)) and bool(np.all(ok_pl))
    for i in range(d_sh.batch_size):
        assert d_sh.to_bytes(i) == d_pl.to_bytes(i)
    np.testing.assert_array_equal(sh_rx.rtcp_rx_max, pl_rx.rtcp_rx_max)
    np.testing.assert_array_equal(sh_tx.rtcp_tx_index,
                                  pl_tx.rtcp_tx_index)


def test_sharded_async_protect_matches_sync():
    """`protect_rtp_async` on the MESH table: the
    deferred-scatter seam must produce bit-identical wire to the sync
    mesh path, with host TX state committed at dispatch."""
    sh_a, _ = _tables()
    sh_b, _ = _tables()
    pends = []
    for k in range(3):
        b = _batch(np.random.default_rng(900 + k), 24, 700 + 24 * k)
        pends.append(sh_a.protect_rtp_async(b))
    # all three dispatched before any materialization: TX state already
    # committed (the async contract) and not touched by result()
    tx_at_dispatch = sh_a.tx_ext.copy()
    outs = [p.result() for p in pends]
    np.testing.assert_array_equal(sh_a.tx_ext, tx_at_dispatch)
    for k in range(3):
        b = _batch(np.random.default_rng(900 + k), 24, 700 + 24 * k)
        w = sh_b.protect_rtp(b)
        for i in range(w.batch_size):
            assert outs[k].to_bytes(i) == w.to_bytes(i), f"batch {k} row {i}"
    np.testing.assert_array_equal(sh_a.tx_ext, sh_b.tx_ext)


def test_mesh_gcm_grouped_and_per_row_parity():
    """The sharded GCM table's grouped-GHASH path must
    match the sharded per-row path and the single-chip table bit for
    bit; the live seam picks between them by registry measurement."""
    from libjitsi_tpu.kernels import registry

    prof = SrtpProfile.AEAD_AES_128_GCM
    rng = np.random.default_rng(41)
    mks = rng.integers(0, 256, (CAP, 16), dtype=np.uint8)
    mss = rng.integers(0, 256, (CAP, 12), dtype=np.uint8)
    mesh = make_media_mesh()

    def mk_pair(cls, *extra):
        tx = cls(CAP, *extra, prof)
        tx.add_streams(np.arange(CAP), mks, mss)
        rx = cls(CAP, *extra, prof)
        rx.add_streams(np.arange(CAP), mks, mss)
        return tx, rx

    wires = {}
    try:
        for prov in ("grouped", "per_row"):
            registry.force("mesh_gcm_rtp_protect", prov)
            registry.force("mesh_gcm_rtp_unprotect", prov)
            sh_tx, sh_rx = mk_pair(ShardedSrtpTable, mesh)
            # heavy stream reuse so the grouped grid is structurally
            # usable (24 lanes over <= 8 streams)
            r = np.random.default_rng(77)
            streams = r.integers(0, 8, 24)
            pls = [r.integers(0, 256, 40, dtype=np.uint8).tobytes()
                   for _ in range(24)]
            b = rtp_header.build(
                pls, list(range(200, 224)), [0] * 24,
                (0x5000 + streams).tolist(), [96] * 24,
                stream=streams.tolist())
            w = sh_tx.protect_rtp(b)
            wires[prov] = [w.to_bytes(i) for i in range(w.batch_size)]
            d, ok = sh_rx.unprotect_rtp(w)
            assert bool(np.all(ok)), f"{prov}: auth failed"
            for i in range(d.batch_size):
                assert d.to_bytes(i) == b.to_bytes(i)
    finally:
        registry.force("mesh_gcm_rtp_protect", None)
        registry.force("mesh_gcm_rtp_unprotect", None)
    assert wires["grouped"] == wires["per_row"]


def test_mesh_bridge_tick_matches_single_chip():
    """The ASSEMBLED ConferenceBridge in mesh mode (sharded SRTP tables
    + psum mixer) must emit byte-identical wire packets to the plain
    single-chip bridge — via the parity harness shared with the
    driver's multi-chip dryrun (libjitsi_tpu.mesh.parity)."""
    import libjitsi_tpu
    from libjitsi_tpu.mesh.parity import assert_bridge_parity
    from libjitsi_tpu.service.bridge import ConferenceBridge

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    cfg = libjitsi_tpu.configuration_service()
    mesh = make_media_mesh()
    assert_bridge_parity(cfg, mesh, capacity=16)
    # mesh COMPOSES with pipelined: the deferred-scatter
    # seam lets the dispatch overlap, and the wire stays byte-identical
    assert_bridge_parity(cfg, mesh, capacity=16, pipelined=True)


@pytest.mark.slow
def test_mesh_bridge_restore_stays_sharded_and_warmup():
    """A checkpointed mesh bridge must resume with MESH tables (not a
    silent single-chip fallback), and warmup() must pre-compile the
    lane ladder / measurement off the tick path."""
    import libjitsi_tpu
    from libjitsi_tpu.service.bridge import ConferenceBridge

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    cfg = libjitsi_tpu.configuration_service()
    mesh = make_media_mesh()
    bridge = ConferenceBridge(cfg, port=0, capacity=16,
                              recv_window_ms=0, mesh=mesh)
    bridge.add_participant(5, (b"\x05" * 16, b"\x06" * 14),
                           (b"\x07" * 16, b"\x08" * 14))
    snap = bridge.snapshot()
    bridge.close()
    b2 = ConferenceBridge.restore(cfg, snap, port=0, recv_window_ms=0,
                                  mesh=mesh)
    assert isinstance(b2.rx_table, ShardedSrtpTable)
    assert isinstance(b2.tx_table, ShardedSrtpTable)
    # sharded warmup ladder: compiles banked before any tick
    b2.rx_table.warmup(max_batch=8)
    assert ("protect", 10, True, 12) in b2.rx_table._sh_fns
    b2.close()
    # non-mesh warmup path (scratch table, real state untouched)
    b3 = ConferenceBridge(cfg, port=0, capacity=8, recv_window_ms=0)
    b3.add_participant(6, (b"\x01" * 16, b"\x02" * 14),
                       (b"\x03" * 16, b"\x04" * 14))
    tx_before = b3.tx_table.tx_ext.copy()
    b3.warmup()
    np.testing.assert_array_equal(b3.tx_table.tx_ext, tx_before)
    b3.close()


def test_sharded_gcm_table_parity_and_rtcp():
    """AEAD-GCM on the sharded table: per-row-form shard_map must be
    bit-identical to the single-chip GCM table (which itself picks
    grouped/per-row by measurement), and the inherited single-chip
    SRTCP path must work on the sharded object."""
    from libjitsi_tpu.core.packet import PacketBatch
    from libjitsi_tpu.mesh.parity import assert_table_parity

    mesh = make_media_mesh()
    assert_table_parity(mesh, capacity=CAP, batch_size=24,
                        profile=SrtpProfile.AEAD_AES_128_GCM)
    # SRTCP through the sharded object (inherited path)
    rng = np.random.default_rng(3)
    mks = rng.integers(0, 256, (CAP, 16), dtype=np.uint8)
    mss = rng.integers(0, 256, (CAP, 12), dtype=np.uint8)
    tx = ShardedSrtpTable(CAP, mesh, SrtpProfile.AEAD_AES_128_GCM)
    tx.add_streams(np.arange(CAP), mks, mss)
    rx = ShardedSrtpTable(CAP, mesh, SrtpProfile.AEAD_AES_128_GCM)
    rx.add_streams(np.arange(CAP), mks, mss)
    blob = b"\x81\xc8\x00\x06" + (0x1234).to_bytes(4, "big") + b"x" * 20
    b = PacketBatch.from_payloads([blob], stream=[2])
    wire = tx.protect_rtcp(b)
    dec, ok = rx.unprotect_rtcp(wire)
    assert bool(np.all(ok)) and dec.to_bytes(0) == blob
    # warmup and the live seams must share one fn-cache key (the gcm
    # ops normalize tag/encrypt out of the key)
    tx.warmup(max_batch=8)
    assert ("gcm_protect", 0, True, 12) in tx._sh_fns


@pytest.mark.slow
def test_mesh_sfu_bridge_fanout_matches_single_chip():
    """The ASSEMBLED SfuBridge in mesh mode (sharded tables + leg-
    sharded fan-out translator) must emit byte-identical forwarded wire
    to the single-chip bridge."""
    import libjitsi_tpu
    from libjitsi_tpu.mesh.parity import assert_sfu_parity
    from libjitsi_tpu.service.sfu_bridge import SfuBridge

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    cfg = libjitsi_tpu.configuration_service()
    mesh = make_media_mesh()
    # (both sides dispatch a tick's fan-out and collect it in the
    # next: the SFU's one tick shape, on one chip and on the mesh)
    assert_sfu_parity(cfg, mesh, capacity=16)
    # a mesh snapshot refuses a single-chip restore (un-sharding a
    # deployment must be loud, not silent)
    sfu = SfuBridge(cfg, port=0, capacity=16, recv_window_ms=0,
                    mesh=mesh)
    snap = sfu.snapshot()
    sfu.close()
    with pytest.raises(ValueError):
        SfuBridge.restore(cfg, snap, port=0)
    back = SfuBridge.restore(cfg, snap, port=0, mesh=mesh)
    back.close()


def test_sharded_table_on_2d_multihost_mesh():
    """DCN rehearsal at PRODUCT level: the sharded table partitions its
    rows over the 2-D (dcn, streams) mesh — same parity contract as the
    1-D mesh (SURVEY §2.7 DCN row)."""
    from libjitsi_tpu.mesh import make_multihost_mesh
    from libjitsi_tpu.mesh.parity import assert_table_parity

    mesh2d = make_multihost_mesh(2)
    assert mesh2d.devices.shape == (2, 4)
    assert_table_parity(mesh2d, capacity=CAP, batch_size=24, rounds=1)


@pytest.mark.slow
def test_mesh_bridge_on_2d_multihost_mesh():
    """The assembled ConferenceBridge also runs on the 2-D multi-host
    mesh (rows over (dcn x streams); mixer psums over both axes) —
    byte-identical to single-chip."""
    import libjitsi_tpu
    from libjitsi_tpu.mesh import make_multihost_mesh
    from libjitsi_tpu.mesh.parity import assert_bridge_parity

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    assert_bridge_parity(libjitsi_tpu.configuration_service(),
                         make_multihost_mesh(2), capacity=16)


def test_sharded_translator_cm_and_gcm_parity():
    """The leg-sharded fan-out translator must produce byte-identical
    wire to the single-chip RtpTranslator for BOTH CM and GCM (GCM via
    the sharded per-row form; the single-chip side free to pick its
    full-mesh fast path — outputs must agree regardless)."""
    from libjitsi_tpu.core.packet import PacketBatch
    from libjitsi_tpu.mesh import ShardedRtpTranslator
    from libjitsi_tpu.sfu.translator import RtpTranslator

    mesh = make_media_mesh()
    for profile, salt in ((SrtpProfile.AES_CM_128_HMAC_SHA1_80, 14),
                          (SrtpProfile.AEAD_AES_128_GCM, 12)):
        rng = np.random.default_rng(11)
        keys = {r: (bytes([r]) * 16, bytes([r + 1]) * salt)
                for r in range(8)}
        pair = []
        for cls, args in ((RtpTranslator, {"capacity": 8,
                                           "profile": profile}),
                          (ShardedRtpTranslator,
                           {"capacity": 8, "mesh": mesh,
                            "profile": profile})):
            tr = cls(**args)
            for r, (mk, ms) in keys.items():
                tr.add_receiver(r, mk, ms)
            tr.connect(0, list(range(1, 8)))
            pair.append(tr)
        pls = [rng.integers(0, 256, 40, dtype=np.uint8).tobytes()
               for _ in range(4)]
        # MIXED header sizes (CSRC lists on half the packets): payload
        # offsets differ per row, so _uniform_off returns None and the
        # sharded non-constant-offset trace is exercised too
        csrcs = [[], [0xAA], [], [0xBB, 0xCC]]
        outs = []
        for tr in pair:
            b = rtp_header.build(pls, [700 + i for i in range(4)],
                                 [0] * 4, [0x1234] * 4, [96] * 4,
                                 csrcs=csrcs, stream=[0] * 4)
            # fan-out needs tag headroom beyond the payload
            wide = PacketBatch.empty(b.batch_size, b.capacity + 32)
            wide.data[:, :b.capacity] = b.data
            wide.length[:] = b.length
            wide.stream[:] = b.stream
            out, recv = tr.translate(wide, np.arange(700, 704))
            outs.append({(int(recv[i]), i): out.to_bytes(i)
                         for i in range(out.batch_size)})
        assert outs[0] == outs[1], f"{profile} sharded fan-out diverged"


def test_sharded_table_kdr_rekey_parity():
    """kdr epoch re-keying on the SHARDED table: _install_session_keys
    mutates the key masters mid-stream, which must invalidate the
    sharded device copies through the _dev mirror — wire stays byte-
    identical to the plain table across an epoch boundary."""
    kdr = 8
    rng = np.random.default_rng(77)
    mk = rng.integers(0, 256, 16, dtype=np.uint8).tobytes()
    ms = rng.integers(0, 256, 14, dtype=np.uint8).tobytes()
    mesh = make_media_mesh()
    sh = ShardedSrtpTable(8, mesh)
    sh.add_stream(3, mk, ms, kdr=kdr)
    pl = SrtpStreamTable(8)
    pl.add_stream(3, mk, ms, kdr=kdr)

    def batch(start):
        return rtp_header.build([b"k" * 48] * 4,
                                [start + i for i in range(4)],
                                [0] * 4, [0x42] * 4, [96] * 4,
                                stream=[3] * 4)

    for start in (0, 6, 14, 30):       # crosses epochs 0->1->3
        w_sh = sh.protect_rtp(batch(start))
        w_pl = pl.protect_rtp(batch(start))
        for i in range(4):
            assert w_sh.to_bytes(i) == w_pl.to_bytes(i), (start, i)
    assert sh._epoch_rtp[3] == pl._epoch_rtp[3] >= 1


# ------------------------------------------------ the warm ladder's shapes

@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_owner_plan_lanes_are_row_classes(n_dev):
    """The lanes a chip are the row class of the hottest shard's REAL
    rows, whatever the skew: the one-chip ladder's five rungs are every
    lane count a mesh launch can take (multiples of the largest beyond
    it), and the plan routes every row to its owner and back."""
    from libjitsi_tpu.core.packet import ROW_CLASSES, _round_rows
    from libjitsi_tpu.mesh.table import _OwnerPlan, local_rows

    rng = np.random.default_rng(n_dev)
    cap = 1024 * n_dev
    rows_per = cap // n_dev
    for _ in range(200):
        n = int(rng.choice([1, 3, 15, 16, 17, 64, 400, 1023, 4088]))
        hot = rng.random() < 0.5          # half the draws: one hot shard
        ids = (rng.integers(0, rows_per, n) if hot
               else rng.integers(0, cap, n))
        plan = _OwnerPlan(ids, cap, rows_per, n_dev)
        top = int(np.bincount(ids // rows_per, minlength=n_dev).max())
        assert plan.per in ROW_CLASSES and plan.per == _round_rows(top)
        assert plan.slot.shape == (n_dev, plan.per)
        assert int(plan.counts.max()) == top
        # every row reaches the chip that owns it, and comes back
        np.testing.assert_array_equal(plan.slot.reshape(-1)[plan.inv],
                                      np.arange(n))
        local = local_rows(plan, ids, cap, rows_per, n_dev)
        owner = plan.inv // plan.per
        np.testing.assert_array_equal(
            owner * rows_per + local.reshape(-1)[plan.inv], ids)
    # shard-major, equal, class-filling batches route by identity
    ids = np.repeat(np.arange(n_dev) * rows_per, 16) + np.tile(
        np.arange(16), n_dev)
    plan = _OwnerPlan(ids, cap, rows_per, n_dev)
    assert plan.affine and plan.per == 16
    assert not _OwnerPlan(ids[:-1], cap, rows_per, n_dev).affine
    # past the largest class: multiples of it
    big = _OwnerPlan(np.zeros(5000, np.int64), cap, rows_per, n_dev)
    assert big.per == 2 * ROW_CLASSES[-1]
    # the fan-out's own 512-row class is the one-chip translator's: a
    # hot shard of 257-512 rows still takes 1,024 lanes a chip
    for hot in (257, 300, 512):
        assert _OwnerPlan(np.zeros(hot, np.int64), cap, rows_per,
                          n_dev).per == 1024


def _compiled_names(caplog):
    """Program names in JAX's compile log (`jax.log_compiles`)."""
    import re

    return set(re.findall(r"Compiling jit\(([\w.<>-]+)\) ", caplog.text))


@pytest.mark.parametrize("what", ["table", "translator"])
def test_warmups_on_sharded_objects_compile_the_mesh_programs(what,
                                                              caplog):
    """`warmup_rtp` / `warmup_rtcp` / `fanout_warmups` on a sharded
    object run on a scratch object of ITS class on ITS mesh: the
    programs in the compile log are the `mesh_*` ones the object
    launches, none of the one-chip ones, and the live object's state
    and placed tables are left alone."""
    import logging

    import jax

    from libjitsi_tpu.mesh import ShardedRtpTranslator

    mesh = make_media_mesh(jax.devices()[:4])
    cap = 40            # no other test's: the programs compile here
    caplog.set_level(logging.WARNING)
    with jax.log_compiles(True):
        if what == "table":
            tab = ShardedSrtpTable(cap, mesh)
            tab.add_stream(3, b"\x01" * 16, b"\x02" * 14)
            before = (tab.tx_ext.copy(), tab.rx_max.copy(),
                      tab.placements)
            tab.warmup_rtp(16)
            tab.warmup_rtcp(16)
            assert isinstance(tab._scratch(), ShardedSrtpTable)
            assert tab._scratch().mesh is mesh
            np.testing.assert_array_equal(tab.tx_ext, before[0])
            np.testing.assert_array_equal(tab.rx_max, before[1])
            assert tab.placements == before[2] == 0
            want = {"mesh_protect_rtp", "mesh_unprotect_rtp",
                    "mesh_rtcp_protect", "mesh_rtcp_unprotect"}
        else:
            tr = ShardedRtpTranslator(cap, mesh)
            thunks = tr.fanout_warmups(16)
            assert len(thunks) == 2       # one program a width
            # the ladder hands it `ROW_CLASSES` values, which round to
            # themselves: the mesh ladder's shapes are what they were
            assert tr._pads_rows is False
            thunks[0]()
            assert tr.placements == 0 and tr._sh_dev == {}
            want = {"mesh_fanout_protect"}
    names = _compiled_names(caplog)
    assert want <= names, names
    one_chip = {"_fanout_protect", "_protect_rtp_dev",
                "_unprotect_rtp_packed_impl", "_unprotect_rtp_impl",
                "_protect_rtcp_dev", "_unprotect_rtcp_dev"}
    assert not names & one_chip, names


def test_tables_of_one_mesh_share_their_programs():
    """rx table, tx table and every scratch table of a mesh launch the
    same jit objects, so a scratch table's warm-up warms the live
    tables (a table a jit of its own compiled everything again)."""
    import jax

    mesh = make_media_mesh(jax.devices()[:4])
    a, b = ShardedSrtpTable(CAP, mesh), ShardedSrtpTable(CAP, mesh)
    assert a._shard_fn("unprotect", 10, True, 12) is \
        b._shard_fn("unprotect", 10, True, 12)
    assert a._scratch()._sh_fns is a._sh_fns
    other = ShardedSrtpTable(CAP, make_media_mesh(jax.devices()[:2]))
    assert other._sh_fns is not a._sh_fns


# ------------------------------------- the packed mesh seams (one plane)

def _ids_for(case: str, rows_per: int) -> np.ndarray:
    """Key rows of one batch over four shards of `rows_per` rows."""
    rng = np.random.default_rng(7)
    if case == "skewed":             # 3 / 30 / 2 / 5 rows a shard
        return rng.permutation(np.concatenate([
            d * rows_per + rng.integers(0, rows_per, n)
            for d, n in enumerate((3, 30, 2, 5))]))
    if case == "two_empty_shards":   # the mesh cell's 1/0/0/8 x 7 rows
        return np.concatenate([rng.integers(0, rows_per, 7),
                               3 * rows_per + rng.integers(0, rows_per,
                                                           56)])
    if case == "affine":             # shard-major, a row class a shard
        return np.concatenate([d * rows_per + rng.integers(0, rows_per,
                                                           16)
                               for d in range(4)])
    return np.array([2 * rows_per + 5])          # one row


@pytest.mark.parametrize("case", ["skewed", "two_empty_shards", "affine",
                                  "one_row"])
def test_lane_plane_there_and_back_is_the_identity(case):
    """`pack` -> lane gather -> the mesh -> inverse gather ->
    `split_out` hands every real row back where it was: its bytes, its
    three words and its IV, with word 0 the chip-local key row; one
    array crosses each way and its bytes are the lane plane's."""
    import jax

    from libjitsi_tpu.core import staging
    from libjitsi_tpu.core.packet import _round_rows

    mesh = make_media_mesh(jax.devices()[:4])
    tab = ShardedSrtpTable(64, mesh)
    ids = _ids_for(case, tab.rows_per)
    n, width = len(ids), 224
    rng = np.random.default_rng(len(ids))
    plane = staging.alloc(n, width)
    plane[:, :width] = rng.integers(0, 256, (n, width), dtype=np.uint8)
    words = [rng.integers(0, 1 << 32, n, dtype=np.int64) for _ in range(3)]
    iv = rng.integers(0, 256, (n, 16), dtype=np.uint8)
    sent = plane[:, :width].copy()

    launch = tab._packed_call(
        lambda _rk, _mid, lanes: lanes, plane, ids, words, iv,
        lambda host: (staging.split_out(host, staging.WORDS),
                      host[:, -staging.IV_BYTES:]))
    (data, got), iv_back = launch.fetch()
    np.testing.assert_array_equal(data, sent)
    np.testing.assert_array_equal(got[:, 0], ids % tab.rows_per)
    for k, w in enumerate(words):
        np.testing.assert_array_equal(got[:, 1 + k].view(np.uint32), w)
    np.testing.assert_array_equal(iv_back, iv)
    hottest = int(np.bincount(ids // tab.rows_per, minlength=4).max())
    lanes = _round_rows(hottest)
    assert launch.counts == {"shards": 4, "lanes": lanes,
                             "rows_hottest_shard": hottest,
                             "affine": int(case == "affine")}
    assert launch.h2d_arrays == launch.d2h_arrays == 1
    assert launch.h2d_bytes == launch.d2h_bytes \
        == 4 * lanes * (width + staging.TAIL)
    np.testing.assert_array_equal(
        tab.shard_rows, np.bincount(ids // tab.rows_per, minlength=4))


def _spy(obj, seam: str) -> list:
    """Keep every `staging.Launch` that `obj`'s seam hands back."""
    kept, real = [], getattr(obj, seam)

    def call(*args, **kwargs):
        kept.append(real(*args, **kwargs))
        return kept[-1]

    setattr(obj, seam, call)
    return kept


def _same_launch_outputs(mesh_launch, one_launch) -> int:
    """A mesh seam's `fetch()` against the one-chip seam's, over the
    mesh's rows (the one-chip host plane pads its rows behind them):
    same arrays, same dtypes.  Returns the rows compared."""
    got, want = mesh_launch.fetch(), one_launch.fetch()
    assert len(got) == len(want)
    n = len(got[0])
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape[1:] == w.shape[1:]
        np.testing.assert_array_equal(g, w[:n])
    return n


@pytest.mark.parametrize("payload,width", [(100, 224), (400, 544)])
def test_packed_mesh_unprotect_equals_the_one_chip_table(payload, width):
    """The packed mesh unprotect hands back what the one-chip packed
    unprotect does, byte for byte: data, media lengths and verdicts,
    a flipped tag bit refused by both."""
    import jax

    mesh = make_media_mesh(jax.devices()[:4])
    rng = np.random.default_rng(payload)
    mks = rng.integers(0, 256, (CAP, 16), dtype=np.uint8)
    mss = rng.integers(0, 256, (CAP, 14), dtype=np.uint8)
    tx = SrtpStreamTable(CAP)
    sh, one = ShardedSrtpTable(CAP, mesh), SrtpStreamTable(CAP)
    for t in (tx, sh, one):
        t.add_streams(np.arange(CAP), mks, mss)
    wire = tx.protect_rtp(_batch(rng, 21, 500, sizes=(payload,)))
    bad = 5
    wire.data[bad, int(wire.length[bad]) - 1] ^= 0x04
    spies = [_spy(t, "_cm_rtp_unprotect_call") for t in (sh, one)]
    oks = []
    for t in (sh, one):
        copy = type(wire)(wire.data.copy(), wire.length.copy(),
                          wire.stream.copy())
        _out, ok = t.unprotect_rtp(copy)
        oks.append(np.asarray(ok))
    np.testing.assert_array_equal(oks[0], oks[1])
    assert not oks[0][bad] and oks[0].sum() == 20
    (m,), (o,) = spies
    assert _same_launch_outputs(m, o) == 21
    data, mlen, auth_ok = m.fetch()
    assert data.shape == (21, width) and mlen.dtype == np.int32
    assert auth_ok.dtype == bool and auth_ok.sum() == 20
    assert (m.h2d_arrays, m.d2h_arrays) == (1, 1)


@pytest.mark.parametrize("payload,width", [(40, 224), (400, 544)])
def test_packed_mesh_fanout_equals_the_one_chip_translator(payload,
                                                           width):
    """The packed mesh fan-out hands back what the one-chip packed
    fan-out does, byte for byte: wire bytes and wire lengths."""
    import jax

    from libjitsi_tpu.core.packet import PacketBatch
    from libjitsi_tpu.mesh import ShardedRtpTranslator
    from libjitsi_tpu.sfu.translator import RtpTranslator

    mesh = make_media_mesh(jax.devices()[:4])
    rng = np.random.default_rng(payload)
    keys = rng.integers(0, 256, (CAP, 30), dtype=np.uint8)
    pair = (ShardedRtpTranslator(CAP, mesh), RtpTranslator(CAP))
    for tr in pair:
        for r in range(CAP):
            tr.add_receiver(r, keys[r, :16].tobytes(),
                            keys[r, 16:].tobytes())
        tr.connect(0, list(range(1, CAP)))      # every shard has legs
    pls = [rng.integers(0, 256, payload, dtype=np.uint8).tobytes()
           for _ in range(3)]
    spies = [_spy(tr, "_cm_fanout_call") for tr in pair]
    outs = []
    for tr in pair:
        b = rtp_header.build(pls, [900 + i for i in range(3)], [0] * 3,
                             [0x4321] * 3, [96] * 3, stream=[0] * 3)
        wide = PacketBatch.empty(b.batch_size, b.capacity + 32)
        wide.data[:, :b.capacity] = b.data
        wide.length[:] = b.length
        wide.stream[:] = b.stream
        out, recv = tr.translate(wide, np.arange(900, 903))
        outs.append({(int(recv[i]), i): out.to_bytes(i)
                     for i in range(out.batch_size)})
    assert outs[0] == outs[1] and len(outs[0]) == 3 * (CAP - 1)
    (m,), (o,) = spies
    assert _same_launch_outputs(m, o) == 3 * (CAP - 1)
    data, lens = m.fetch()
    assert data.shape == (45, width) and lens.dtype == np.int32
    assert (m.h2d_arrays, m.d2h_arrays) == (1, 1)
