"""StreamLifecycleManager unit tests: the admission state machine
(queue -> stage -> commit), every typed rejection reason, evict
bookkeeping vs overload shedding, bucketed warmup cadence, the
tick-bracket compile guard, and checkpoint reconciliation — all
against a host-only dummy bridge (no sockets, no device).  The e2e
staged-install recovery proof lives in tests/test_chaos_recovery.py
and the full churn soak in scripts/churn_soak.py (slow twin below).
"""

import importlib.util
import os
import types

import numpy as np
import pytest

import libjitsi_tpu
from libjitsi_tpu.control.dtls import StubDtlsEndpoint
from libjitsi_tpu.io import UdpEngine
from libjitsi_tpu.service.lifecycle import (ADMIT_REASONS,
                                            LifecycleConfig,
                                            StreamLifecycleManager)
from libjitsi_tpu.service.sfu_bridge import SfuBridge
from libjitsi_tpu.service.supervisor import (BridgeSupervisor,
                                             SupervisorConfig)
from libjitsi_tpu.utils.metrics import MetricsRegistry

_SOAK = os.path.join(os.path.dirname(__file__), os.pardir,
                     "scripts", "churn_soak.py")


class WarmTable:
    """Records warmup calls: the lifecycle plane's pre-compile cadence
    is observable as the exact (row_class) sequence it warms."""

    def __init__(self):
        self.rtp_warms = []
        self.rtcp_warms = []
        self.active = np.zeros(64, dtype=bool)

    def warmup_rtp(self, rows, payload_len=160):
        self.rtp_warms.append(rows)

    def warmup_rtcp(self, batch_size=1):
        self.rtcp_warms.append(batch_size)


class LcBridge:
    """Host-side stand-in implementing exactly the surface the manager
    drives: slot registry, stage/commit/remove, warmable tables."""

    def __init__(self, capacity=8):
        self.capacity = capacity
        self._free = list(range(capacity))
        self._ssrc_of = {}
        self._tx_keys = {}
        self._staged = set()
        self.rx_table = WarmTable()
        self.tx_table = WarmTable()
        self.calls = []
        bridge = self

        class _Reg:
            @property
            def free_slots(self):
                return len(bridge._free)

        self.registry = _Reg()

    def has_ssrc(self, ssrc):
        return ssrc in self._ssrc_of.values()

    def stage_endpoints(self, specs):
        sids = []
        for ssrc, rx, tx, _name in specs:
            sid = self._free.pop(0)
            self._ssrc_of[sid] = ssrc
            self._tx_keys[sid] = tuple(tx)
            self._staged.add(sid)
            sids.append(sid)
        self.calls.append(("stage", tuple(sids)))
        return sids

    def commit_endpoints(self, sids):
        for sid in sids:
            self._staged.discard(int(sid))
        self.calls.append(("commit", tuple(int(s) for s in sids)))

    def remove_endpoints(self, sids):
        for sid in sids:
            sid = int(sid)
            self._ssrc_of.pop(sid, None)
            self._tx_keys.pop(sid, None)
            self._staged.discard(sid)
            self._free.append(sid)
        self.calls.append(("remove", tuple(int(s) for s in sids)))


def _keys(b):
    return (bytes([b]) * 16, bytes([b + 1]) * 14)


def _lc(capacity=8, supervisor=None, **cfg):
    bridge = LcBridge(capacity=capacity)
    lc = StreamLifecycleManager(bridge, supervisor=supervisor,
                                config=LifecycleConfig(**cfg))
    return lc, bridge


@pytest.mark.parametrize("stated, devices, placement, refused", [
    (0, 0, 4, None), (0, 4, 2, None), (4, 4, 4, None), (1, 0, 1, None),
    (4, 0, 4, "the bridge's tables have 1"),
    (1, 4, 1, "the bridge's tables have 4"),
    (4, 4, 2, "2 placement shards over 4 table shards")])
def test_stated_table_shards_are_held_to_the_bridge(stated, devices,
                                                    placement, refused):
    """`LifecycleConfig.table_shards` picks nothing: a deployment that
    states its bridge's table shards gets a manager that refuses a
    bridge, or a placement, sharded otherwise; unstated, as before."""
    bridge = LcBridge(capacity=8)
    bridge.registry.capacity = 8
    if devices:
        bridge._mesh = types.SimpleNamespace(
            devices=np.zeros((devices, 1), dtype=object))
    cfg = LifecycleConfig(table_shards=stated)
    if refused is None:
        lc = StreamLifecycleManager(bridge, config=cfg)
        lc.enable_placement(placement)
        assert lc.placer.n_shards == placement
        return
    with pytest.raises(ValueError, match=refused):
        StreamLifecycleManager(bridge, config=cfg).enable_placement(
            placement)


def _all_events(flight):
    """Flatten global + per-stream rings, in record order (sid-keyed
    events route to per-stream rings; `seq` restores the interleave)."""
    d = flight.dump_all()
    evs = list(d["global"])
    for ring in d["streams"].values():
        evs.extend(ring)
    return sorted(evs, key=lambda e: e["seq"])


def _global_kinds(lc):
    return [e["kind"] for e in lc.flight.dump_all()["global"]]


# ---------------------------------------------------- admit pipeline

def test_join_queues_then_stages_then_commits_off_tick():
    lc, bridge = _lc()
    ok, why = lc.request_join(0x10, _keys(2), _keys(4))
    assert (ok, why) == (True, "queued")
    # nothing touched the bridge yet — admission is pure bookkeeping
    assert not bridge.calls and lc.admits == 0
    # barrier 1: commit (nothing staged) then stage the install wave
    lc.run_between_ticks()
    assert bridge.calls == [("stage", (0,))]
    assert lc.key_installs == 1 and lc.admits == 0
    assert 0 in bridge._staged            # staged, not yet live
    # barrier 2: the staged batch flips live atomically
    lc.run_between_ticks()
    assert bridge.calls[1] == ("commit", (0,))
    assert lc.admits == 1 and 0 not in bridge._staged
    kinds = [e["kind"] for e in _all_events(lc.flight)]
    assert kinds.index("admit_queued") < kinds.index("key_install") \
        < kinds.index("admit_commit")


def test_install_wave_is_batch_limited():
    lc, bridge = _lc(capacity=8, install_batch=2)
    for i in range(5):
        assert lc.request_join(0x100 + i, _keys(2), _keys(4))[0]
    lc.run_between_ticks()
    assert len(bridge._staged) == 2       # install_batch, not all 5
    assert lc.key_installs_pending == 5   # 3 queued + 2 staged
    lc.run_between_ticks()                # commit 2, stage next 2
    assert lc.admits == 2 and len(bridge._staged) == 2
    lc.run_between_ticks()
    lc.run_between_ticks()
    assert lc.admits == 5 and lc.key_installs_pending == 0


def test_leave_cancels_queued_join_without_touching_bridge():
    lc, bridge = _lc()
    lc.request_join(0x42, _keys(2), _keys(4))
    assert lc.request_leave(ssrc=0x42)
    lc.run_between_ticks()
    assert not bridge.calls and lc.admits == 0 and lc.evicts == 0
    assert "admit_cancelled" in _global_kinds(lc)
    # unknown ssrc: nothing to cancel or evict
    assert not lc.request_leave(ssrc=0xDEAD)


def test_live_evict_lands_at_the_barrier_and_recycles_the_slot():
    lc, bridge = _lc(capacity=2)
    lc.request_join(0x21, _keys(2), _keys(4))
    lc.request_join(0x22, _keys(6), _keys(8))
    lc.run_between_ticks()
    lc.run_between_ticks()
    assert lc.admits == 2 and bridge.registry.free_slots == 0
    assert lc.request_leave(ssrc=0x21)
    # queued evict frees nothing until the barrier
    assert bridge.registry.free_slots == 0
    lc.run_between_ticks()
    assert lc.evicts == 1 and bridge.registry.free_slots == 1
    assert ("remove", (0,)) in bridge.calls
    # duplicate evict requests de-dup; departed sid is simply gone
    lc.request_leave(sid=0)
    lc.request_leave(sid=0)
    lc.run_between_ticks()
    assert lc.evicts == 1
    # the freed slot admits a NEW stream
    assert lc.request_join(0x23, _keys(10), _keys(12))[0]
    lc.run_between_ticks()
    lc.run_between_ticks()
    assert lc.admits == 3 and 0x23 in bridge._ssrc_of.values()


# ------------------------------------------------- typed rejections

def test_host_side_rejections_are_typed_and_counted():
    lc, bridge = _lc(capacity=2, max_pending=8)
    assert lc.request_join(0x31, _keys(2), _keys(4))[0]
    # duplicate: already queued
    assert lc.request_join(0x31, _keys(2), _keys(4)) \
        == (False, "duplicate")
    # capacity: queued joins have slots spoken for (2 slots, 1 queued,
    # next join fits; the one after does not)
    assert lc.request_join(0x32, _keys(6), _keys(8))[0]
    assert lc.request_join(0x33, _keys(10), _keys(12)) \
        == (False, "capacity")
    lc.run_between_ticks()
    lc.run_between_ticks()
    # duplicate: already live
    assert lc.request_join(0x31, _keys(2), _keys(4)) \
        == (False, "duplicate")
    assert lc.admit_rejected == {"duplicate": 2, "capacity": 1}
    rejects = [e for e in lc.flight.dump_all()["global"]
               if e["kind"] == "admit_reject"]
    assert [e["reason"] for e in rejects] \
        == ["duplicate", "capacity", "duplicate"]
    assert all(e["reason"] in ADMIT_REASONS for e in rejects)


def test_backlog_rejection_bounds_the_queue():
    lc, _bridge = _lc(capacity=8, max_pending=3)
    for i in range(3):
        assert lc.request_join(0x50 + i, _keys(2), _keys(4))[0]
    assert lc.request_join(0x60, _keys(2), _keys(4)) \
        == (False, "backlog")
    assert lc.admit_rejected == {"backlog": 1}


def test_supervisor_burn_reasons_pass_through():
    for reason in ("fast_burn", "stalled", "shedding", "host_bound"):
        sup = types.SimpleNamespace(
            ticks=7, flight=None, pending_lifecycle=None,
            admission_decision=lambda r=reason: (False, r))
        lc, _bridge = _lc()
        lc.supervisor = sup        # attach after init: flight stays own
        assert lc.request_join(0x70, _keys(2), _keys(4)) \
            == (False, reason)
        assert lc.admit_rejected == {reason: 1}
        assert reason in ADMIT_REASONS
        (ev,) = [e for e in lc.flight.dump_all()["global"]
                 if e["kind"] == "admit_reject"]
        assert ev["tick"] == 7 and ev["reason"] == reason


def test_rejections_render_as_typed_metric_labels():
    reg = MetricsRegistry()
    bridge = LcBridge(capacity=1)
    lc = StreamLifecycleManager(bridge, config=LifecycleConfig(),
                                metrics=reg)
    lc.request_join(0x10, _keys(2), _keys(4))
    lc.request_join(0x10, _keys(2), _keys(4))     # duplicate
    lc.request_join(0x11, _keys(2), _keys(4))     # capacity
    txt = reg.render()
    assert ('libjitsi_tpu_lifecycle_admit_rejected'
            '{reason="duplicate"} 1') in txt
    assert ('libjitsi_tpu_lifecycle_admit_rejected'
            '{reason="capacity"} 1') in txt
    assert "# TYPE libjitsi_tpu_lifecycle_admits counter" in txt


# ------------------------------------------------- bucketed warmup

def test_warmups_fire_only_at_bucket_boundaries():
    lc, bridge = _lc(capacity=64, min_bucket=4, pkts_per_stream=4,
                     install_batch=64, max_pending=512)
    lc.request_join(0x80, _keys(2), _keys(4))
    lc.run_between_ticks()
    # bucket 4 -> aggregate estimate 16 rows -> one class of headroom
    # covers 64; both tables warm RTP and RTCP for each class
    assert bridge.rx_table.rtp_warms == [16, 64]
    assert bridge.tx_table.rtp_warms == [16, 64]
    assert bridge.rx_table.rtcp_warms == [16, 64]
    # admits WITHIN the bucket compile nothing new
    for i in range(3):
        lc.request_join(0x81 + i, _keys(2), _keys(4))
    lc.run_between_ticks()
    assert bridge.rx_table.rtp_warms == [16, 64]
    # crossing the boundary warms only the NEW classes, off-tick
    for i in range(10):
        lc.request_join(0x90 + i, _keys(2), _keys(4))
    lc.run_between_ticks()
    assert bridge.rx_table.rtp_warms == [16, 64, 256]
    assert lc._warm_bucket == 16


def test_rtp_warmup_runs_beside_the_rest_rx_before_tx(monkeypatch):
    """Nothing in a rung is timed (the GCM form is a rule of the shape),
    so the RTP pair compiles in the pool with the fan-out variants and
    the SRTCP pair: all four overlap.  Within the RTP pair rx goes
    first and tx finds its programs warm."""
    import threading

    monkeypatch.setattr(os, "cpu_count", lambda: 4)   # pool: 1 per core
    lc, bridge = _lc(capacity=64, min_bucket=4)
    log, lock = [], threading.Lock()
    # rx RTP + 2 fan-outs (one a width: `fanout_warmups`' count since
    # the payload offset is an operand) + SRTCP: passes only if all
    # four overlap
    together = threading.Barrier(4, timeout=30)

    def span(name, meet):
        def run(*_a, **_k):
            with lock:
                log.append(("in", name))
            if meet:
                together.wait()
            with lock:
                log.append(("out", name))
        return run

    bridge.rx_table.warmup_rtp = span("rx_rtp", True)
    bridge.tx_table.warmup_rtp = span("tx_rtp", False)
    bridge.rx_table.warmup_rtcp = span("rtcp", True)
    bridge.tx_table.warmup_rtcp = lambda *_a, **_k: None
    bridge.translator = types.SimpleNamespace(
        fanout_warmups=lambda rc, payload_len: [span("fan0", True),
                                                span("fan1", True)])
    lc._warm_class(16, rtp=True)
    assert sorted(log[:4]) == [("in", "fan0"), ("in", "fan1"),
                               ("in", "rtcp"), ("in", "rx_rtp")]
    assert log.index(("out", "rx_rtp")) < log.index(("in", "tx_rtp"))
    assert ("out", "tx_rtp") in log
    log.clear()
    together = threading.Barrier(3, timeout=30)
    lc._warm_class(64, rtp=False)              # listener rows: no RTP
    assert {n for _, n in log} == {"fan0", "fan1", "rtcp"}


class FanStub:
    """A translator as the ladder sees it: whether it pads its rows in
    `expand` (the one-chip translator does, the mesh's does not), its
    `launch_rows`, and `fanout_warmups` giving one thunk a width, which
    records (rows, width) when the pool runs it."""

    def __init__(self, pads_rows: bool):
        self._pads_rows = pads_rows
        self.launch_rows = 4096
        self.warmed = []

    def fanout_warmups(self, rows, payload_len):
        return [lambda w=w: self.warmed.append((rows, w))
                for w in (224, 1536)]


@pytest.mark.parametrize("pads_rows, programs, fanout_classes", [
    (True, 32, [16, 64, 256, 512, 1024, 4096]),
    (False, 30, [16, 64, 256, 1024, 4096])], ids=["one_chip", "mesh"])
def test_a_whole_ladder_warms_the_fanouts_own_classes(
        pads_rows, programs, fanout_classes):
    """Five rungs of six programs (the RTP pair, the fan-out at its two
    widths, the SRTCP pair) and, for a translator that pads its rows to
    the fan-out's own classes, the 512-row fan-out beside the 1,024-row
    rung: 32 programs on one chip, the parent's 30 on a mesh, whose
    lanes `_OwnerPlan` pads to `ROW_CLASSES`.  `launch_rows` is the
    largest rung either way."""
    lc, bridge = _lc(capacity=4096, min_bucket=4, pkts_per_stream=4)
    tr = bridge.translator = FanStub(pads_rows)
    lc._ensure_warm(2048)
    rx, tx = bridge.rx_table, bridge.tx_table
    assert rx.rtp_warms == tx.rtp_warms == [16, 64, 256, 1024, 4096]
    assert rx.rtcp_warms == tx.rtcp_warms == [16, 64, 256, 1024, 4096]
    assert sorted(tr.warmed) == [(c, w) for c in fanout_classes
                                 for w in (224, 1536)]
    assert (len(rx.rtp_warms) + len(tx.rtp_warms) + len(rx.rtcp_warms)
            + len(tx.rtcp_warms) + len(tr.warmed)) == programs
    assert sorted(lc._warm_rows) == [16, 64, 256, 1024, 4096]
    assert tr.launch_rows == 4096


def test_the_512_row_fanout_joins_the_pool_of_the_1024_row_rung():
    """A ladder that stops at 256 rows warms no 512-row fan-out (no
    tick of at most `launch_rows` = 256 rows pads to it); the rung that
    warms 1,024 warms 512 in the same pool, so every class under
    `launch_rows` is warm whenever `plan_launches` may cut by it."""
    lc, bridge = _lc(capacity=4096, min_bucket=4, pkts_per_stream=1)
    tr = bridge.translator = FanStub(True)
    lc._ensure_warm(64)                   # 64 rows: cover 256
    assert sorted({c for c, _w in tr.warmed}) == [16, 64, 256]
    assert tr.launch_rows == 256
    del tr.warmed[:]
    lc._ensure_warm(256)                  # 256 rows: cover 1,024
    assert sorted(tr.warmed) == [(c, w) for c in (512, 1024)
                                 for w in (224, 1536)]
    assert bridge.rx_table.rtp_warms == [16, 64, 256, 1024]
    assert tr.launch_rows == 1024


def test_a_listener_only_ladder_warms_the_512_row_fanout_too():
    """Listener rows warm no uplink RTP, and the fan-out at every one
    of its own classes under the cover."""
    lc, bridge = _lc(capacity=4096, min_bucket=4)
    tr = bridge.translator = FanStub(True)
    lc._ensure_warm_listeners(200)        # bucket 256: cover 1,024
    assert bridge.rx_table.rtp_warms == []
    assert bridge.rx_table.rtcp_warms == [16, 64, 256, 1024]
    assert sorted({c for c, _w in tr.warmed}) == [16, 64, 256, 512, 1024]
    assert sorted(lc._warm_lrows) == [16, 64, 256, 1024]
    assert tr.launch_rows == 1024


def test_the_translators_say_whether_they_pad_their_rows():
    from libjitsi_tpu.mesh.translator import ShardedRtpTranslator
    from libjitsi_tpu.sfu.translator import RtpTranslator

    assert RtpTranslator._pads_rows is True
    assert ShardedRtpTranslator._pads_rows is False


# -------------------------------------------- tick compile bracket

def test_tick_bracket_counts_in_window_compiles(monkeypatch):
    from libjitsi_tpu.service import lifecycle as lc_mod
    events = {"n": 0}
    monkeypatch.setattr(
        lc_mod, "compile_stats",
        lambda: types.SimpleNamespace(compile_events=events["n"]))
    lc, _bridge = _lc()
    lc.tick_begin()
    lc.tick_end()                 # quiet tick: clean
    assert lc.datapath_recompiles == 0
    lc.assert_datapath_clean()
    lc.tick_begin()
    events["n"] += 3              # a compile landed INSIDE the tick
    lc.tick_end()
    assert lc.datapath_recompiles == 3
    assert "datapath_recompile" in _global_kinds(lc)
    with pytest.raises(AssertionError, match="3 compile event"):
        lc.assert_datapath_clean()
    # compiles between brackets (off-tick) never count
    events["n"] += 5
    lc.tick_begin()
    lc.tick_end()
    assert lc.datapath_recompiles == 3


# ------------------------------------------ shed vs evict separation

class DummyLoop:
    def __init__(self, cap):
        self.registry = types.SimpleNamespace(capacity=cap)
        self.recv_window_ms = 1
        self.inbound_drop = np.zeros(cap, dtype=bool)
        self.inbound_dropped = np.zeros(cap, dtype=np.int64)
        self.inbound_dropped_total = 0


class DummyBridge:
    def __init__(self, cap=8, sids=(0, 1, 2, 3)):
        self.loop = DummyLoop(cap)
        self.degraded = False
        self._ssrc_of = {s: 100 + s for s in sids}
        self.rx_table = types.SimpleNamespace(
            auth_fail=np.zeros(cap, dtype=np.int64),
            replay_reject=np.zeros(cap, dtype=np.int64))
        self.speaker = types.SimpleNamespace(dominant=0)

    def tick(self, now=None):
        return {"rx": 0}


class FakeClock:
    def __init__(self, durations):
        self.durations = list(durations)
        self.t = 0.0
        self.half = False

    def __call__(self):
        if self.half:
            self.t += self.durations.pop(0) if self.durations else 0.0
        self.half = not self.half
        return self.t


def test_lifo_unwind_never_resurrects_an_evicted_stream():
    # drive the ladder until streams shed, evict one of them via the
    # lifecycle path, then recover: the LIFO unwind must restore the
    # OTHER shed streams and skip the departed one
    sup = BridgeSupervisor(
        DummyBridge(), SupervisorConfig(deadline_ms=10.0,
                                        overload_after=1, shed_step=2,
                                        overload_exit=1),
        clock=FakeClock([0.05] * 7 + [0.001] * 30))
    for _ in range(7):
        sup.tick()
    shed = list(sup._shed)
    assert len(shed) >= 2
    gone = shed[-1]
    sup.note_evicted([gone])
    assert gone not in sup._shed_set      # membership cleared at once
    assert gone in sup._evicted
    assert sup.health()["evicted"] == 1
    for _ in range(30):
        sup.tick()
    assert sup.level == 0 and not sup._shed
    restored = [e["sid"] for e in _all_events(sup.flight)
                if e["kind"] == "shed_restore"]
    assert gone not in restored
    assert set(restored) == set(shed) - {gone}
    # flight keeps the two mortalities distinct
    kinds_gone = [e["kind"] for e in sup.flight.dump(gone)["events"]]
    assert "evicted" in kinds_gone and "shed" in kinds_gone
    # a NEW stream admitted into the recycled row is shed-eligible again
    sup.note_admitted([gone])
    assert gone not in sup._evicted and sup.health()["evicted"] == 0


def test_eviction_clears_quarantine_and_strike_history():
    cfg = SupervisorConfig(deadline_ms=1000.0, quarantine_window=5,
                           quarantine_auth_threshold=10,
                           quarantine_backoff_ticks=4)
    bridge = DummyBridge()
    sup = BridgeSupervisor(bridge, cfg)
    for _ in range(3):
        bridge.rx_table.auth_fail[2] += 4
        sup.tick(now=0.0)
    assert 2 in sup._quarantined
    sup.note_evicted([2])
    # the departed stream's ban and strike history die with it: the
    # row's next occupant starts with a clean record
    assert 2 not in sup._quarantined and 2 not in sup._q_strikes
    assert not bridge.loop.inbound_drop[2]


def test_admission_decision_reflects_live_pressure():
    sup = BridgeSupervisor(DummyBridge(),
                           SupervisorConfig(deadline_ms=10.0))
    assert sup.admission_decision() == (True, "ok")
    sup._shed_set.add(3)
    assert sup.admission_decision() == (False, "shedding")
    sup._shed_set.clear()
    sup.slo = types.SimpleNamespace(state=lambda *a: "fast_burn",
                                    on_tick=lambda: None)
    assert sup.admission_decision() == (False, "fast_burn")


# ------------------------------------------------- handshake plane

def _dtls_lc(**cfg):
    """Real SfuBridge (the handshake plane wraps its association
    table) + supervisor + lifecycle manager, stub endpoints so the
    tests run without the `cryptography` package."""
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    bridge = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                       capacity=8, recv_window_ms=0)
    bridge._dtls.endpoint_factory = StubDtlsEndpoint
    sup = BridgeSupervisor(bridge, SupervisorConfig(deadline_ms=1000.0))
    lc = StreamLifecycleManager(bridge, supervisor=sup,
                                config=LifecycleConfig(**cfg))
    # bucketed warmups are the churn soak's subject; skip them here so
    # the tests pin handshake semantics without minutes of pre-compiles
    lc._warm_bucket = 1 << 30
    return lc, bridge, sup


def test_request_handshake_requires_a_dtls_table():
    lc, _bridge = _lc()                  # LcBridge has no _dtls
    with pytest.raises(RuntimeError, match="no DTLS association table"):
        lc.request_handshake(0x10)


def test_handshake_backpressure_is_typed_with_retry_hint():
    lc, bridge, sup = _dtls_lc(max_handshakes=2)
    try:
        assert lc.request_handshake(0x61) == (True, "queued", 0.0)
        assert lc.request_handshake(0x62)[0]
        assert lc.handshakes.depth == 2
        # the refusal originates in the supervisor's burn-aware
        # admission decision, typed like shard_burn/fast_burn
        assert sup.admission_decision(handshake_backlog=2,
                                      handshake_bound=2) \
            == (False, "handshake_backlog")
        assert sup.admission_decision(handshake_backlog=1,
                                      handshake_bound=2) == (True, "ok")
        ok, reason, retry = lc.request_handshake(0x63)
        assert (ok, reason) == (False, "handshake_backlog")
        assert reason in ADMIT_REASONS
        assert retry > 0.0 and retry == lc.handshakes.retry_after()
        # duplicate outranks backlog and carries no retry hint
        assert lc.request_handshake(0x61) == (False, "duplicate", 0.0)
        assert lc.admit_rejected \
            == {"handshake_backlog": 1, "duplicate": 1}
        ev = [e for e in _all_events(lc.flight)
              if e["kind"] == "handshake_reject"]
        assert [e["reason"] for e in ev] \
            == ["handshake_backlog", "duplicate"]
        assert ev[0]["retry_after_s"] == retry
        # a deeper backlog raises the hint: refused clients scale
        # their exponential backoff on it, spreading the retry wave
        lc.handshakes.table._inbox.extend(
            (b"", (9, 9)) for _ in range(3 * lc.cfg.handshake_batch))
        assert lc.handshakes.retry_after() > retry
    finally:
        bridge.close()


def test_handshake_keys_land_only_via_the_commit_barrier():
    """End-to-end against a real bridge: the tick thread only ENQUEUES
    handshake datagrams, every endpoint feed runs on the off-tick
    drain, completion stages the keys, and only the commit barrier
    flips the row live."""
    lc, bridge, _sup = _dtls_lc()
    eng = UdpEngine(port=0, max_batch=32)
    try:
        caddr = (0x7F000001, eng.port)          # 127.0.0.1 as uint32
        assert lc.request_handshake(0x60, remote_addr=caddr)[0]
        sid = next(s for s, v in bridge._ssrc_of.items() if v == 0x60)
        fp = bridge._dtls.pending[sid].local_fingerprint
        client = StubDtlsEndpoint("client", remote_fingerprint=fp)
        # in-tick ingest: enqueue only — zero endpoint feeds
        lc.tick_begin()
        for d in client.handshake_packets():
            bridge._dtls.on_dtls(d, caddr)
        lc.tick_end()
        assert bridge._dtls.feeds_total == 0
        assert lc.tick_thread_handshake_feeds == 0
        # off-tick drain passes until the server side completes; the
        # client's flights re-enter through the same enqueue-only path
        for _ in range(80):
            lc.handshakes.drain()
            if sid in bridge._staged:
                break
            back, _, _ = eng.recv_batch(timeout_ms=20)
            lc.tick_begin()
            for i in range(back.batch_size):
                for out in client.feed(back.to_bytes(i)):
                    bridge._dtls.on_dtls(out, caddr)
            lc.tick_end()
        # completed: STAGED with keys, not yet live, never inline
        assert sid in bridge._staged and sid in bridge._tx_keys
        assert sid not in bridge._dtls.pending
        assert lc.key_installs == 1 and lc.handshakes.completed == 1
        assert lc.admits == 0
        assert bridge._dtls.feeds_total > 0
        assert lc.tick_thread_handshake_feeds == 0
        assert lc.handshakes.off_tick_seconds > 0.0
        # the commit barrier flips it live
        lc.commit()
        assert sid not in bridge._staged and lc.admits == 1
        kinds = [e["kind"] for e in _all_events(lc.flight)]
        assert kinds.index("handshake_queued") \
            < kinds.index("handshake_complete") \
            < kinds.index("admit_commit")
        # the client side finishes off the DONE flight and both ends
        # export the same traffic keys (bridge tx == client's rx half)
        back, _, _ = eng.recv_batch(timeout_ms=100)
        for i in range(back.batch_size):
            client.feed(back.to_bytes(i))
        assert client.complete
        _prof, _ck, _cs, sk, ss = client.srtp_keys()
        assert bridge._tx_keys[sid] == (sk, ss)
    finally:
        eng.close()
        bridge.close()


# --------------------------------------------------- reconciliation

def test_reconcile_completes_surviving_staged_and_rolls_back_rest():
    lc, bridge = _lc()
    # survivor: keys + ssrc mapping rode the bridge snapshot
    bridge._ssrc_of[3] = 0xA1
    bridge._tx_keys[3] = _keys(4)
    bridge._free.remove(3)
    # half state: row mapped but its keys did NOT survive
    bridge._ssrc_of[5] = 0xA2
    bridge._free.remove(5)
    lc._reconcile({
        "staged": [(3, 0xA1), (5, 0xA2), (6, 0xA3)],
        "queued": [(0xB1, _keys(2), _keys(4), None)],
    })
    # survivor completed
    assert lc.admits == 1
    assert any(e["kind"] == "admit_commit" and e.get("recovered")
               for e in _all_events(lc.flight))
    # half-installed row rolled back — removed, slot freed
    assert ("remove", (5,)) in bridge.calls
    assert 5 not in bridge._ssrc_of and 5 in bridge._free
    # fully-absent row: rollback recorded, nothing to remove
    rb = [e for e in _all_events(lc.flight)
          if e["kind"] == "admit_rollback"]
    assert sorted(e["sid"] for e in rb) == [5, 6]
    # queued join re-entered the normal pipeline
    assert lc.key_installs_pending == 1
    lc.run_between_ticks()
    lc.run_between_ticks()
    assert 0xB1 in bridge._ssrc_of.values() and lc.admits == 2
    # invariant: nothing is left half-installed
    for sid in (3, 5, 6):
        assert (sid in bridge._ssrc_of) == (sid in bridge._tx_keys)


def test_constructor_consumes_supervisor_pending_lifecycle():
    sup = BridgeSupervisor(DummyBridge(sids=()),
                           SupervisorConfig(deadline_ms=10.0))
    sup.pending_lifecycle = {
        "staged": [], "queued": [(0xC1, _keys(2), _keys(4), None)]}
    bridge = LcBridge()
    lc = StreamLifecycleManager(bridge, supervisor=sup)
    assert sup.lifecycle is lc and sup.pending_lifecycle is None
    assert lc.key_installs_pending == 1


# --------------------------------------------------------- slow twin

@pytest.mark.slow
def test_churn_soak_invariants():
    spec = importlib.util.spec_from_file_location("churn_soak", _SOAK)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    report = mod.run_soak(duration_s=2.0, ramp_s=1.0, join_rate_hz=60.0,
                          mean_hold_s=0.5, capacity=128, probes=2,
                          target_events_per_sec=100.0, seed=0,
                          verbose=False)
    failed = {k: v for k, v in report.items()
              if k.startswith("ok_") and not v}
    assert not failed, (failed, report)
    assert report["window_recompiles"] == 0
    assert report["window_admits"] > 0 and report["window_evicts"] > 0


@pytest.mark.slow
def test_broadcast_churn_soak_invariants():
    """Small-config twin of `churn_soak.py --broadcast`: Poisson
    listener churn plus periodic speaker flips on a broadcast
    conference must hold zero data-path recompiles in the steady
    window, refuse no listener, keep the fanout-only mask in lockstep
    with the live listener set, and bound listener-join p99."""
    spec = importlib.util.spec_from_file_location("churn_soak", _SOAK)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    report = mod.run_broadcast_soak(
        duration_s=3.0, ramp_s=2.0, n_speakers=4, n_listeners=192,
        mean_hold_s=2.0, n_shards=8, capacity=512,
        flip_every_ticks=50, seed=0, verbose=False)
    failed = {k: v for k, v in report.items()
              if k.startswith("ok_") and not v}
    assert not failed, (failed, report)
    assert report["window_recompiles"] == 0
    assert report["speaker_flips"] > 0
    assert report["join_p99_s"] > 0.0


@pytest.mark.slow
def test_reconnect_soak_invariants():
    """Small-config twin of `churn_soak.py --reconnect --smoke`: a
    mass simultaneous-reconnect storm with a mid-storm kill/recover
    must restore media for every client within the p99 bound, keep
    every handshake feed off the tick thread, refuse only with typed
    `handshake_backlog` (retry-after honored), land keys exclusively
    through the staged commit barrier, and reconcile every
    association after recovery — completed, rolled back, or requeued,
    never torn."""
    spec = importlib.util.spec_from_file_location("churn_soak", _SOAK)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    report = mod.run_reconnect_soak(
        n_clients=24, max_handshakes=6, handshake_batch=8,
        capacity=128, storm_budget_s=60.0, restore_p99_bound_s=10.0,
        seed=0, verbose=False)
    failed = {k: v for k, v in report.items()
              if k.startswith("ok_") and not v}
    assert not failed, (failed, report)
    assert report["window_recompiles"] == 0
    assert report["torn_rows"] == []
    assert report["handshakes_completed"] == report["key_installs_staged"]
    assert report["refusals"].get("handshake_backlog", 0) > 0


@pytest.mark.slow
def test_cascade_soak_invariants():
    """Small-config twin of `churn_soak.py --cascade --smoke`: a
    two-bridge cascade carrying the speaker bus over the trunk, bridge
    A killed mid-call — the survivor must detect the failover, adopt
    the evicted orphan through the commit barrier, restore media within
    the p99 bound with zero data-path recompiles, refuse only with
    typed `trunk_down` (retry-after honored), and reconcile every row
    — committed-with-keys or staged, never torn."""
    spec = importlib.util.spec_from_file_location("churn_soak", _SOAK)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    report = mod.run_cascade_soak(
        n_senders=3, n_receivers=2, pre_rounds=10, post_rounds=60,
        restore_p99_bound_s=2.0, seed=0, verbose=False)
    failed = {k: v for k, v in report.items()
              if k.startswith("ok_") and not v}
    assert not failed, (failed, report)
    assert report["window_recompiles"] == 0
    assert report["torn_rows"] == []
    assert report["failovers"] == 1
    assert report["orphans_adopted"] >= 1
    assert report["refusals"].get("trunk_down", 0) > 0
    assert report["conf_bridge_home"] == 1


# ------------------------------------- admission grows with the table

def _admitted(install_batch: int, n: int = 96, mesh=None):
    """An SfuBridge with `n` endpoints in conferences of 8 admitted
    through `request_join` in waves of `install_batch` (ladder off:
    admission is host work), placement over four shards."""
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    kwargs = {} if mesh is None else {"mesh": mesh}
    bridge = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                       capacity=128, recv_window_ms=0, **kwargs)
    sup = BridgeSupervisor(bridge, SupervisorConfig(deadline_ms=60_000.0))
    lc = StreamLifecycleManager(
        bridge, supervisor=sup,
        config=LifecycleConfig(install_batch=install_batch,
                               max_pending=1024))
    lc._warm_bucket = 1 << 30
    lc.enable_placement(4)
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 256, (n, 2, 30), dtype=np.uint8)
    for i in range(n):
        k = [(bytes(keys[i, j, :16]), bytes(keys[i, j, 16:]))
             for j in (0, 1)]
        ok, why = lc.request_join(0x7000 + i, k[0], k[1],
                                  conference=i // 8)
        assert ok, why
    connects, inner = [0], bridge.translator.connect

    def connect(sid, rr):
        connects[0] += 1
        inner(sid, rr)

    bridge.translator.connect = connect
    waves = 0
    while lc.admits < n:
        sup.tick(now=1000.0 + 0.02 * sup.ticks)
        waves += 1
        if mesh is not None:
            # what a launch does first: a wave's re-keying dropped the
            # placed copies, however many times it set `_dev = None`
            bridge.rx_table._sharded_device("rtp")
            bridge.translator._sharded_device()
        assert waves < 4 * n
    # a wave is staged in one tick and goes live in the next
    return bridge, lc, waves - 1, connects[0]


def _state(bridge, lc):
    rx, tx, tr = bridge.rx_table, bridge.tx_table, bridge.translator
    return {
        "rx": (rx._rk_rtp, rx._mid_rtp, rx._salt_rtp, rx._rk_rtcp,
               rx._mid_rtcp, rx._salt_rtcp, rx.active),
        "tx": (tx._rk_rtp, tx._mid_rtp, tx._salt_rtp, tx._rk_rtcp,
               tx._mid_rtcp, tx._salt_rtcp, tx.active),
        "legs": (tr._rk, tr._mid, tr._salt, tr.active),
        "ssrc_of": dict(bridge._ssrc_of),
        "conf_of": dict(bridge._conf_of),
        "routes": {s: rr.tolist() for s, rr in tr._routes.items()},
        "placement": {c: lc.placer.shard_of(c) for c in range(12)},
    }


@pytest.mark.parametrize("install_batch", [64, 512])
def test_waves_leave_what_single_joins_leave(install_batch):
    """Admission in waves of 64 or of 512 leaves byte-equal key tables,
    leg tables, SSRC map, routes and placement to one join a wave; and
    a commit reconnects the conferences it touched, not every live
    sender (the route rebuilds of a bridge grow with the bridge, not
    with its square)."""
    b1, lc1, waves1, connects1 = _admitted(1)
    want = _state(b1, lc1)
    b1.close()
    bw, lcw, waves, connects = _admitted(install_batch)
    got = _state(bw, lcw)
    bw.close()
    assert waves1 == 96 and waves == -(-96 // install_batch)
    for name in ("rx", "tx", "legs"):
        for a, b in zip(want[name], got[name]):
            np.testing.assert_array_equal(a, b)
    for name in ("ssrc_of", "conf_of", "routes", "placement"):
        assert want[name] == got[name], name
    assert len(got["routes"]) == 96
    assert all(len(rr) == 7 for rr in got["routes"].values())
    # a wave reconnects at most the conferences it touches: one join a
    # wave reconnects its conference's members so far (1 + ... + 8 a
    # conference), a wave of whole conferences each sender once
    assert connects1 == 12 * 36
    assert connects == 96


def test_a_sharded_table_is_placed_at_most_once_a_wave():
    import jax

    from libjitsi_tpu.mesh import make_media_mesh

    mesh = make_media_mesh(jax.devices()[:4])
    bridge, lc, waves, _connects = _admitted(32, mesh=mesh)
    assert waves == 3
    assert bridge.rx_table.placements == waves
    assert bridge.translator.placements == waves
    # no conference straddles a shard
    for conf in range(12):
        rows = [s for s, c in bridge._conf_of.items() if c == conf]
        assert len({s // 32 for s in rows}) == 1 and len(rows) == 8
    bridge.close()
