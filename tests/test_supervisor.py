"""BridgeSupervisor unit tests: watchdog state machine, the overload
escalation ladder (recv window -> degraded -> shedding) and its
recovery, sliding-window quarantine with exponential-backoff
re-admission, checkpoint file versioning, and the health primitives.

All against a dummy bridge — no sockets, no device; the e2e proofs
live in tests/test_chaos_recovery.py.
"""

import types

import numpy as np
import pytest

from libjitsi_tpu.service.supervisor import (BridgeSupervisor, CKPT_MAGIC,
                                             CKPT_VERSION, SupervisorConfig)
from libjitsi_tpu.utils.health import (ExponentialBackoff, HEALTHY,
                                       OVERLOADED, STALLED,
                                       SlidingWindowCounter, Watchdog,
                                       retrying)
from libjitsi_tpu.utils.metrics import MetricsRegistry

CAP = 8


class DummyLoop:
    def __init__(self):
        self.registry = types.SimpleNamespace(capacity=CAP)
        self.recv_window_ms = 1
        self.inbound_drop = np.zeros(CAP, dtype=bool)
        self.inbound_dropped = np.zeros(CAP, dtype=np.int64)
        self.inbound_dropped_total = 0


class DummyBridge:
    def __init__(self):
        self.loop = DummyLoop()
        self.degraded = False
        self._ssrc_of = {0: 100, 1: 101, 2: 102, 3: 103}
        self.rx_table = types.SimpleNamespace(
            auth_fail=np.zeros(CAP, dtype=np.int64),
            replay_reject=np.zeros(CAP, dtype=np.int64))
        self.speaker = types.SimpleNamespace(dominant=0)
        self.ticked = 0

    def tick(self, now=None):
        self.ticked += 1
        return {"rx": 0}


class FakeClock:
    """Scripted tick durations: each supervisor tick reads the clock
    twice (t0/t1); the second read advances by the next duration."""

    def __init__(self, durations):
        self.durations = list(durations)
        self.t = 0.0
        self.half = False

    def __call__(self):
        if self.half:
            self.t += self.durations.pop(0) if self.durations else 0.0
        self.half = not self.half
        return self.t


def _sup(durations, **cfg_kwargs):
    cfg = SupervisorConfig(deadline_ms=10.0, **cfg_kwargs)
    bridge = DummyBridge()
    return BridgeSupervisor(bridge, cfg,
                            clock=FakeClock(durations)), bridge


# ------------------------------------------------------------- watchdog

def test_watchdog_states_and_counters():
    wd = Watchdog(0.010, overload_after=3, stall_after=5)
    assert wd.state == HEALTHY
    for _ in range(2):
        assert wd.observe(0.020)
    assert wd.state == HEALTHY and wd.consecutive == 2
    wd.observe(0.020)
    assert wd.state == OVERLOADED
    for _ in range(2):
        wd.observe(0.020)
    assert wd.state == STALLED and wd.overruns == 5
    assert not wd.observe(0.001)          # one good tick clears the run
    assert wd.state == HEALTHY and wd.consecutive == 0
    assert wd.max_consecutive == 5 and wd.worst_s == 0.020


def test_supervisor_passes_result_through_and_counts():
    sup, bridge = _sup([0.001] * 3)
    assert sup.tick() == {"rx": 0}
    sup.tick(now=1.0)
    assert bridge.ticked == 2 and sup.ticks == 2
    assert sup.health()["state"] == HEALTHY


# -------------------------------------------------------------- ladder

def test_escalation_ladder_then_full_recovery():
    # 12 overrun ticks (escalate every 2), then 30 good ones
    sup, bridge = _sup([0.05] * 12 + [0.001] * 30,
                       overload_after=2, overload_exit=3, shed_step=2)
    for _ in range(12):
        sup.tick()
    # rung 1: batching window zeroed; rung 2: degraded; rung 3+: shed
    assert bridge.loop.recv_window_ms == 0
    assert bridge.degraded
    assert sup.level >= 3 and len(sup._shed) > 0
    assert bridge.loop.inbound_drop[sorted(sup._shed_set)].all()
    # dominant speaker (sid 0) is never shed
    assert 0 not in sup._shed_set
    # every shed produced a retrievable post-mortem naming its trigger
    pms = [p for p in sup.postmortems if p["trigger"] == "overload_shed"]
    assert {p["sid"] for p in pms} == set(sup._shed_set)
    for p in pms:
        assert p["event"]["kind"] == "shed"
        assert any(e["kind"] == "shed" and e["sid"] == p["sid"]
                   for e in p["dump"]["events"])
        # the global ring in the dump shows the ladder walking up
        assert any(e["kind"] == "ladder_escalate"
                   for e in p["dump"]["global"])
    for _ in range(30):
        sup.tick()
    assert sup.level == 0
    assert not bridge.degraded
    assert bridge.loop.recv_window_ms == 1          # restored
    assert not sup._shed and not bridge.loop.inbound_drop.any()
    # recovery left its own trail: de-escalations + per-sid restores
    glob = {e["kind"] for e in sup.flight.dump_all()["global"]}
    assert "ladder_deescalate" in glob
    for sid in {p["sid"] for p in pms}:
        kinds = [e["kind"] for e in sup.flight.dump(sid)["events"]]
        assert "shed_restore" in kinds


def _stage_sup(ledger, durations, with_recovery=True, slo=None,
               **cfg_kwargs):
    """Supervisor over a DummyBridge with a seeded stage ledger (the
    tracer stub returns the same per-stage seconds every tick) and an
    optional recovery stub that records shed_fec/throttle_rtx calls.
    The phase split is read off that ledger and the fake clock's tick
    (`durations`), by the rule in utils/tracing.py."""
    cfg = SupervisorConfig(deadline_ms=10.0, overload_after=1,
                           **cfg_kwargs)
    bridge = DummyBridge()
    bridge.loop.tracer = types.SimpleNamespace(
        take_ledger=lambda: dict(ledger))
    calls = []
    if with_recovery:
        bridge.recovery = types.SimpleNamespace(
            shed_fec=lambda on: calls.append(("shed_fec", on)),
            throttle_rtx=lambda on: calls.append(("throttle_rtx", on)))
    sup = BridgeSupervisor(bridge, cfg, clock=FakeClock(durations),
                           slo=slo)
    return sup, bridge, calls


def _escalations(sup):
    return [e for e in sup.flight.dump_all()["global"]
            if e["kind"] == "ladder_escalate"]


def test_stage_skew_forward_chain_sheds_fec_before_recv_window():
    """forward_chain owning the tick budget must pick shed_fec FIRST —
    not the wall-time ladder's recv_window rung."""
    ledger = {"ingress": 0.0004, "forward_chain": 0.009,
              "egress": 0.0006}
    sup, bridge, calls = _stage_sup(ledger, [0.05])
    sup.tick()
    (ev,) = _escalations(sup)
    assert ev["rung"] == "shed_fec"
    assert ev["stage"] == "forward_chain"
    assert ev["stage_share"] == pytest.approx(0.9, abs=0.01)
    assert ev["slo_state"] == "none"
    assert calls == [("shed_fec", True)]
    # the wall-ladder rungs stayed untouched
    assert bridge.loop.recv_window_ms == 1 and not bridge.degraded


def test_sfu_overrun_in_forward_chain_escalates_on_self_time(
        sfu_with_traffic, monkeypatch):
    """On an SfuBridge `reverse_chain` CONTAINS the whole of
    `_on_media`, so by inclusive time it always dominates and its
    share's base counts nested stages twice.  The ladder judges by
    self time: a tick lost inside `forward_chain` says so, over the
    sum of self times, and takes the rung that acts on it."""
    import time

    sfu, sup, send = sfu_with_traffic
    send.until_forwarded()                  # shapes compiled, warm
    dispatch = sfu.translator.translate_async

    def slow(batch, index):
        time.sleep(1.0)                     # forward_chain's own time
        return dispatch(batch, index)

    monkeypatch.setattr(sfu.translator, "translate_async", slow)
    sup.cfg.overload_after = 1
    sup.watchdog.deadline_s = 0.5
    send.until_forwarded()
    (ev,) = _escalations(sup)
    led, self_led = sup.last_ledger, sup.last_self_ledger
    assert max(led, key=led.get) == "reverse_chain"     # as it was
    assert ev["stage"] == "forward_chain"
    assert ev["stage_s"] == pytest.approx(self_led["forward_chain"])
    assert ev["stage_share"] == pytest.approx(
        self_led["forward_chain"] / sum(self_led.values()), abs=1e-3)
    assert ev["stage_share"] > 0.6 and ev["rung"] == "shed_fec", self_led
    assert sup.health()["last_ledger"] == led


@pytest.mark.parametrize("slow_call, stage", [
    ("block_until_ready", "unprotect_block"), ("fetch", "unprotect_d2h")])
def test_sfu_overrun_inside_the_device_seam_escalates_as_it_did(
        sfu_with_traffic, monkeypatch, slow_call, stage):
    """The seam's spans are grandchildren of `reverse_chain`: a tick
    lost waiting for the unprotect names the leaf it was lost in
    (`unprotect_wait`, now a container, kept none of it), neither
    `forward_chain` nor `ingress` gains self time, and the ladder takes
    the rung it took when `unprotect_wait` was the leaf: the wall
    ladder's first."""
    import time

    from libjitsi_tpu.core import staging

    sfu, sup, send = sfu_with_traffic
    send.until_forwarded()                  # shapes compiled, warm
    real = getattr(staging.Launch, slow_call)
    slept = []

    def slow(self):
        # the unprotect's call (the tick's first such call collects
        # the fan-out the tick before dispatched)
        if not slept and sfu.loop.tracer._open.stage == stage:
            slept.append(1)
            time.sleep(1.0)
        return real(self)

    sup.cfg.overload_after = 1
    sup.watchdog.deadline_s = 0.5
    monkeypatch.setattr(staging.Launch, slow_call, slow)
    send.until_forwarded()
    (ev,) = _escalations(sup)
    led, self_led = sup.last_ledger, sup.last_self_ledger
    assert ev["stage"] == stage and ev["stage_s"] >= 1.0
    assert ev["stage_s"] == pytest.approx(self_led[stage])
    assert ev["rung"] == sup.LADDER[0] == "recv_window"
    assert led["unprotect_wait"] >= led[stage] >= 1.0
    assert self_led["unprotect_wait"] < 0.05
    assert self_led["forward_chain"] < 0.05 and self_led["ingress"] < 0.05


def test_stage_skew_ingress_shrinks_recv_window_and_unwinds_lifo():
    ledger = {"ingress": 0.008, "forward_chain": 0.001,
              "egress": 0.001}
    sup, bridge, calls = _stage_sup(
        ledger, [0.05, 0.05] + [0.001] * 10, overload_exit=2)
    sup.tick()
    (ev, ) = _escalations(sup)
    assert ev["rung"] == "recv_window" and ev["stage"] == "ingress"
    assert bridge.loop.recv_window_ms == 0
    # second escalation: ingress rung already held -> wall ladder next
    sup.tick()
    assert _escalations(sup)[-1]["rung"] == "degrade"
    assert bridge.degraded and not calls
    # recovery unwinds LIFO: degrade first, then the window restores
    for _ in range(2):
        sup.tick()
    assert not bridge.degraded and bridge.loop.recv_window_ms == 0
    for _ in range(2):
        sup.tick()
    assert bridge.loop.recv_window_ms == 1
    assert sup.level == 0


def test_stage_skew_below_threshold_falls_back_to_wall_ladder():
    """A balanced ledger (no stage >= stage_share_threshold) must walk
    the PR-2 wall-time order even when forward_chain is nominally the
    dominant stage."""
    ledger = {"ingress": 0.003, "forward_chain": 0.004,
              "egress": 0.003}
    sup, bridge, calls = _stage_sup(ledger, [0.05, 0.05])
    sup.tick()
    sup.tick()
    rungs = [e["rung"] for e in _escalations(sup)]
    assert rungs == ["recv_window", "degrade"]
    assert not calls


def test_stage_skew_without_recovery_skips_fec_rung():
    ledger = {"forward_chain": 0.009, "ingress": 0.001}
    sup, bridge, calls = _stage_sup(ledger, [0.05],
                                    with_recovery=False)
    sup.tick()
    (ev,) = _escalations(sup)
    assert ev["rung"] == "recv_window"       # no controller to act on
    assert not calls


def test_escalation_event_carries_live_slo_state():
    slo = types.SimpleNamespace(state=lambda *a: "fast_burn",
                                on_tick=lambda: None)
    ledger = {"forward_chain": 0.009, "ingress": 0.001}
    sup, _bridge, _calls = _stage_sup(ledger, [0.05], slo=slo)
    sup.tick()
    (ev,) = _escalations(sup)
    assert ev["slo_state"] == "fast_burn"
    assert sup.health()["slo_state"] == "fast_burn"


def test_escalation_names_host_phase_when_host_bound():
    """A host-dominant phase split must reach the ladder_escalate
    event: the page says "host-bound, host_python owns the tick", not
    just which pipeline stage overran."""
    ledger = {"ingress": 0.001, "forward_chain": 0.0135,
              "fanout_dispatch": 0.002, "fanout_put": 0.0005,
              "unprotect_block": 0.001}
    phases = {"host_python": 0.0155, "dispatch": 0.002,
              "h2d_transfer": 0.0005, "device_compute": 0.001,
              "d2h_transfer": 0.0, "idle": 0.001}
    sup, _bridge, _calls = _stage_sup(ledger, [0.02])
    sup.tick()
    (ev,) = _escalations(sup)
    assert ev["phase"] == "host_python"
    assert ev["bound"] == "host"
    assert ev["phase_share"] == pytest.approx(0.775, abs=0.001)
    attr = sup.phase_attribution()
    assert attr["bound"] == "host"
    assert attr["phase"] == "host_python"
    assert attr["phases"] == pytest.approx(phases)
    assert sum(attr["phases"].values()) == pytest.approx(sup.last_tick_s)
    assert sup.health()["bound"] == "host"


def test_escalation_names_device_phase_when_device_bound():
    ledger = {"forward_chain": 0.001, "unprotect_dispatch": 0.001,
              "unprotect_block": 0.006, "fanout_wait": 0.009,
              "fanout_d2h": 0.002}
    sup, _bridge, _calls = _stage_sup(ledger, [0.02])
    sup.tick()
    (ev,) = _escalations(sup)
    assert ev["phase"] == "device_compute"
    assert ev["bound"] == "device"


def test_escalation_without_a_tracer_reports_unknown():
    """A bridge whose loop has no tracer has no ledger to read a split
    off — attribution degrades to unknown, never crashes."""
    sup, _bridge = _sup([0.05], overload_after=1)
    assert sup.tracer is None
    sup.tick()
    (ev,) = _escalations(sup)
    assert ev["phase"] == "unknown"
    assert ev["bound"] == "unknown"
    assert sup.phase_attribution()["phases"] == {}


def test_phase_split_is_of_the_tick_the_ladder_judges():
    """Every drain takes the split anew: an escalation is labelled
    with the phases of the tick that overran, not of an earlier one."""
    drains = [{"unprotect_block": 0.04, "ingress": 0.001},
              {"forward_chain": 0.04, "ingress": 0.001},
              {"ingress": 0.045}]
    sup, bridge, _calls = _stage_sup({}, [0.05] * 3)
    bridge.loop.tracer.take_ledger = lambda: drains.pop(0)
    bounds = []
    for _ in range(3):
        sup.tick()
        bounds.append(_escalations(sup)[-1]["bound"])
        assert sum(sup.last_phases.values()) == pytest.approx(0.05)
    assert bounds == ["device", "host", "idle"]
    assert sup.last_phases["idle"] == 0.045


def test_shed_is_deterministic_and_priority_ordered():
    cfg = SupervisorConfig(deadline_ms=10.0, overload_after=1,
                           shed_step=2)
    bridge = DummyBridge()
    sup = BridgeSupervisor(bridge, cfg, priorities={1: 5, 2: 0, 3: 0},
                           clock=FakeClock([0.05] * 3))
    sup.tick()          # level 1
    sup.tick()          # level 2
    sup.tick()          # level 3: shed 2
    # lowest priority first, then highest sid: 3 then 2 (1 has prio 5,
    # 0 is the dominant speaker)
    assert sup._shed == [3, 2]


# ---------------------------------------------------------- quarantine

def test_quarantine_convicts_releases_and_backs_off():
    cfg = SupervisorConfig(deadline_ms=1000.0, quarantine_window=5,
                           quarantine_auth_threshold=10,
                           quarantine_backoff_ticks=4,
                           quarantine_backoff_cap=8)
    bridge = DummyBridge()
    sup = BridgeSupervisor(bridge, cfg)
    for _ in range(3):
        bridge.rx_table.auth_fail[2] += 4
        sup.tick(now=0.0)
    assert 2 in sup._quarantined and bridge.loop.inbound_drop[2]
    assert sup.quarantine_total == 1
    # the conviction dumped a post-mortem whose ring shows the storm
    pm = next(p for p in sup.postmortems if p["trigger"] == "quarantine")
    assert pm["sid"] == 2 and pm["event"]["reason"] == "auth_storm"
    assert any(e["kind"] == "srtp_auth_fail"
               for e in pm["dump"]["events"])
    first_release = sup._quarantined[2]
    assert first_release - sup.ticks <= 4
    # other streams untouched
    assert not bridge.loop.inbound_drop[[0, 1, 3]].any()
    while sup.ticks < first_release:
        sup.tick(now=0.0)
    assert 2 not in sup._quarantined and not bridge.loop.inbound_drop[2]
    assert any(e["kind"] == "quarantine_release"
               for e in sup.flight.dump(2)["events"])
    # relapse: second conviction's ban is exponentially longer
    for _ in range(3):
        bridge.rx_table.auth_fail[2] += 4
        sup.tick(now=0.0)
    assert 2 in sup._quarantined
    assert sup._quarantined[2] - sup.ticks >= 7      # 4 * 2 (minus 1 tick)
    assert sup.quarantine_total == 2


def test_quarantine_threshold_is_windowed_not_lifetime():
    cfg = SupervisorConfig(deadline_ms=1000.0, quarantine_window=3,
                           quarantine_auth_threshold=10)
    bridge = DummyBridge()
    sup = BridgeSupervisor(bridge, cfg)
    # 2 failures/tick forever: lifetime total crosses 10 but any
    # 3-tick window holds only 6 — never quarantined
    for _ in range(20):
        bridge.rx_table.auth_fail[1] += 2
        sup.tick(now=0.0)
    assert 1 not in sup._quarantined


# ------------------------------------------------------------- metrics

def test_supervisor_metrics_render():
    reg = MetricsRegistry()
    cfg = SupervisorConfig(deadline_ms=10.0, overload_after=1,
                           quarantine_window=5,
                           quarantine_auth_threshold=5)
    bridge = DummyBridge()
    sup = BridgeSupervisor(bridge, cfg, metrics=reg,
                           clock=FakeClock([0.05] * 4))
    bridge.rx_table.auth_fail[3] += 6
    for _ in range(4):
        sup.tick()
    txt = reg.render()
    assert "# TYPE libjitsi_tpu_supervisor_ticks_overrun counter" in txt
    assert "libjitsi_tpu_supervisor_ticks_overrun 4" in txt
    assert "libjitsi_tpu_supervisor_watchdog_state 1" in txt
    assert "libjitsi_tpu_supervisor_streams_quarantined 1" in txt
    assert "libjitsi_tpu_supervisor_quarantine_total 1" in txt
    assert 'libjitsi_tpu_srtp_auth_fail{stream="3"} 6' in txt
    assert "# TYPE libjitsi_tpu_srtp_auth_fail counter" in txt


# ----------------------------------------------------------- checkpoint

def test_checkpoint_rejects_garbage_and_wrong_version(tmp_path):
    bad = tmp_path / "bad.ckpt"
    bad.write_bytes(b"not a checkpoint")
    with pytest.raises(Exception):
        BridgeSupervisor.load_checkpoint(str(bad))

    import pickle
    wrong = tmp_path / "wrong.ckpt"
    wrong.write_bytes(pickle.dumps({"magic": "other", "version": 1}))
    with pytest.raises(ValueError, match="not a libjitsi_tpu"):
        BridgeSupervisor.load_checkpoint(str(wrong))
    futur = tmp_path / "future.ckpt"
    futur.write_bytes(pickle.dumps(
        {"magic": CKPT_MAGIC, "version": CKPT_VERSION + 1}))
    with pytest.raises(ValueError, match="version"):
        BridgeSupervisor.load_checkpoint(str(futur))


def test_periodic_checkpoint_cadence(tmp_path):
    path = str(tmp_path / "bridge.ckpt")

    class SnapBridge(DummyBridge):
        def snapshot(self):
            return {"hello": 1}

    cfg = SupervisorConfig(deadline_ms=1000.0, checkpoint_every=3,
                           checkpoint_path=path)
    sup = BridgeSupervisor(SnapBridge(), cfg)
    for _ in range(7):
        sup.tick(now=0.0)
    assert sup.checkpoints_written == 2
    blob = BridgeSupervisor.load_checkpoint(path)
    assert blob["snap"] == {"hello": 1}
    assert blob["ticks"] == 6 and blob["bridge"] == "SnapBridge"


# ------------------------------------------------------ health helpers

def test_sliding_window_counter_expires_old_ticks():
    win = SlidingWindowCounter(4, window=3)
    win.push(np.array([5, 0, 0, 0]))
    win.push(np.array([0, 2, 0, 0]))
    assert list(win.sums()) == [5, 2, 0, 0]
    win.push(np.zeros(4, dtype=np.int64))
    win.push(np.zeros(4, dtype=np.int64))     # row with the 5 rotates out
    assert list(win.sums()) == [0, 2, 0, 0]
    win.reset_rows([1])
    assert list(win.sums()) == [0, 0, 0, 0]


def test_exponential_backoff_caps():
    bo = ExponentialBackoff(4, factor=2.0, cap=10)
    assert [bo.delay(a) for a in range(4)] == [4, 8, 10, 10]


def test_retrying_bounded_and_sleeps_backoff():
    slept = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError(98, "in use")
        return "bound"

    assert retrying(flaky, retries=5, backoff_s=0.01,
                    sleep=slept.append) == "bound"
    assert calls["n"] == 3 and slept == [0.01, 0.02]

    def always():
        raise OSError(98, "in use")

    with pytest.raises(OSError):
        retrying(always, retries=3, backoff_s=0.01, sleep=slept.append)
