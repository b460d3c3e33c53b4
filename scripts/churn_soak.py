#!/usr/bin/env python
"""Churn soak: continuous Poisson join/leave against the stream
lifecycle plane, with a capacity-per-chip report.

Drives an SfuBridge + BridgeSupervisor + StreamLifecycleManager with a
`ChurnModel` (Poisson joins, exponential holds, diurnal rate swing)
while persistent probe endpoints exchange talk-spurt-gated media over
loopback UDP under simulated downlink loss, recovering via NACK.  After
a ramp to steady state the measured window asserts the lifecycle
plane's acceptance invariants:

- ZERO compile events land inside tick windows (CompileCacheStats
  bracketing via lifecycle.tick_begin/tick_end) — admits/evicts ride
  pre-warmed bucket shapes;
- `table_protect` p99 against the LIVE churn-mutated table stays
  within `--p99-factor` (2x) of the pre-churn static-batch p99;
- residual media loss across the probes stays under `--residual-bound`
  (1%) with NACK recovery enabled;
- rejected admissions carry TYPED reasons in both the metrics scrape
  and the flight ring;
- sustained churn meets `--target-events` joins+leaves per second.

Usage:
    JAX_PLATFORMS=cpu python scripts/churn_soak.py            # full
    JAX_PLATFORMS=cpu python scripts/churn_soak.py --smoke    # tier-1

`--reconnect` runs the mass-reconnect storm chaos scenario instead;
`--broadcast` the top-K listener fan-out soak; `--cascade` the
two-bridge trunk failover chaos scenario (kill one bridge mid-call,
the conference survives on the other).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import libjitsi_tpu  # noqa: E402
from libjitsi_tpu.control.dtls import (  # noqa: E402
    HAVE_CRYPTOGRAPHY, DtlsSrtpEndpoint, StubDtlsEndpoint,
    generate_certificate)
from libjitsi_tpu.core.packet import PacketBatch  # noqa: E402
from libjitsi_tpu.io import UdpEngine  # noqa: E402
from libjitsi_tpu.mesh.cascade import (  # noqa: E402
    CascadeTrunk, TrunkConfig)
from libjitsi_tpu.rtp import header as rtp_header  # noqa: E402
from libjitsi_tpu.rtp import rtcp  # noqa: E402
from libjitsi_tpu.service.lifecycle import (  # noqa: E402
    ADMIT_REASONS, LifecycleConfig, StreamLifecycleManager)
from libjitsi_tpu.service.sfu_bridge import SfuBridge  # noqa: E402
from libjitsi_tpu.service.supervisor import (  # noqa: E402
    BridgeSupervisor, CascadeSupervisor, SupervisorConfig)
from libjitsi_tpu.transform.srtp import SrtpStreamTable  # noqa: E402
from libjitsi_tpu.utils.faults import (  # noqa: E402
    ChurnModel, DiurnalProfile, TalkSpurtModel)


def _keys(b: int, salt_len: int = 14):
    """Deterministic (master key, master salt) from one byte seed."""
    return (bytes([b & 0xFF]) * 16, bytes([(b + 1) & 0xFF]) * salt_len)


class _Probe:
    """Persistent endpoint measuring end-to-end loss under churn: sends
    talk-spurt media, drops `drop_rate` of its downlink before decrypt
    (wire-level loss — seq/ssrc are read from the clear header), NACKs
    the gaps, and accounts every (sender, seq) it eventually decrypts."""

    FIRST_SEQ = 1000

    def __init__(self, ssrc: int, bridge_port: int, n_probes: int,
                 seed: int, profile=None):
        self.ssrc = ssrc
        tkw = {} if profile is None else {"profile": profile}
        salt_len = 14 if profile is None else profile.policy.salt_len
        self.rx_key = _keys(ssrc & 0xFF, salt_len)
        self.tx_key = _keys((ssrc + 2) & 0xFF, salt_len)
        self.protect = SrtpStreamTable(capacity=1, **tkw)
        self.protect.add_stream(0, *self.rx_key)
        self.open = SrtpStreamTable(capacity=max(4, n_probes), **tkw)
        self.row_of = {}
        self.engine = UdpEngine(port=0, max_batch=256)
        self.bridge_port = bridge_port
        self.seq = self.FIRST_SEQ
        self.sid = None                    # filled once committed
        self.got = set()                   # (sender ssrc, seq)
        self.pending = {}                  # sender ssrc -> set(seq)
        self.scanned_to = {}               # sender ssrc -> seq
        self._head = {}                    # sender ssrc -> seq @ last round
        self.wire_drops = 0
        self.rng = np.random.default_rng(seed)

    def expect_sender(self, ssrc: int) -> None:
        row = len(self.row_of)
        self.row_of[ssrc] = row
        self.open.add_stream(row, *self.tx_key)
        self.pending[ssrc] = set()
        self.scanned_to[ssrc] = self.FIRST_SEQ

    def send_media(self, n: int = 2) -> None:
        pls = [b"\x5a" * 120] * n
        b = rtp_header.build(pls, [self.seq + i for i in range(n)],
                             [0] * n, [self.ssrc] * n, [96] * n,
                             stream=[0] * n)
        self.seq += n
        self.engine.send_batch(self.protect.protect_rtp(b),
                               "127.0.0.1", self.bridge_port)

    def drain(self, drop_rate: float = 0.0) -> None:
        back, _, _ = self.engine.recv_batch(timeout_ms=0)
        if back.batch_size == 0:
            return
        hdr = rtp_header.parse(back)
        drop = self.rng.random(back.batch_size) < drop_rate
        keep = []
        for i in range(back.batch_size):
            ssrc = int(hdr.ssrc[i])
            if ssrc not in self.row_of:
                continue                   # FEC / foreign stream
            if drop[i] and (ssrc, int(hdr.seq[i])) not in self.got:
                self.wire_drops += 1       # lost on the simulated wire
                continue
            keep.append(i)
        if not keep:
            return
        sub = PacketBatch(
            back.data[keep], np.asarray(back.length)[keep],
            np.asarray([self.row_of[int(hdr.ssrc[i])] for i in keep]))
        dec, ok = self.open.unprotect_rtp(sub)
        dhdr = rtp_header.parse(dec)
        for j in np.nonzero(np.asarray(ok))[0]:
            j = int(j)
            self.got.add((int(dhdr.ssrc[j]), int(dhdr.seq[j])))

    def nack_round(self, senders, max_seqs: int = 30) -> None:
        """Scan each sender's seq space for gaps, NACK the freshest.

        The horizon is the sender's head as of the PREVIOUS round —
        those packets have had a full round trip to arrive, so anything
        absent is a real gap.  (A fixed in-flight allowance freezes the
        horizon just below a pausing talker's final packets, and the
        bridge cache ages them out before the first NACK ever goes
        out.)"""
        for other in senders:
            if other is self:
                continue
            hi = self._head.get(other.ssrc, self.scanned_to[other.ssrc])
            self._head[other.ssrc] = other.seq
            pend = self.pending[other.ssrc]
            for s in range(self.scanned_to[other.ssrc], hi):
                if (other.ssrc, s) not in self.got:
                    pend.add(s)
            self.scanned_to[other.ssrc] = max(
                self.scanned_to[other.ssrc], hi)
            pend -= {s for s in pend if (other.ssrc, s) in self.got}
            if not pend:
                continue
            want = sorted(pend)[-max_seqs:]
            blob = rtcp.build_compound([rtcp.build_nack(rtcp.Nack(
                sender_ssrc=self.ssrc, media_ssrc=other.ssrc,
                lost_seqs=want))])
            wire = self.protect.protect_rtcp(
                PacketBatch.from_payloads([blob], stream=[0]))
            self.engine.send_batch(wire, "127.0.0.1", self.bridge_port)

    def close(self) -> None:
        self.engine.close()


def _timed_protect(table, sid: int, seq0: int, n: int = 64,
                   payload_len: int = 160) -> float:
    """One protect launch against the LIVE table (includes any pending
    copy-on-write / re-upload the churn left behind); returns seconds."""
    pls = [b"\x00" * payload_len] * n
    b = rtp_header.build(pls, [(seq0 + i) & 0xFFFF for i in range(n)],
                         [0] * n, [0x7E57] * n, [96] * n,
                         stream=[sid] * n)
    t0 = time.perf_counter()
    out = table.protect_rtp(b)
    np.asarray(out.data).ravel()[0]        # force materialization
    return time.perf_counter() - t0


def run_soak(duration_s: float = 30.0, ramp_s: float = 6.0,
             settle_s: float = 1.0, dt: float = 0.02,
             join_rate_hz: float = 300.0, mean_hold_s: float = 0.6,
             capacity: int = 1024, probes: int = 3,
             drop_rate: float = 0.05,
             target_events_per_sec: float = 500.0,
             residual_bound: float = 0.01,
             p99_factor_bound: float = 2.0, seed: int = 0,
             gcm: bool = False,
             verbose: bool = True, report_path=None) -> dict:
    """Run the soak; returns the report dict (every `ok_*` must hold).

    `gcm` swaps the whole wire onto AEAD_AES_128_GCM and enables the
    keystream pregeneration cache on both bridge tables — the same
    acceptance invariants then cover the cached crypto fast path (in
    particular ZERO data-path recompiles: fills and fused-hit kernels
    must ride the pre-warmed ladder, never compile inside a tick)."""
    import jax

    from libjitsi_tpu.transform.srtp.policy import SrtpProfile

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    profile = SrtpProfile.AEAD_AES_128_GCM if gcm else None
    salt_len = 14 if profile is None else profile.policy.salt_len
    bkw = {} if profile is None else {"profile": profile}
    cfg = libjitsi_tpu.configuration_service()
    bridge = SfuBridge(cfg, port=0, capacity=capacity, recv_window_ms=0,
                       **bkw)
    ks_caches = []
    if gcm:
        for t in (bridge.rx_table, bridge.tx_table):
            # single-chip tables only: the mesh subclasses override the
            # GCM seams and must never see a cache consult ahead of them
            if type(t) is SrtpStreamTable:
                ks_caches.append(t.enable_keystream_cache(window=256))
    reg = bridge.loop.metrics
    sup = BridgeSupervisor(
        bridge,
        SupervisorConfig(deadline_ms=1000.0,
                         quarantine_auth_threshold=1 << 30,
                         quarantine_replay_threshold=1 << 30),
        metrics=reg)
    lc = StreamLifecycleManager(bridge, supervisor=sup, metrics=reg)

    now = 100.0
    t0_wall = time.perf_counter()

    # ---- probes join through the lifecycle plane like anyone else
    plist = [_Probe(0x50 + 11 * k, bridge.port, probes, seed + 10 + k,
                    profile=profile)
             for k in range(probes)]
    for p in plist:
        accepted, why = lc.request_join(p.ssrc, p.rx_key, p.tx_key,
                                        name=f"probe-{p.ssrc:#x}")
        assert accepted, f"probe admission refused: {why}"
    while any(p.ssrc not in bridge._ssrc_of.values() for p in plist):
        sup.tick(now=now)
        now += dt
    sid_of = {s: v for v, s in
              ((sid, ssrc) for sid, ssrc in bridge._ssrc_of.items())}
    for p in plist:
        p.sid = sid_of[p.ssrc]
        for other in plist:
            if other is not p:
                p.expect_sender(other.ssrc)

    # ---- address-latch phase: fan-out toward a receiver is filtered
    # (and NOT cached for NACK) until that receiver's source address
    # latches on its first inbound packet, so the first few packets of
    # a brand-new pair are unrecoverable by design.  Every probe sends
    # until all addresses are live, then the per-sender accounting
    # floor is the seq AFTER latch — the soak measures churn loss, not
    # bring-up loss.
    for _ in range(6):
        for p in plist:
            p.send_media(1)
        sup.tick(now=now)
        bridge.flush_egress()    # the fan-out leaves on a worker
        now += dt
        for p in plist:
            p.drain(0.0)
    floor = {p.ssrc: p.seq for p in plist}
    for p in plist:
        for other in plist:
            if other is not p:
                p.scanned_to[other.ssrc] = floor[other.ssrc]

    # ---- static protect p99 baseline: same tick cadence, probe
    # traffic, wire drops and NACK rounds as the churn window — only
    # the population is frozen.  (A tight idle timing loop would
    # flatter the baseline: no interleaved tick work, perfectly warm
    # caches — and the 2x bound would then measure the cost of ticking,
    # not the cost of churn.)
    spurt = TalkSpurtModel(probes, seed=seed + 1)
    meas_sid = plist[0].sid
    meas_seq = 0
    for _ in range(5):                   # settle the protect path
        _timed_protect(bridge.tx_table, meas_sid, meas_seq)
        meas_seq += 64
    static_samples = []
    static_ticks = max(20, int(round(min(duration_s, 4.0) / dt)))
    for t in range(static_ticks):
        speaking = spurt.advance(dt)
        if t % 2 == 0:
            for i, p in enumerate(plist):
                if speaking[i]:
                    p.send_media(2)
        sup.tick(now=now)
        bridge.flush_egress()
        for p in plist:
            p.drain(drop_rate)
        if t % 2 == 1:
            for p in plist:
                p.nack_round(plist)
        if t % 2 == 0:
            static_samples.append(
                _timed_protect(bridge.tx_table, meas_sid, meas_seq))
            meas_seq += 64
        now += dt
    p99_static = float(np.percentile(static_samples, 99))

    # ---- churn drivers
    period = 8.0 * duration_s
    t_mid = now + ramp_s + duration_s / 2.0
    cm = ChurnModel(join_rate_hz, mean_hold_s, seed=seed,
                    diurnal=DiurnalProfile(period_s=period, depth=0.2,
                                           peak_t=t_mid + period / 2.0))
    drv = np.random.default_rng(seed + 2)
    next_ssrc = 0x10000
    alive: list = []                       # churned ssrcs not yet left
    churn_samples: list = []
    peak_pop = len(bridge._ssrc_of)

    ramp_ticks = int(round(ramp_s / dt))
    window_ticks = int(round(duration_s / dt))
    settle_ticks = int(round(settle_s / dt))
    w0 = {}                                # counters at window start
    for t in range(ramp_ticks + window_ticks + settle_ticks):
        in_window = ramp_ticks <= t < ramp_ticks + window_ticks
        in_settle = t >= ramp_ticks + window_ticks
        if t == ramp_ticks:
            w0 = dict(recompiles=lc.datapath_recompiles,
                      admits=lc.admits, evicts=lc.evicts,
                      joins=cm.joins_offered, leaves=cm.leaves_offered)
        speaking = spurt.advance(dt)
        if t % 2 == 0:
            for i, p in enumerate(plist):
                if speaking[i]:
                    p.send_media(2)
        if not in_settle:
            joins, leaves = cm.step(dt, now, len(alive))
            for _ in range(joins):
                ssrc = next_ssrc
                next_ssrc += 1
                ok_j, _why = lc.request_join(
                    ssrc, _keys(ssrc & 0xFF, salt_len),
                    _keys((ssrc + 2) & 0xFF, salt_len))
                if ok_j:
                    alive.append(ssrc)
            if leaves and alive:
                committed = set(bridge._ssrc_of.values())
                pool = [s for s in alive if s in committed]
                drv.shuffle(pool)
                for ssrc in pool[:leaves]:
                    lc.request_leave(ssrc=ssrc)
                    alive.remove(ssrc)
        sup.tick(now=now)
        bridge.flush_egress()
        for p in plist:
            p.drain(0.0 if in_settle else drop_rate)
        if t % 2 == 1:
            for p in plist:
                p.nack_round(plist)
        if in_window and t % 2 == 0:
            churn_samples.append(
                _timed_protect(bridge.tx_table, meas_sid, meas_seq))
            meas_seq += 64
        peak_pop = max(peak_pop, len(bridge._ssrc_of))
        now += dt

    # ---- force at least one typed rejection (a duplicate join)
    dup_ok, dup_reason = lc.request_join(plist[0].ssrc,
                                         plist[0].rx_key,
                                         plist[0].tx_key)
    assert not dup_ok and dup_reason == "duplicate", dup_reason

    # ---- accounting
    p99_churn = float(np.percentile(churn_samples, 99))
    expected = 0
    missing = 0
    missing_pairs = []
    for p in plist:
        for other in plist:
            if other is p:
                continue
            lo, hi = floor[other.ssrc], other.seq
            expected += hi - lo
            for s in range(lo, hi):
                if (other.ssrc, s) not in p.got:
                    missing += 1
                    missing_pairs.append(
                        (hex(p.ssrc), hex(other.ssrc), s, hi))
    residual = missing / expected if expected else 0.0

    window_admits = lc.admits - w0["admits"]
    window_evicts = lc.evicts - w0["evicts"]
    events_per_sec = (window_admits + window_evicts) / duration_s
    window_recompiles = lc.datapath_recompiles - w0["recompiles"]

    scrape = reg.render()
    flight_kinds = {e.get("kind")
                    for e in sup.flight.dump_all()["global"]}
    typed_in_scrape = "_admit_rejected{reason=" in scrape
    n_dev = jax.device_count()

    report = {
        "model_time_s": round(ramp_s + duration_s + settle_s, 3),
        "window_s": duration_s,
        "wall_s": round(time.perf_counter() - t0_wall, 3),
        "devices": n_dev,
        "capacity_rows": capacity,
        "peak_population": int(peak_pop),
        "peak_population_per_chip": round(peak_pop / n_dev, 1),
        "window_admits": window_admits,
        "window_evicts": window_evicts,
        "events_per_sec": round(events_per_sec, 1),
        "events_per_sec_per_chip": round(events_per_sec / n_dev, 1),
        "joins_offered": cm.joins_offered,
        "leaves_offered": cm.leaves_offered,
        "admit_rejected": dict(lc.admit_rejected),
        "key_installs": lc.key_installs,
        "warm_bucket": lc._warm_bucket,
        "priming_recompiles": w0["recompiles"],
        "window_recompiles": window_recompiles,
        "protect_p99_static_ms": round(p99_static * 1e3, 3),
        "protect_p99_churn_ms": round(p99_churn * 1e3, 3),
        "probe_expected": expected,
        "probe_wire_drops": sum(p.wire_drops for p in plist),
        "probe_missing": missing,
        "probe_missing_pairs": missing_pairs[:8],
        "rtx_served": bridge.recovery.rtx_requests_served,
        "rtx_cache_miss": bridge.recovery.rtx_cache_miss,
        "retransmitted": bridge.retransmitted,
        "residual_loss_ratio": round(residual, 5),
        "profile": bridge.profile.name,
        "keystream_cache": (None if not ks_caches else {
            "hits": sum(c.hits for c in ks_caches),
            "misses": sum(c.misses for c in ks_caches),
            "evictions": sum(c.evictions for c in ks_caches),
            "filled_slots": sum(c.filled_slots for c in ks_caches),
            "fill_seconds": round(sum(c.fill_seconds
                                      for c in ks_caches), 4),
        }),
        # ---- invariants
        "ok_zero_datapath_recompiles": window_recompiles == 0,
        "ok_protect_p99_bounded":
            p99_churn <= p99_factor_bound * p99_static,
        "ok_residual_loss": residual <= residual_bound,
        "ok_churn_rate": events_per_sec >= target_events_per_sec,
        "ok_typed_reasons": (bool(lc.admit_rejected)
                             and typed_in_scrape
                             and "admit_reject" in flight_kinds),
        "ok_media_flowed": expected > 0 and len(plist[0].got) > 0,
    }
    for p in plist:
        p.close()
    bridge.close()
    libjitsi_tpu.stop()
    if verbose:
        print("---- churn soak report ----")
        for k, v in report.items():
            print(f"{k:32s} {v}")
    if report_path:
        with open(report_path, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
    return report


def run_broadcast_soak(duration_s: float = 20.0, ramp_s: float = 8.0,
                       dt: float = 0.02, n_speakers: int = 8,
                       n_listeners: int = 4096,
                       join_rate_hz=None, mean_hold_s: float = 10.0,
                       n_shards: int = 8, capacity=None,
                       flip_every_ticks: int = 200,
                       join_p99_bound_s: float = 0.25, seed: int = 0,
                       verbose: bool = True, report_path=None) -> dict:
    """Broadcast-conference churn soak: one declared broadcast
    conference (`n_speakers` on the home shard, fanout-only listeners
    straddling all shards) under Poisson listener join/leave at the
    conference's steady population, with periodic speaker
    promote/demote flips riding the same commit barrier.  Asserts:

    - ZERO compile events inside tick windows once the ramp is over —
      listener churn rides the fanout-only warmup ladder and role
      flips ride pre-warmed shapes;
    - listener-join p99 (request_join -> committed live, model time)
      stays under `join_p99_bound_s` — the off-tick install pipeline
      keeps up with broadcast-scale churn;
    - the conference's `bcast_listener_join` slice stays healthy (no
      refused listener joins at steady state) and the loop's
      fanout-only mask tracks the live listener set exactly.

    No probe media rides this soak — end-to-end loss under churn is
    the plain soak's job; this one isolates the lifecycle plane at
    broadcast scale."""
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    if capacity is None:
        capacity = max(512, 2 * n_listeners)
    if capacity % n_shards:
        capacity += n_shards - capacity % n_shards
    if join_rate_hz is None:
        # stationary population: joins/s x mean hold = listener count
        join_rate_hz = n_listeners / mean_hold_s
    cfg = libjitsi_tpu.configuration_service()
    bridge = SfuBridge(cfg, port=0, capacity=capacity, recv_window_ms=0)
    reg = bridge.loop.metrics
    sup = BridgeSupervisor(
        bridge, SupervisorConfig(deadline_ms=1000.0), metrics=reg)
    lc = StreamLifecycleManager(bridge, supervisor=sup, metrics=reg)
    lc.enable_placement(n_shards)
    conf = 1
    lc.declare_broadcast(conf)
    now = 100.0
    t0_wall = time.perf_counter()

    for k in range(n_speakers):
        ok, why = lc.request_join(0x100 + k, _keys(k),
                                  _keys(k + 2), conference=conf,
                                  role="speaker")
        assert ok, f"speaker admission refused: {why}"
    while lc.admits < n_speakers:
        sup.tick(now=now)
        now += dt

    cm = ChurnModel(join_rate_hz, mean_hold_s, seed=seed)
    drv = np.random.default_rng(seed + 2)
    next_ssrc = 0x10000
    alive: list = []
    waiting: dict = {}                  # ssrc -> request model-time
    latencies: list = []
    flips = 0

    def _join_listener(ssrc):
        nonlocal next_ssrc
        ok_j, _why = lc.request_join(
            ssrc, _keys(ssrc & 0xFF), _keys((ssrc + 2) & 0xFF),
            conference=conf)
        if ok_j:
            alive.append(ssrc)
            waiting[ssrc] = now
        return ok_j

    ramp_ticks = int(round(ramp_s / dt))
    window_ticks = int(round(duration_s / dt))
    w0 = {}
    for t in range(ramp_ticks + window_ticks):
        in_window = t >= ramp_ticks
        if t == ramp_ticks:
            w0 = dict(recompiles=lc.datapath_recompiles,
                      admits=lc.admits, evicts=lc.evicts,
                      join_bad=lc._bcast[conf]["join_bad"])
        if not in_window and len(alive) < n_listeners:
            # ramp: fill toward the target population, batch-paced so
            # the queue never trips the backlog bar
            room = lc.cfg.max_pending - lc.key_installs_pending - 1
            for _ in range(min(room, lc.cfg.install_batch,
                               n_listeners - len(alive))):
                _join_listener(next_ssrc)
                next_ssrc += 1
        if in_window:
            joins, leaves = cm.step(dt, now, len(alive))
            for _ in range(joins):
                _join_listener(next_ssrc)
                next_ssrc += 1
            if leaves and alive:
                committed = set(bridge._ssrc_of.values())
                pool = [s for s in alive if s in committed]
                drv.shuffle(pool)
                for ssrc in pool[:leaves]:
                    lc.request_leave(ssrc=ssrc)
                    alive.remove(ssrc)
                    waiting.pop(ssrc, None)
            if flip_every_ticks and t % flip_every_ticks == 0:
                # speaker churn rides the same barrier: promote a
                # random committed listener, demote a random speaker
                spk = sorted(lc._bcast[conf]["speakers"])
                lst = sorted(s for s in lc._listener_sids
                             if s in bridge._ssrc_of
                             and s not in bridge._staged)
                if spk and lst:
                    lc.promote_speaker(conf, lst[drv.integers(len(lst))])
                    lc.demote_speaker(conf, spk[drv.integers(len(spk))])
                    flips += 1
        sup.tick(now=now)
        if waiting:
            # committed means LIVE, not merely staged: a staged row
            # sits in _ssrc_of already but only flips at the barrier
            committed = {s for sid, s in bridge._ssrc_of.items()
                         if sid not in bridge._staged}
            for ssrc in [s for s in waiting if s in committed]:
                latencies.append(now - waiting.pop(ssrc))
        now += dt

    window_recompiles = lc.datapath_recompiles - w0["recompiles"]
    window_join_bad = lc._bcast[conf]["join_bad"] - w0["join_bad"]
    join_p99 = float(np.percentile(latencies, 99)) if latencies else 0.0
    live_listeners = sum(1 for s in lc._listener_sids
                         if s in bridge._ssrc_of
                         and s not in bridge._staged)
    mask_n = int(bridge.loop.fanout_only.sum())
    events = (lc.admits - w0["admits"]) + (lc.evicts - w0["evicts"])

    report = {
        "mode": "broadcast",
        "model_time_s": round(ramp_s + duration_s, 3),
        "window_s": duration_s,
        "wall_s": round(time.perf_counter() - t0_wall, 3),
        "capacity_rows": capacity,
        "n_shards": n_shards,
        "speakers": n_speakers,
        "listener_target": n_listeners,
        "listener_population": len(lc._listener_sids),
        "listener_shards": lc.placer.listener_shards(conf),
        "window_events": events,
        "events_per_sec": round(events / duration_s, 1),
        "window_join_refused": window_join_bad,
        "join_p99_s": round(join_p99, 4),
        "join_samples": len(latencies),
        "speaker_flips": flips,
        "speaker_promotions": lc.speaker_promotions,
        "speaker_demotions": lc.speaker_demotions,
        "priming_recompiles": w0["recompiles"],
        "window_recompiles": window_recompiles,
        "warm_bucket": lc._warm_bucket,
        "warm_listener_bucket": lc._warm_lbucket,
        "fanout_only_rows": mask_n,
        # ---- invariants
        "ok_zero_datapath_recompiles": window_recompiles == 0,
        "ok_join_p99": (len(latencies) > 0
                        and join_p99 <= join_p99_bound_s),
        "ok_no_refused_listeners": window_join_bad == 0,
        "ok_fanout_mask_tracks_listeners": mask_n == live_listeners,
        "ok_population": (len(lc._listener_sids)
                          >= 0.5 * n_listeners),
    }
    bridge.close()
    libjitsi_tpu.stop()
    if verbose:
        print("---- broadcast churn soak report ----")
        for k, v in report.items():
            print(f"{k:32s} {v}")
    if report_path:
        with open(report_path, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
    return report


class _ReconnectClient:
    """One reconnecting participant: a loopback UDP socket plus a real
    OpenSSL DTLS client endpoint.  The driver admits it through
    `request_handshake`, honors typed refusals by sleeping out the
    retry-after hint with exponential backoff, and counts it restored
    only when BOTH sides hold keys and the bridge row is committed
    live (not merely staged)."""

    def __init__(self, ssrc: int, bridge_port: int, ep_cls,
                 cert_der, key_der, seed: int):
        self.ssrc = ssrc
        self.engine = UdpEngine(port=0, max_batch=64)
        self.bridge_port = bridge_port
        self._ep_cls = ep_cls
        self._cert = (cert_der, key_der)
        self.ep = None
        self.state = "idle"            # idle -> pending -> live
        self.attempts = 0
        self.retry_at = 0.0
        self.requested_at = None       # first admission attempt
        self.refusals = 0
        self.rng = np.random.default_rng(seed)

    @property
    def addr(self):
        return (0x7F000001, self.engine.port)   # 127.0.0.1 as uint32

    def start_handshake(self) -> None:
        """(Re)start the client side from scratch and send the first
        flight — on admit, and again after a crash-recover when the
        server's in-flight association state died with the process."""
        self.ep = self._ep_cls("client", cert_der=self._cert[0],
                               key_der=self._cert[1])
        self._tx(self.ep.handshake_packets())

    def _tx(self, datagrams) -> None:
        if datagrams:
            self.engine.send_batch(PacketBatch.from_payloads(datagrams),
                                   "127.0.0.1", self.bridge_port)

    def pump(self) -> None:
        """Drain inbound server flights, advance the handshake, drive
        the RFC 6347 flight retransmission timer."""
        if self.ep is None or self.state != "pending":
            return
        back, _, _ = self.engine.recv_batch(timeout_ms=0)
        out = []
        for i in range(back.batch_size):
            if self.ep.complete:
                break
            out.extend(self.ep.feed(back.to_bytes(i)))
        if not self.ep.complete:
            out.extend(self.ep.tick())
        self._tx(out)

    def close(self) -> None:
        self.engine.close()


def _dtls_echo(sender, receiver, tick_fn, seq0: int,
               rounds: int = 16, need: int = 3) -> int:
    """SRTP media through the bridge between two DTLS-keyed clients,
    each side using only its own handshake-exported keys; returns how
    many of the sender's packets the receiver decrypted."""
    prof_s, stk, stsalt, _, _ = sender.ep.srtp_keys()
    tx = SrtpStreamTable(capacity=1, profile=prof_s)
    tx.add_stream(0, stk, stsalt)
    prof_r, _, _, rrk, rrsalt = receiver.ep.srtp_keys()
    rx = SrtpStreamTable(capacity=1, profile=prof_r)
    rx.add_stream(0, rrk, rrsalt)
    got, seq = 0, seq0
    for _ in range(rounds):
        pkt = rtp_header.build([b"\x5b" * 120] * 2, [seq, seq + 1],
                               [0, 0], [sender.ssrc] * 2, [96] * 2,
                               stream=[0, 0])
        seq += 2
        sender.engine.send_batch(tx.protect_rtp(pkt), "127.0.0.1",
                                 sender.bridge_port)
        tick_fn()
        back, _, _ = receiver.engine.recv_batch(timeout_ms=0)
        if back.batch_size == 0:
            continue
        hdr = rtp_header.parse(back)
        keep = [i for i in range(back.batch_size)
                if int(hdr.ssrc[i]) == sender.ssrc]
        if not keep:
            continue
        sub = PacketBatch(back.data[keep],
                          np.asarray(back.length)[keep],
                          np.asarray([0] * len(keep)))
        _dec, ok = rx.unprotect_rtp(sub)
        got += int(np.asarray(ok).sum())
        if got >= need:
            break
    return got


def _flight_kinds(flight) -> set:
    dump = flight.dump_all()
    kinds = {e.get("kind") for e in dump["global"]}
    for evs in dump["streams"].values():
        kinds |= {e.get("kind") for e in evs}
    return kinds


def run_reconnect_soak(n_clients: int = 1000, dt: float = 0.02,
                       max_handshakes: int = 128,
                       handshake_batch: int = 256,
                       kill_frac: float = 0.5,
                       restore_p99_bound_s: float = 10.0,
                       storm_budget_s: float = 120.0,
                       capacity=None, seed: int = 0,
                       verbose: bool = True, report_path=None) -> dict:
    """Mass-reconnect chaos scenario: `n_clients` real DTLS clients
    storm one bridge's handshake plane, the bridge is killed mid-storm
    and recovered from its checkpoint, and every association must come
    back — completed rows with working keys, staged rows committed or
    rolled back, in-flight rows requeued at their bound 5-tuple.
    Acceptance gates (every `ok_*` must hold):

    - time-to-media-restored p99 (recover -> committed live with both
      sides keyed, model time) under `restore_p99_bound_s`;
    - ZERO data-path recompiles inside tick windows after priming, on
      both the original and the recovered bridge;
    - ZERO handshake work attributed to the tick thread: every OpenSSL
      feed runs on the between-ticks drain (`phase_attribution` off-tick
      ledger + the lifecycle feed bracket both say so);
    - every refusal TYPED (`handshake_backlog` observed, with a
      retry-after hint clients honor via exponential backoff) and the
      total refusal count bounded — no refusal storms, no silent drops;
    - keys land ONLY via the staged commit barrier (stage counts match
      handshake completions exactly — the inline install path never
      runs)."""
    try:                               # one UDP socket per client
        import resource
        soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
        if soft < n_clients + 256:
            resource.setrlimit(resource.RLIMIT_NOFILE,
                               (min(hard, n_clients + 512), hard))
    except Exception:
        pass

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    if capacity is None:
        capacity = max(256, 2 * n_clients)
    cfg = libjitsi_tpu.configuration_service()
    bridge = SfuBridge(cfg, port=0, capacity=capacity, recv_window_ms=0)
    reg = bridge.loop.metrics
    sup = BridgeSupervisor(
        bridge,
        SupervisorConfig(deadline_ms=1000.0,
                         quarantine_auth_threshold=1 << 30,
                         quarantine_replay_threshold=1 << 30),
        metrics=reg)
    lcfg = LifecycleConfig(max_handshakes=max_handshakes,
                           handshake_batch=handshake_batch)
    lc = StreamLifecycleManager(bridge, supervisor=sup, metrics=reg,
                                config=lcfg)

    now = 100.0
    t0_wall = time.perf_counter()
    # real OpenSSL endpoints when `cryptography` is installed; the
    # same-surface stub otherwise (gated dependency — the plane's
    # datagram flows, admission and recovery logic are identical)
    if HAVE_CRYPTOGRAPHY:
        ep_cls = DtlsSrtpEndpoint
        cert_der, key_der, _fp = generate_certificate("reconnect-soak")
    else:
        ep_cls = StubDtlsEndpoint
        cert_der = key_der = None
    bridge._dtls.endpoint_factory = ep_cls
    clients = [_ReconnectClient(0x20000 + k, bridge.port, ep_cls,
                                cert_der, key_der, seed + 100 + k)
               for k in range(n_clients)]
    refused: dict = {}

    def _try_admit(c, lc_cur):
        if c.requested_at is None:
            c.requested_at = now
        ok, reason, retry = lc_cur.request_handshake(
            c.ssrc, remote_addr=c.addr, name=f"rc-{c.ssrc:#x}")
        if ok:
            c.state = "pending"
            c.attempts = 0
            c.start_handshake()
        else:
            c.refusals += 1
            refused[reason] = refused.get(reason, 0) + 1
            c.attempts += 1
            base = retry if retry > 0 else 0.05
            # exponential backoff on the server's retry-after hint,
            # jittered so the retry wave doesn't resynchronize into
            # the next storm front
            c.retry_at = now + base * (2 ** min(c.attempts - 1, 6)) \
                * (1.0 + 0.25 * float(c.rng.random()))
        return ok

    def _promote(b, cs, lat, base) -> None:
        committed = {ssrc: sid for sid, ssrc in b._ssrc_of.items()}
        for c in cs:
            if (c.state != "pending" or c.ep is None
                    or not c.ep.complete):
                continue
            sid = committed.get(c.ssrc)
            if (sid is not None and sid in b._tx_keys
                    and sid not in b._staged):
                c.state = "live"
                lat.append(now - (c.requested_at if base is None
                                  else base))

    # ---- priming: two clients handshake and exchange media BEFORE the
    # measured window, so first-media compiles land as priming, and the
    # final post-recover echo rides warm caches
    for c in clients[:2]:
        ok, why, _r = lc.request_handshake(
            c.ssrc, remote_addr=c.addr, name=f"rc-{c.ssrc:#x}")
        assert ok, f"priming admission refused: {why}"
        c.state = "pending"
        c.requested_at = now
        c.start_handshake()
    for _ in range(600):
        sup.tick(now=now)
        for c in clients[:2]:
            c.pump()
        _promote(bridge, clients[:2], [], None)
        now += dt
        if all(c.state == "live" for c in clients[:2]):
            break
    assert all(c.state == "live" for c in clients[:2]), \
        "priming handshakes stalled"

    def _tick1():
        nonlocal now
        sup.tick(now=now)
        bridge.flush_egress()
        now += dt

    prime_got = _dtls_echo(clients[0], clients[1], _tick1, seq0=3000)
    assert prime_got > 0, "priming media never flowed"
    w0 = dict(recompiles=lc.datapath_recompiles)

    # ---- the storm: everyone else reconnects at once
    storm_ticks = int(round(storm_budget_s / dt))
    kill_target = max(2, int(round(kill_frac * n_clients)))
    latencies_join: list = []
    peak_depth = 0
    for _ in range(storm_ticks):
        for c in clients:
            if c.state == "idle" and now >= c.retry_at:
                _try_admit(c, lc)
        sup.tick(now=now)
        for c in clients:
            c.pump()
        _promote(bridge, clients, latencies_join, None)
        peak_depth = max(peak_depth, lc.handshakes.depth)
        now += dt
        n_live = sum(1 for c in clients if c.state == "live")
        if n_live >= kill_target and lc.handshakes.depth > 0:
            break
    n_live_at_kill = sum(1 for c in clients if c.state == "live")
    assert lc.handshakes.depth > 0, \
        "storm drained before the kill point — raise n_clients"

    # ---- kill mid-storm, recover from the checkpoint
    ckpt = os.path.join(tempfile.gettempdir(),
                        f"reconnect_soak_{os.getpid()}.ckpt")
    sup.save_checkpoint(ckpt)
    pre = dict(feeds=bridge._dtls.feeds_total,
               retransmits=bridge._dtls.retransmits_total,
               inbox_dropped=bridge._dtls.inbox_dropped,
               completed=lc.handshakes.completed,
               key_installs=lc.key_installs,
               recompiles=lc.datapath_recompiles,
               tick_feeds=lc.tick_thread_handshake_feeds,
               off_tick_s=lc.handshakes.off_tick_seconds,
               pending=len(bridge._dtls.pending),
               inbox=len(bridge._dtls._inbox))
    scrape1 = reg.render()
    kinds = _flight_kinds(sup.flight)
    bridge.close()                                 # the crash

    sup2 = BridgeSupervisor.recover(cfg, ckpt, SfuBridge, port=0,
                                    supervisor_config=sup.cfg,
                                    recv_window_ms=0)
    bridge2 = sup2.bridge
    bridge2._dtls.endpoint_factory = ep_cls     # before reconcile requeues
    lc2 = StreamLifecycleManager(bridge2, supervisor=sup2,
                                 metrics=bridge2.loop.metrics,
                                 config=lcfg)
    recover_now = now
    latencies_restore: list = []
    requeued_ssrcs = {bridge2._ssrc_of[s] for s in bridge2._dtls.pending
                      if s in bridge2._ssrc_of}
    keyed_ssrcs = {v for s, v in bridge2._ssrc_of.items()
                   if s in bridge2._tx_keys}
    restored_instantly = 0
    for c in clients:
        c.bridge_port = bridge2.port
        if (c.state == "live" and c.ssrc in keyed_ssrcs
                and c.ep is not None and c.ep.complete):
            restored_instantly += 1       # keys rode the checkpoint
            latencies_restore.append(dt)
            continue
        if c.ssrc in requeued_ssrcs:
            # server row survived as a fresh pending association bound
            # to our 5-tuple: redo the client side against it
            c.state = "pending"
            c.start_handshake()
        elif c.ssrc in keyed_ssrcs:
            # server completed + keyed but WE never saw the final
            # flight: only signaling resolves this — leave + rejoin
            lc2.request_leave(ssrc=c.ssrc)
            c.state = "idle"
            c.ep = None
            c.attempts = 0
            c.retry_at = recover_now + 5 * dt
        else:
            # association didn't survive (requeue refused under
            # backlog, or never admitted): back to the admission queue
            c.state = "idle"
            c.ep = None
            c.attempts = 0
            c.retry_at = recover_now

    sup2.tick(now=now)            # commit the reconciled staged rows
    now += dt
    torn = [s for s in bridge2._ssrc_of
            if s not in bridge2._tx_keys
            and s not in bridge2._dtls.pending]

    # ---- drive the re-handshake wave until everyone is back
    for _ in range(storm_ticks):
        if all(c.state == "live" for c in clients):
            break
        for c in clients:
            if c.state == "idle" and now >= c.retry_at:
                _try_admit(c, lc2)
        sup2.tick(now=now)
        for c in clients:
            c.pump()
        _promote(bridge2, clients, latencies_restore, recover_now)
        peak_depth = max(peak_depth, lc2.handshakes.depth)
        now += dt

    def _tick2():
        nonlocal now
        sup2.tick(now=now)
        sup2.bridge.flush_egress()
        now += dt

    all_live = all(c.state == "live" for c in clients)
    echo_got = (_dtls_echo(clients[0], clients[1], _tick2, seq0=4000)
                if clients[0].state == clients[1].state == "live"
                else 0)

    # ---- accounting
    p99_restore = (float(np.percentile(latencies_restore, 99))
                   if latencies_restore else float("inf"))
    p99_join = (float(np.percentile(latencies_join, 99))
                if latencies_join else 0.0)
    window_recompiles = ((pre["recompiles"] - w0["recompiles"])
                         + lc2.datapath_recompiles)
    feeds_total = pre["feeds"] + bridge2._dtls.feeds_total
    tick_feeds = pre["tick_feeds"] + lc2.tick_thread_handshake_feeds
    off_tick_s = pre["off_tick_s"] + lc2.handshakes.off_tick_seconds
    completed = pre["completed"] + lc2.handshakes.completed
    key_installs = pre["key_installs"] + lc2.key_installs
    total_refusals = sum(c.refusals for c in clients)
    kinds |= _flight_kinds(sup2.flight)
    attr2 = sup2.phase_attribution().get("off_tick", {})

    report = {
        "mode": "reconnect",
        "endpoint_impl": ("openssl" if HAVE_CRYPTOGRAPHY else "stub"),
        "clients": n_clients,
        "max_handshakes": max_handshakes,
        "handshake_batch": handshake_batch,
        "capacity_rows": capacity,
        "wall_s": round(time.perf_counter() - t0_wall, 3),
        "model_time_s": round(now - 100.0, 3),
        "live_at_kill": n_live_at_kill,
        "pending_at_kill": pre["pending"],
        "inbox_at_kill": pre["inbox"],
        "requeued": lc2.handshakes.requeued,
        "restored_instantly": restored_instantly,
        "peak_queue_depth": peak_depth,
        "handshakes_completed": completed,
        "key_installs_staged": key_installs,
        "dtls_feeds_total": feeds_total,
        "dtls_retransmits_total": (pre["retransmits"]
                                   + bridge2._dtls.retransmits_total),
        "inbox_dropped": (pre["inbox_dropped"]
                          + bridge2._dtls.inbox_dropped),
        "refusals": dict(refused),
        "refusals_total": total_refusals,
        "join_p99_s": round(p99_join, 4),
        "restore_p99_s": round(p99_restore, 4),
        "restore_samples": len(latencies_restore),
        "priming_recompiles": w0["recompiles"],
        "window_recompiles": window_recompiles,
        "off_tick_drain_s": round(off_tick_s, 4),
        "off_tick_ledger": attr2,
        "torn_rows": torn,
        "echo_decrypted": echo_got,
        # ---- invariants
        "ok_all_restored": all_live,
        "ok_media_restored_p99": (all_live and len(latencies_restore) > 0
                                  and p99_restore <= restore_p99_bound_s),
        "ok_zero_datapath_recompiles": window_recompiles == 0,
        "ok_no_tick_thread_handshake": (
            tick_feeds == 0 and feeds_total > 0 and off_tick_s > 0
            and attr2.get("handshake_tick_thread_feeds", 1) == 0),
        "ok_typed_refusals": (
            refused.get("handshake_backlog", 0) > 0
            and set(refused) <= set(ADMIT_REASONS)
            and '_admit_rejected{reason="handshake_backlog"' in scrape1
            and "handshake_reject" in kinds
            and total_refusals <= n_clients * 40),
        "ok_commit_barrier_only": (key_installs == completed
                                   and completed >= n_clients
                                   and "handshake_complete" in kinds),
        "ok_reconciled": (not torn
                          and (pre["pending"] == 0
                               or "handshake_requeue" in kinds)),
        "ok_media_flowed": prime_got > 0 and echo_got > 0,
    }
    for c in clients:
        c.close()
    bridge2.close()
    libjitsi_tpu.stop()
    try:
        os.remove(ckpt)
    except OSError:
        pass
    if verbose:
        print("---- reconnect storm soak report ----")
        for k, v in report.items():
            print(f"{k:32s} {v}")
    if report_path:
        with open(report_path, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
    return report


def run_cascade_soak(dt: float = 0.01, n_senders: int = 3,
                     n_receivers: int = 2,
                     pre_rounds: int = 30, post_rounds: int = 150,
                     restore_p99_bound_s: float = 2.0,
                     hop_p99_bound_s: float = 1.0,
                     refusal_bound: int = 80, seed: int = 0,
                     verbose: bool = True, report_path=None) -> dict:
    """Bridge-cascade failover chaos: one conference spans two bridges
    over a `CascadeTrunk` (mesh/cascade.py), senders homed on bridge A,
    receivers on bridge B, the trunk carrying the top-K speaker bus.
    Bridge A is killed mid-call; the conference must survive on B.
    Acceptance gates (every `ok_*` must hold):

    - media flows sender -> A -> trunk -> B -> receiver before the
      kill, and the trunk payload is the SPEAKER BUS: a non-speaker's
      uplink never crosses the trunk;
    - heartbeat loss flips the trunk down, B promotes the orphaned
      conference and ADOPTS a roster member it no longer holds a row
      for (evicted mid-outage) through the normal commit barrier;
    - time-to-media-restored p99 (bridge-A kill -> speaker decrypted
      again on B, model time) under `restore_p99_bound_s`;
    - ZERO data-path recompiles inside tick windows after priming, on
      both bridges — failover rides warm shapes;
    - every refusal TYPED (`trunk_down` observed with a retry-after
      hint the joiner honors via exponential backoff) and bounded;
    - cross-hop journey tracing held (PR 19): the trunk trace
      extension produced hop-labeled `packet_journey_seconds`
      observations on B with a bounded p99, the rtt-corrected trunk
      one-way-delay estimate is live, and the trunk-down conviction
      captured a `trunk_failover` post-mortem naming the in-flight
      journey set;
    - full reconciliation, never torn: every row on the survivor is
      committed-with-keys or still staged/queued, the adoption queue
      drains, and the placer re-homes the conference on the survivor's
      bridge axis."""
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    cfg = libjitsi_tpu.configuration_service()
    TK = (_keys(0xA0), _keys(0xB0))        # A->B, B->A trunk keys
    CONF, CONF_COLD = 7, 8

    def mk(bid, pid, txk, rxk):
        b = SfuBridge(cfg, port=0, capacity=64, recv_window_ms=0)
        tr = CascadeTrunk(txk, rxk, TrunkConfig(), port=0,
                          seed=seed + bid)
        sup = CascadeSupervisor(
            b, tr, SupervisorConfig(deadline_ms=1000.0),
            metrics=b.loop.metrics, bridge_id=bid, peer_bridge_id=pid)
        lc = StreamLifecycleManager(b, supervisor=sup,
                                    metrics=b.loop.metrics,
                                    config=LifecycleConfig())
        # cascade needs the placer: conference ids ride placement, and
        # failover re-homes conferences on the bridge axis
        lc.enable_placement(1)
        lc.placer.enable_bridges(2)
        tr.attach(b.loop)
        return b, tr, sup, lc

    bA, tA, supA, lcA = mk(0, 1, TK[0], TK[1])
    bB, tB, supB, lcB = mk(1, 0, TK[1], TK[0])
    now = 100.0
    t0_wall = time.perf_counter()
    tA.connect("127.0.0.1", tB.port, now=now)
    tB.connect("127.0.0.1", tA.port, now=now)
    supA.cascade_conference(CONF)
    supB.cascade_conference(CONF, remote=True)
    supB.cascade_conference(CONF_COLD, remote=True)
    # register the broadcast route on B up front: roster-installed
    # remote rows land as listeners until the SPEAKERS frame promotes
    bB.set_broadcast_speakers(CONF, [])

    def tick_both(k=1):
        nonlocal now
        for _ in range(k):
            supA.tick(now=now)
            supB.tick(now=now)
            now += dt
        bA.flush_egress()
        bB.flush_egress()

    def tick_b(k=1):
        nonlocal now
        for _ in range(k):
            supB.tick(now=now)
            now += dt
        bB.flush_egress()

    senders, receivers = [], []
    for k in range(n_senders):
        rx, tx = _keys(0x10 + 4 * k), _keys(0x12 + 4 * k)
        s = dict(ssrc=0x1000 + k, rx=rx, tx=tx, seq=1,
                 ts=0, eng=UdpEngine(port=0, max_batch=64),
                 prot=SrtpStreamTable(capacity=1))
        s["prot"].add_stream(0, *rx)
        ok, why = lcA.request_join(s["ssrc"], rx, tx,
                                   name=f"snd{k}", conference=CONF)
        assert ok, f"sender join refused: {why}"
        senders.append(s)
    row_of = {s["ssrc"]: k for k, s in enumerate(senders)}
    for k in range(n_receivers):
        rx, tx = _keys(0x80 + 4 * k), _keys(0x82 + 4 * k)
        r = dict(ssrc=0x2000 + k, rx=rx, tx=tx, got={},
                 eng=UdpEngine(port=0, max_batch=64),
                 open=SrtpStreamTable(capacity=n_senders + 1))
        # one open row PER SENDER (same downlink key): the probe sees
        # n interleaved seq spaces and needs separate replay windows
        for j in range(n_senders):
            r["open"].add_stream(j, *tx)
        ok, why = lcB.request_join(r["ssrc"], rx, tx,
                                   name=f"rcv{k}", conference=CONF)
        assert ok, f"receiver join refused: {why}"
        receivers.append(r)

    def _send_from(s, port, n=2):
        pls = [bytes([0x40 + row_of[s["ssrc"]]]) * 120] * n
        seqs = [(s["seq"] + i) & 0xFFFF for i in range(n)]
        b = rtp_header.build(pls, seqs,
                             [s["ts"] + i for i in range(n)],
                             [s["ssrc"]] * n, [96] * n,
                             stream=[0] * n)
        s["seq"] = (s["seq"] + n) & 0xFFFF
        s["ts"] += n
        s["eng"].send_batch(s["prot"].protect_rtp(b),
                            "127.0.0.1", port)

    def _latch(r, port):
        b = rtp_header.build([b"\x11" * 40], [1], [0], [r["ssrc"]],
                             [96], stream=[0])
        t = SrtpStreamTable(capacity=1)
        t.add_stream(0, *r["rx"])
        r["eng"].send_batch(t.protect_rtp(b), "127.0.0.1", port)

    def _drain(r, timeout_ms=0):
        fresh = {}
        back, _, _ = r["eng"].recv_batch(timeout_ms=timeout_ms)
        if not back.batch_size:
            return fresh
        raw = [back.to_bytes(j) for j in range(back.batch_size)]
        keep = [w for w in raw
                if len(w) >= 12
                and int.from_bytes(w[8:12], "big") in row_of]
        if not keep:
            return fresh
        sub = PacketBatch.from_payloads(
            keep, stream=[row_of[int.from_bytes(w[8:12], "big")]
                          for w in keep])
        _, okm = r["open"].unprotect_rtp(sub)
        for j, w in enumerate(keep):
            if bool(okm[j]):
                ssrc = int.from_bytes(w[8:12], "big")
                fresh[ssrc] = fresh.get(ssrc, 0) + 1
                r["got"][ssrc] = r["got"].get(ssrc, 0) + 1
        return fresh

    # ---- setup: commit joins, sync rosters both ways, trunks up
    for _ in range(400):
        tick_both()
        if (tA.state == tB.state == "up"
                and all(bB._sid_of_ssrc(s["ssrc"]) is not None
                        for s in senders)
                and all(bA._sid_of_ssrc(r["ssrc"]) is not None
                        for r in receivers)):
            break
    assert tA.state == tB.state == "up", "trunk never came up"
    assert all(bB._sid_of_ssrc(s["ssrc"]) is not None
               for s in senders), "roster sync never installed senders"

    # ---- top-K speaker bus: all but the last sender speak
    bus = senders[:-1] if n_senders > 1 else senders[:]
    bA.set_broadcast_speakers(
        CONF, [bA._sid_of_ssrc(s["ssrc"]) for s in bus])
    tick_both(6)
    spk_on_b = {bB._sid_of_ssrc(s["ssrc"]) for s in bus}
    speakers_propagated = bB._bcast_speakers.get(CONF) == spk_on_b
    for r in receivers:
        _latch(r, bB.port)
    tick_both(4)

    # ---- priming: media + a speaker flip land every compile before
    # the measured window
    def _media_rounds(rounds, legs, port, timeout_ms=0):
        nonlocal now
        for _ in range(rounds):
            for s in legs:
                _send_from(s, port)
            tick_both(2)
            for r in receivers:
                _drain(r, timeout_ms=timeout_ms)

    _media_rounds(6, bus, bA.port)
    flipped = senders[1:]                 # drop 0, add the last
    bA.set_broadcast_speakers(
        CONF, [bA._sid_of_ssrc(s["ssrc"]) for s in flipped])
    tick_both(4)
    _media_rounds(6, flipped, bA.port)
    w0A, w0B = lcA.datapath_recompiles, lcB.datapath_recompiles
    # hop-journey baseline at the same boundary: priming rounds carry
    # the compile stalls, and the cross-hop p99 gate must judge the
    # warm window only (same exclusion the recompile gate applies)
    hop0 = ({h: np.asarray(c.bucket_counts, dtype=np.int64).copy()
             for h, c in supB._journey_vec.children()}
            if supB._journey_vec is not None else {})
    for r in receivers:
        r["got"].clear()

    # ---- measured pre-kill window on the flipped bus
    bus = flipped
    bus_ssrcs = [s["ssrc"] for s in bus]
    _media_rounds(pre_rounds, bus, bA.port)
    pre_got = {r["ssrc"]: dict(r["got"]) for r in receivers}
    ok_media_pre = (tA.relay_frames_total > 0
                    and supB.remote_delivered > 0
                    and all(r["got"].get(ss, 0) > 0
                            for r in receivers for ss in bus_ssrcs))
    # speaker-bus restriction: the non-speaker's uplink is accepted at
    # A but never crosses the trunk
    nonspeaker = senders[0]
    r0 = tA.relay_frames_total
    for _ in range(5):
        _send_from(nonspeaker, bA.port)
        tick_both(2)
    relay_nonspeaker = tA.relay_frames_total - r0
    r0 = tA.relay_frames_total
    for _ in range(5):
        _send_from(bus[-1], bA.port)
        tick_both(2)
    relay_speaker = tA.relay_frames_total - r0
    ok_speaker_bus = (speakers_propagated and relay_nonspeaker == 0
                      and relay_speaker > 0)
    trunk_rtt = float(tA.rtt)

    # ---- kill bridge A mid-call
    kill_t = now
    recompiles_a = lcA.datapath_recompiles
    relayed_at_kill = tA.relay_frames_total
    bA.close()
    tA.close()
    tick_b(4)            # drain any in-flight trunk frames from A
    # stand-in for the survivor's idle reaper: a quiet remote row is
    # evicted mid-outage; nothing reinstalls it (its home bridge is
    # dead), so failover must re-key it from the synced roster — the
    # orphan-adoption path
    orphan = bus[0]
    lcB.request_leave(ssrc=orphan["ssrc"])
    tick_b(2)
    assert bB._sid_of_ssrc(orphan["ssrc"]) is None, \
        "orphan eviction did not take"
    down_ticks = 0
    while tB.state != "down" and down_ticks < 400:
        tick_b()
        down_ticks += 1
    detect_s = now - kill_t
    ok_failover = (tB.state == "down"
                   and supB.trunk_failovers_total == 1)

    # ---- adoption through the commit barrier
    for _ in range(400):
        tick_b()
        if not supB.adopting and supB.orphans_adopted >= 1:
            break
    orphan_sid = bB._sid_of_ssrc(orphan["ssrc"])
    ok_orphan = (supB.orphans_adopted >= 1
                 and orphan_sid is not None
                 and orphan_sid in bB._tx_keys
                 and orphan["ssrc"] not in tB._remote_ssrcs)
    # read the adoption evidence out of the flight ring NOW: the
    # orphan's per-stream ring is bounded and the restore phase's
    # header sampling would roll the event out
    kinds = _flight_kinds(supB.flight)

    # ---- typed refusals: a late joiner dials the survivor for a
    # conference still homed on the dead bridge
    refused: dict = {}
    joiner = dict(attempts=0, retry_at=now, admitted=False)
    jrx, jtx = _keys(0x60), _keys(0x62)

    def _joiner_try():
        if joiner["admitted"] or now < joiner["retry_at"]:
            return
        ok, reason = lcB.request_join(0x3000, jrx, jtx,
                                      name="late", conference=CONF_COLD)
        if ok:
            joiner["admitted"] = True
            return
        refused[reason] = refused.get(reason, 0) + 1
        joiner["attempts"] += 1
        hint = lcB.retry_after_hint(reason, conference=CONF_COLD)
        joiner["retry_at"] = now + max(hint, dt) * (
            2 ** min(joiner["attempts"] - 1, 6))

    for _ in range(40):
        _joiner_try()
        tick_b()
    refusals_while_down = sum(refused.values())
    # signaling re-homes the cold conference on the survivor: the
    # typed refusals lift and the joiner's next retry admits
    lcB.promote_remote_conference(CONF_COLD)
    for _ in range(200):
        _joiner_try()
        tick_b()
        if joiner["admitted"]:
            break
    tick_b(2)
    ok_typed_refusals = (
        refused.get("trunk_down", 0) > 0
        and set(refused) <= set(ADMIT_REASONS)
        and refusals_while_down <= refusal_bound
        and joiner["admitted"])

    # ---- media restored on the survivor: speakers redial B
    for r in receivers:
        r["got"].clear()
    restore_t: dict = {}
    for _ in range(post_rounds):
        for s in bus:
            _send_from(s, bB.port, n=1)
        tick_b()
        for r in receivers:
            fresh = _drain(r, timeout_ms=2)
            for ss in fresh:
                if ss in bus_ssrcs and ss not in restore_t:
                    restore_t[ss] = now - kill_t
    restored = [restore_t.get(ss) for ss in bus_ssrcs]
    p99_restore = (float(np.percentile(
        [t for t in restored if t is not None], 99))
        if any(t is not None for t in restored) else float("inf"))
    ok_restored = (all(t is not None for t in restored)
                   and p99_restore <= restore_p99_bound_s)

    # ---- reconciliation: never torn, queues drained, re-homed
    torn = [sid for sid in bB._ssrc_of
            if sid not in bB._tx_keys and sid not in bB._staged]
    ok_reconciled = (not torn and not supB.adopting
                     and not supB._adopt_q
                     and not supB._pending_commit
                     and not supB._conf_outstanding
                     and lcB.placer.bridge_of(CONF) == 1)
    window_recompiles = ((recompiles_a - w0A)
                         + (lcB.datapath_recompiles - w0B))
    kinds |= _flight_kinds(supB.flight)
    scrape = bB.loop.metrics.render()
    ok_metrics = all(m in scrape for m in (
        "trunk_heartbeats_total", "trunk_relay_pps", "trunk_rtt",
        "trunk_failovers_total", "cascade_orphans_adopted",
        "trunk_one_way_delay_seconds"))

    # ---- cross-hop journey gate: every trunk-delivered frame carried
    # the trace extension, so B's journey vec must hold a b0-b1 child
    # with a bounded p99 (wall time A-ingress -> B-trunk-ingest,
    # same-host clocks here so the raw delta is honest).  p99 is
    # computed over the post-priming window via the hop0 baseline.
    def _hop_window(h, c):
        wc = np.asarray(c.bucket_counts, dtype=np.int64).copy()
        base = hop0.get(h)
        if base is not None:
            wc -= base
        cum = np.cumsum(wc)
        n = int(cum[-1])
        if n <= 0:
            return 0, None
        k = int(np.searchsorted(cum, 0.99 * n, side="left"))
        p99 = (float(c.uppers[k]) if k < len(c.uppers)
               else float("inf"))
        return n, p99

    vec = supB._journey_vec
    cross_hops = {h: c for h, c in (vec.children() if vec is not None
                                    else []) if h != "local"}
    hop_win = {h: _hop_window(h, c)
               for h, c in sorted(cross_hops.items())}
    hop_p99s = {h: p for h, (_, p) in hop_win.items()}
    ok_cross_hop = (bool(cross_hops)
                    and all(n > 0 for n, _ in hop_win.values())
                    and all(p is not None and p <= hop_p99_bound_s
                            for p in hop_p99s.values()))
    ok_trunk_owd = supB.trunk_owd_s > 0.0
    ok_failover_pm = any(p.get("trigger") == "trunk_failover"
                         for p in supB.postmortems)
    ok_hop_exported = 'hop="b0-b1"' in scrape

    report = {
        "mode": "cascade",
        "senders": n_senders,
        "receivers": n_receivers,
        "wall_s": round(time.perf_counter() - t0_wall, 3),
        "model_time_s": round(now - 100.0, 3),
        "trunk_rtt_s": round(trunk_rtt, 4),
        "relayed_at_kill": relayed_at_kill,
        "remote_delivered": supB.remote_delivered,
        "relay_nonspeaker": relay_nonspeaker,
        "relay_speaker": relay_speaker,
        "pre_kill_decrypts": {hex(k): v
                              for k, v in sorted(pre_got.items())},
        "down_detect_s": round(detect_s, 3),
        "failovers": supB.trunk_failovers_total,
        "orphans_adopted": supB.orphans_adopted,
        "orphans_requeued": supB.orphans_requeued,
        "refusals": dict(refused),
        "refusals_while_down": refusals_while_down,
        "joiner_attempts": joiner["attempts"],
        "restore_s": {hex(ss): (round(t, 3) if t is not None else None)
                      for ss, t in zip(bus_ssrcs, restored)},
        "restore_p99_s": (round(p99_restore, 3)
                          if p99_restore != float("inf") else None),
        "priming_recompiles": w0A + w0B,
        "window_recompiles": window_recompiles,
        "hop_journeys": {h: n for h, (n, _) in hop_win.items()},
        "hop_p99_s": {h: (round(p, 4) if p not in (None, float("inf"))
                          else p) for h, p in hop_p99s.items()},
        "trunk_owd_s": round(supB.trunk_owd_s, 5),
        "torn_rows": torn,
        "flight_kinds": sorted(kinds & {"trunk_failover",
                                        "orphan_adopted", "trunk_up"}),
        "conf_bridge_home": lcB.placer.bridge_of(CONF),
        # ---- invariants
        "ok_media_flowed": ok_media_pre,
        "ok_speaker_bus": ok_speaker_bus,
        "ok_failover_detected": (ok_failover
                                 and "trunk_failover" in kinds),
        "ok_orphan_adopted": (ok_orphan
                              and "orphan_adopted" in kinds),
        "ok_media_restored_p99": ok_restored,
        "ok_zero_datapath_recompiles": window_recompiles == 0,
        "ok_typed_refusals": ok_typed_refusals,
        "ok_reconciled": ok_reconciled,
        "ok_metrics_exported": ok_metrics,
        "ok_cross_hop_journeys": ok_cross_hop,
        "ok_trunk_owd": ok_trunk_owd,
        "ok_failover_postmortem": ok_failover_pm,
        "ok_hop_exported": ok_hop_exported,
    }
    for s in senders:
        s["eng"].close()
    for r in receivers:
        r["eng"].close()
    tB.close()
    bB.close()
    libjitsi_tpu.stop()
    if verbose:
        print("---- cascade failover soak report ----")
        for k, v in report.items():
            print(f"{k:32s} {v}")
    if report_path:
        with open(report_path, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
    return report


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--duration", type=float, default=30.0,
                    help="measured churn window, model seconds")
    ap.add_argument("--ramp", type=float, default=6.0,
                    help="ramp to steady state before the window")
    ap.add_argument("--join-rate", type=float, default=300.0)
    ap.add_argument("--hold", type=float, default=0.6,
                    help="mean stream hold time, seconds")
    ap.add_argument("--capacity", type=int, default=1024)
    ap.add_argument("--probes", type=int, default=3)
    ap.add_argument("--drop", type=float, default=0.05,
                    help="simulated probe downlink loss rate")
    ap.add_argument("--target-events", type=float, default=500.0,
                    help="required sustained joins+leaves per second")
    ap.add_argument("--residual-bound", type=float, default=0.01)
    ap.add_argument("--p99-factor", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--report", type=str, default=None,
                    help="write the JSON report here")
    ap.add_argument("--smoke", action="store_true",
                    help="fast tier-1 configuration (~3 s model time)")
    ap.add_argument("--gcm", action="store_true",
                    help="AEAD-GCM wire with the keystream "
                         "pregeneration cache enabled on both bridge "
                         "tables (zero-recompile acceptance for the "
                         "cached crypto fast path)")
    ap.add_argument("--broadcast", action="store_true",
                    help="broadcast-conference mode: Poisson listener "
                         "churn on one hierarchical conference")
    ap.add_argument("--reconnect", action="store_true",
                    help="reconnect-storm chaos mode: mass DTLS "
                         "re-handshakes with a mid-storm kill/recover")
    ap.add_argument("--cascade", action="store_true",
                    help="bridge-cascade chaos mode: two trunked "
                         "bridges, one killed mid-call; the conference "
                         "must survive on the other")
    ap.add_argument("--cascade-senders", type=int, default=4,
                    help="cascade mode: senders homed on the doomed "
                         "bridge")
    ap.add_argument("--cascade-receivers", type=int, default=3,
                    help="cascade mode: receivers on the survivor")
    ap.add_argument("--clients", type=int, default=1000,
                    help="reconnect mode: simultaneous DTLS clients")
    ap.add_argument("--max-handshakes", type=int, default=128,
                    help="reconnect mode: admission bound on in-flight "
                         "handshakes (past it: typed refusals)")
    ap.add_argument("--handshake-batch", type=int, default=256,
                    help="reconnect mode: per-drain OpenSSL budget")
    ap.add_argument("--restore-p99", type=float, default=10.0,
                    help="reconnect mode: time-to-media-restored p99 "
                         "bound, model seconds")
    ap.add_argument("--listeners", type=int, default=4096,
                    help="broadcast mode: steady listener population")
    ap.add_argument("--speakers", type=int, default=8)
    ap.add_argument("--shards", type=int, default=8)
    ap.add_argument("--join-p99", type=float, default=0.25,
                    help="broadcast mode: listener-join p99 bound, "
                         "model seconds")
    args = ap.parse_args()
    if args.reconnect:
        kw = dict(n_clients=args.clients,
                  max_handshakes=args.max_handshakes,
                  handshake_batch=args.handshake_batch,
                  restore_p99_bound_s=args.restore_p99,
                  seed=args.seed, report_path=args.report)
        if args.smoke:
            kw.update(n_clients=24, max_handshakes=6,
                      handshake_batch=8, capacity=128,
                      storm_budget_s=60.0)
        report = run_reconnect_soak(**kw)
        failed = [k for k, v in report.items()
                  if k.startswith("ok_") and not v]
        if failed:
            print(f"INVARIANT FAILURES: {failed}", file=sys.stderr)
            return 1
        print("all reconnect-storm invariants held")
        return 0
    if args.cascade:
        kw = dict(n_senders=args.cascade_senders,
                  n_receivers=args.cascade_receivers,
                  seed=args.seed, report_path=args.report)
        if args.smoke:
            kw.update(n_senders=3, n_receivers=2,
                      pre_rounds=10, post_rounds=60)
        report = run_cascade_soak(**kw)
        failed = [k for k, v in report.items()
                  if k.startswith("ok_") and not v]
        if failed:
            print(f"INVARIANT FAILURES: {failed}", file=sys.stderr)
            return 1
        print("all cascade failover invariants held")
        return 0
    if args.broadcast:
        kw = dict(duration_s=args.duration, ramp_s=args.ramp,
                  mean_hold_s=args.hold, n_speakers=args.speakers,
                  n_listeners=args.listeners, n_shards=args.shards,
                  join_p99_bound_s=args.join_p99, seed=args.seed,
                  report_path=args.report)
        if args.smoke:
            kw.update(duration_s=3.0, ramp_s=2.0, n_listeners=192,
                      mean_hold_s=2.0, capacity=512)
        report = run_broadcast_soak(**kw)
        failed = [k for k, v in report.items()
                  if k.startswith("ok_") and not v]
        if failed:
            print(f"INVARIANT FAILURES: {failed}", file=sys.stderr)
            return 1
        print("all broadcast churn invariants held")
        return 0
    kw = dict(duration_s=args.duration, ramp_s=args.ramp,
              join_rate_hz=args.join_rate, mean_hold_s=args.hold,
              capacity=args.capacity, probes=args.probes,
              drop_rate=args.drop,
              target_events_per_sec=args.target_events,
              residual_bound=args.residual_bound,
              p99_factor_bound=args.p99_factor, seed=args.seed,
              gcm=args.gcm, report_path=args.report)
    if args.smoke:
        kw.update(duration_s=2.0, ramp_s=1.0, join_rate_hz=60.0,
                  mean_hold_s=0.5, capacity=128, probes=2,
                  target_events_per_sec=100.0)
    report = run_soak(**kw)
    failed = [k for k, v in report.items()
              if k.startswith("ok_") and not v]
    if failed:
        print(f"INVARIANT FAILURES: {failed}", file=sys.stderr)
        return 1
    print("all churn invariants held")
    return 0


if __name__ == "__main__":
    sys.exit(main())
