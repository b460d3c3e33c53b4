"""Meetings of 64: a fan-out the program bounds in ROWS, not packets,
and cuts by the row classes it has.

A packet of a member of a 64-member conference is 63 fan-out rows, so a
tick's rows outgrow the largest row class the warm ladder compiles
within a few dozen packets.  `RtpTranslator.translate_async` cuts the
(packet, receiver) rows into launches of the warmed row classes
(`plan_launches`): none over `launch_rows`, and a tick that fits it but
would pad far up to its class goes out as whole launches of a smaller
class and a remainder in its own (1,164 rows: 1,024 + 256 computed, not
4,096) where that saves more padded rows than `LAUNCH_COST_ROWS` a
further launch.  `SfuBridge` caches and hands over a launch at a time;
no launch has a shape the ladder did not warm, whatever the backlog.

Three layers, on the CPU with seeded keys, the last two against the
scalar OpenSSL oracle of `benchmarks/oracle.py`:

* the plan as a pure function, over a table of row counts against
  hand-written cuts at the real classes and the shipped constant;
* the translator alone with `launch_rows` set to a small class on the
  instance (64 rows), at the ratios that matter: a tick of exactly the
  top class, of one row more, of one and a half times, of twice and of
  nine times it; and, with the launch cost set to a few rows on the
  module, ticks that the classes under `launch_rows` cut;
* the bridge as `benchmarks/sut.py` assembles it (supervisor, lifecycle,
  every endpoint through `request_join`): 128 endpoints in two
  conferences of 64, whose ladder warms 16 / 64 / 256 rows, so its
  fan-out is cut at 256 rows = 4 packets (the real classes' 65) and,
  the launch cost set to 8 rows, by the 64- and 16-row classes under
  it; ticks of 1, 4, 5, 6, 9 and 37 packets stand for 1, 65, 66, 100,
  131 and 584, ticks of 2 and 3 for the 1,164 rows that fit the top
  class and pad less as 64 + 64 (+ 64).  One module fixture serves the
  seeded rounds once; the tests each hold one facet of its record.

The real classes (4,096 rows) compile for a minute on XLA:CPU: two
cases run them, marked `slow`.
"""

import importlib.util
import os
import socket

import numpy as np
import pytest

import libjitsi_tpu
from libjitsi_tpu.core.packet import (FANOUT_ROW_CLASSES, ROW_CLASSES,
                                      PacketBatch, _round_fanout_rows)
from libjitsi_tpu.rtp import rtcp
from libjitsi_tpu.sfu import translator as translator_mod
from libjitsi_tpu.sfu.translator import (LAUNCH_COST_ROWS, RtpTranslator,
                                         plan_launches)
from libjitsi_tpu.transform.srtp import SrtpProfile, SrtpStreamTable
from libjitsi_tpu.utils.compile_cache import compile_stats
from libjitsi_tpu.utils.tracing import LEAF_STAGES, PipelineTracer

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CM = SrtpProfile.AES_CM_128_HMAC_SHA1_80
GCM = SrtpProfile.AEAD_AES_128_GCM
SSRC_BASE = 0x64000000
ROWS, CONF = 128, 64
PT = 111
#: the ladder of 128 endpoints at one packet a stream a tick
WARMED = ROW_CLASSES[:3]
#: rows a launch after a tick's first costs while the bridge below
#: serves (the module's `LAUNCH_COST_ROWS` is sized for the real
#: classes: under it nothing at or below 256 rows is ever cut)
COST = 8
#: packets of one tick, a round -> the real rows of its launches, by
#: hand (a packet is 63 rows; classes 16 / 64 / 256, a further launch 8
#: rows): 1 and 4 are one launch (252 rows pad less as 256 than as 4 x
#: 64 + 3 x 8), 2 and 3 fit the top class and are cut by the 64-row
#: class (128 + 8 and 192 + 16 against 256), 5 is one row class more, 6
#: one and a half times the top class with a tail the classes cut, 9
#: over twice, 37 over nine times it with a tail of 27 rows as 16 + 11
SIZES = {1: [63], 2: [64, 62], 3: [64, 64, 61], 4: [252], 5: [256, 59],
         6: [256, 64, 58], 9: [256, 256, 55], 37: [256] * 9 + [16, 11]}
ROUNDS = tuple(SIZES)


@pytest.fixture(scope="module")
def oracle():
    spec = importlib.util.spec_from_file_location(
        "bench_oracle", os.path.join(_ROOT, "benchmarks", "oracle.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _keys(seed: int, n: int, salt: int = 14) -> np.ndarray:
    """[n, 2] (client->bridge, bridge->client) of (key 16, salt)."""
    return np.random.default_rng([seed, 0x64]).integers(
        0, 256, (n, 2, 16 + salt), dtype=np.uint8)


def _pair(raw) -> tuple:
    b = bytes(raw)
    return b[:16], b[16:]


def _plain(rng, ssrc: int, seq: int) -> bytes:
    hdr = (bytes([0x80, PT]) + seq.to_bytes(2, "big")
           + (seq * 960 & 0xFFFFFFFF).to_bytes(4, "big")
           + ssrc.to_bytes(4, "big"))
    return hdr + rng.integers(0, 256, int(rng.integers(40, 161)),
                              dtype=np.uint8).tobytes()


# -------------------------------------------------------------- the plan

#: rows of a tick -> the real rows of its launches at the fan-out's
#: real classes (16 / 64 / 256 / 512 / 1,024 / 4,096, all warmed) and
#: the shipped launch cost (752 rows), written by hand: up to 1,024
#: rows a tick is one launch (300 rows pad to 512, 513 to 1,024: 512 +
#: 16 + 752 is more); 1,025-2,560 rows pad less as one or two
#: 1,024-row launches and a small one than as 4,096 (1,300 rows: 1,024
#: + 512 computed where the row classes without 512 made 1,024 +
#: 1,024; 2,400: 2,048 + 512 + 2 x 752 = 4,064, which the 512-row
#: class brought under 4,096); above that the 4,096-row program runs as
#: before, and whole launches of it go first
PLAN = {
    256: [256], 257: [257], 272: [272], 300: [300], 512: [512],
    513: [513], 528: [528], 700: [700], 1024: [1024],
    1025: [1024, 1], 1164: [1024, 140], 1300: [1024, 276],
    1536: [1024, 512], 1537: [1024, 513], 2048: [1024, 1024],
    2112: [1024, 1024, 64], 2400: [1024, 1024, 352],
    2560: [1024, 1024, 512], 2561: [2561], 4096: [4096],
    4097: [4096, 1], 5396: [4096, 1024, 276],
    36792: [4096] * 8 + [4024],
}
#: the rows those launches are padded to, where the fan-out's own
#: class shows: a tick -> the classes its launches run
PADDED = {300: [512], 513: [1024], 1164: [1024, 256], 1300: [1024, 512],
          1537: [1024, 1024], 2400: [1024, 1024, 512]}


@pytest.mark.parametrize("rows", sorted(PLAN))
def test_the_plan_against_hand_written_cuts(rows):
    assert plan_launches(rows, ROW_CLASSES[-1], LAUNCH_COST_ROWS) == \
        PLAN[rows]
    # a translator that pads nothing here (the mesh's) cuts at the top
    # class alone, as every translator did
    assert plan_launches(rows, ROW_CLASSES[-1]) == \
        [ROW_CLASSES[-1]] * (rows // ROW_CLASSES[-1]) + \
        [rows % ROW_CLASSES[-1]] * bool(rows % ROW_CLASSES[-1])


@pytest.mark.parametrize("rows", sorted(PADDED))
def test_the_plan_pads_to_the_fanouts_own_classes(rows):
    assert [_round_fanout_rows(n) for n in plan_launches(
        rows, ROW_CLASSES[-1], LAUNCH_COST_ROWS)] == PADDED[rows]


def test_the_fanouts_classes_are_the_row_classes_and_512():
    """One definition: `ROW_CLASSES` as it was, a subset of the
    fan-out's tuple, which adds 512 and nothing else."""
    assert ROW_CLASSES == (16, 64, 256, 1024, 4096)
    assert FANOUT_ROW_CLASSES == (16, 64, 256, 512, 1024, 4096)
    assert set(FANOUT_ROW_CLASSES) - set(ROW_CLASSES) == {512}
    assert [_round_fanout_rows(n) for n in (1, 256, 257, 512, 513, 4097)] \
        == [16, 256, 512, 512, 1024, 8192]


@pytest.mark.parametrize("top", FANOUT_ROW_CLASSES[1:])
@pytest.mark.parametrize("cost", [0, 8, LAUNCH_COST_ROWS, 10 ** 6])
def test_no_planned_launch_lies_outside_the_warmed_classes(top, cost):
    """On a ladder warmed up to `top` (whole or partly), whatever the
    rows and the launch cost: launches in row order that sum to the
    rows, none over `top` and each padded to one of the fan-out's
    classes up to it (which the ladder warms, all of them), whole
    launches first, and never more padded rows plus launch costs than
    cuts at `top` alone."""
    def padded(cut):
        return sum(map(_round_fanout_rows, cut)) + cost * (len(cut) - 1)

    for rows in [*range(1, 600), 1024, 1025, 1164, 1300, 2304, 2305,
                 2560, 2561, 4095, 4097, 5260, 9 * top + 27, 36792]:
        cut = plan_launches(rows, top, cost)
        assert sum(cut) == rows and min(cut) > 0, (rows, cut)
        assert all(_round_fanout_rows(n) in FANOUT_ROW_CLASSES
                   and _round_fanout_rows(n) <= top
                   for n in cut), (rows, cut)
        assert cut[:rows // top] == [top] * (rows // top), (rows, cut)
        # full launches of one class, then at most one remainder
        tail = cut[rows // top:]
        assert len(set(tail[:-1])) <= 1 and all(
            n == _round_fanout_rows(n) for n in tail[:-1]), (rows, cut)
        assert padded(cut) <= padded(plan_launches(rows, top)), (rows, cut)
    # at a launch cost beyond any padding nothing under `top` is cut
    if cost >= 10 ** 6:
        assert plan_launches(top - 1, top, cost) == [top - 1]


def test_the_shipped_launch_cost_cuts_no_tick_of_1024_rows_or_fewer():
    """At 752 rows, which the shipped constant is (PR 41's
    measurement), every tick of the `talk-*` cells and of GCM (160-700
    rows) stays one launch: every tail of 1-1,024 rows is.  With the
    fan-out's 512-row class the least such cost is 496: one row less
    and ticks of 513-528 rows would go out as 512 + 16 (without 512 it
    was 752 itself, for 257-272 rows as 256 + 16)."""
    assert LAUNCH_COST_ROWS == 752
    for top in FANOUT_ROW_CLASSES:
        assert all(plan_launches(r, top, LAUNCH_COST_ROWS)
                   == plan_launches(r, top) for r in range(1, 1025))
    cut_at = {cost: [r for r in range(1, 1025) if len(
        plan_launches(r, ROW_CLASSES[-1], cost)) > 1]
        for cost in (495, 496)}
    assert cut_at == {495: list(range(513, 529)), 496: []}


# ------------------------------------------------- the translator alone

def _translator(profile, legs_of, top: int):
    """A translator of 80 receivers whose sender `s` (sid 100 + s)
    fans out to `legs_of[s]` receivers; `launch_rows` = `top`."""
    salt = 12 if profile is GCM else 14
    keys = _keys(7, 80, salt)
    tr = RtpTranslator(capacity=256, profile=profile)
    tr.add_receivers(range(80), [k[1][:16] for k in keys],
                     [k[1][16:] for k in keys])
    at = 0
    for s, n in enumerate(legs_of):
        # lists that differ from sender to sender: the per-row form
        tr.connect(100 + s, [(at + j) % 80 for j in range(n)])
        at += 3
    tr.launch_rows = top
    return tr, keys


def _batch(rng, senders):
    pls = [_plain(rng, 0x7000 + s, 900 + k)
           for k, s in enumerate(senders)]
    b = PacketBatch.from_payloads(pls, capacity=256,
                                  stream=[100 + s for s in senders])
    return b, pls, np.arange(900, 900 + len(senders), dtype=np.int64)


#: (legs a sender, `launch_rows`, launch cost or None for the shipped
#: one, the real rows of each launch): rows = the sum of the legs.  At
#: the shipped cost a top class of 64 is cut at 64 alone; at a cost of
#: 8 rows the classes under the top one cut the tick (or its tail)
RATIOS = {
    "exactly_the_top_class": ((63, 1), 64, None, [64]),
    "one_row_more": ((63, 2), 64, None, [64, 1]),
    "one_and_a_half_times": ((63, 33), 64, None, [64, 32]),
    "twice": ((63, 63, 2), 64, None, [64, 64]),
    "nine_times": ((63,) * 9 + (9,), 64, None, [64] * 9),
    "fits_the_top_class_and_is_cut": ((63, 9), 256, 8, [64, 8]),
    "three_whole_launches_and_a_small_one":
        ((63, 63, 63, 9), 256, 8, [64, 64, 64, 6]),
    "over_the_top_class_with_a_tail_that_is_cut":
        ((63, 30), 64, 8, [64, 16, 13]),
}


@pytest.mark.parametrize("case", sorted(RATIOS))
def test_rows_are_cut_by_the_classes_and_come_back_in_order(
        case, oracle, monkeypatch):
    legs_of, top, cost, sizes = RATIOS[case]
    if cost is not None:
        monkeypatch.setattr(translator_mod, "LAUNCH_COST_ROWS", cost)
    launches, rows = len(sizes), sum(legs_of)
    assert sum(sizes) == rows
    rng = np.random.default_rng(11)
    tr, keys = _translator(CM, legs_of, top)
    tr.tracer = tracer = PipelineTracer(annotate=False)
    seen = []
    call = tr._cm_fanout_call

    def spy(recv, plane, *rest):
        seen.append(plane.shape[0])
        return call(recv, plane, *rest)

    tr._cm_fanout_call = spy
    b, pls, index = _batch(rng, range(len(legs_of)))
    # the plan's answer for the tick's real rows is the call's
    assert len(tr._plan(sum(len(tr._routes[int(x)])
                            for x in b.stream))) == launches
    pend = tr.translate_async(b, index)
    assert pend.launches == launches
    # every launch's plane has its own class's rows, none over the top
    assert seen == [_round_fanout_rows(n) for n in sizes] and max(seen) <= top
    parts = list(pend.each())
    assert [len(r) for _w, r in parts] == sizes
    wire, recv = pend.result()
    assert wire.batch_size == rows == len(recv)
    # a launch's rows are the whole's, in row order
    assert np.array_equal(np.concatenate([r for _w, r in parts]), recv)
    at = 0
    for w, _r in parts:
        for j in range(w.batch_size):
            assert w.to_bytes(j) == wire.to_bytes(at + j)
        at += w.batch_size
    # every row is the oracle's bytes under its receiver's key
    j = 0
    for s, n in enumerate(legs_of):
        for _ in range(n):
            assert wire.to_bytes(j) == oracle.protect_cm(
                *_pair(keys[int(recv[j]), 1]), pls[s], 900 + s), (case, j)
            j += 1
    tracer.take_ledger()
    exp = tracer.last_counts["expand"]
    assert exp["launches"] == launches and exp["legs_max"] == 63
    assert exp["rows"] == rows
    # each launch's own `expand` books the class it is padded to
    assert exp["rows_padded"] == exp["row_class"] == sum(seen)
    assert tr.fanout_launch_rows == {c: seen.count(c) for c in seen}
    # the classes cut a tick that FITS `launch_rows`
    class_cut = int(launches > 1 and rows <= top)
    assert exp["class_cut"] == class_cut
    assert tr.fanout_launches == launches
    assert tr.fanout_split_ticks == int(launches > 1)
    assert tr.fanout_class_cut_ticks == class_cut


@pytest.mark.parametrize("senders", [37, 43, 73], ids=str)
@pytest.mark.parametrize("profile", [CM, GCM], ids=["cm", "gcm"])
def test_a_tick_of_257_to_512_rows_runs_the_512_row_program(
        profile, senders, oracle):
    """37, 43 and 73 packets of conferences of 8 in one tick (259, 301
    and 511 rows, where the GCM cell's ticks stand) on a ladder that
    warmed 1,024 rows: ONE launch padded to the fan-out's own 512-row
    class and not to 1,024, nothing compiled after the 512-row
    warm-ups, every row the oracle's under its receiver's key."""
    protect = oracle.protect_gcm if profile is GCM else oracle.protect_cm
    legs_of = (7,) * senders
    rows = 7 * senders
    tr, keys = _translator(profile, legs_of, 1024)
    tr.tracer = tracer = PipelineTracer(annotate=False)
    thunks = tr.fanout_warmups(300)        # rounds to the 512-row class
    assert len(thunks) == 2                # the audio width, the MTU
    for thunk in thunks:
        thunk()
    seen = []
    name = "_gcm_fanout_call" if profile is GCM else "_cm_fanout_call"
    call = getattr(tr, name)

    def spy(recv, plane, *rest):
        seen.append(plane.shape)
        return call(recv, plane, *rest)

    setattr(tr, name, spy)
    b, pls, index = _batch(np.random.default_rng(44), range(senders))
    before = compile_stats().compile_events
    assert len(tr._plan(rows)) == 1
    wire, recv = tr.translate(b, index)
    assert compile_stats().compile_events == before
    assert [s[0] for s in seen] == [512]
    assert wire.batch_size == rows == len(recv)
    for j in range(rows):
        assert wire.to_bytes(j) == protect(
            *_pair(keys[int(recv[j]), 1]), pls[j // 7], 900 + j // 7), j
    tracer.take_ledger()
    exp = tracer.last_counts["expand"]
    assert (exp["rows"], exp["rows_padded"], exp["row_class"]) == \
        (rows, 512, 512)
    assert (exp["launches"], exp["class_cut"]) == (1, 0)
    assert tr.fanout_launch_rows == {512: 1}
    assert (tr.fanout_launches, tr.fanout_split_ticks) == (1, 0)


def test_a_split_tick_is_byte_equal_to_the_same_packets_a_launch_each(
        oracle):
    """Five senders of 63 legs through one tick (five launches) and
    through five ticks of one launch: the same rows, byte for byte."""
    rng = np.random.default_rng(12)
    tr, _keys_ = _translator(CM, (63,) * 5, 64)
    b, _pls, index = _batch(rng, range(5))
    whole, recv = tr.translate(b, index)
    assert (tr.fanout_launches, tr.fanout_split_ticks) == (5, 1)
    at = 0
    for k in range(5):
        one = PacketBatch(b.data[k:k + 1], b.length[k:k + 1],
                          b.stream[k:k + 1])
        pend = tr.translate_async(one, index[k:k + 1])
        assert pend.launches == 1
        w, r = pend.result()
        assert np.array_equal(r, recv[at:at + 63])
        assert [w.to_bytes(j) for j in range(63)] == \
            [whole.to_bytes(at + j) for j in range(63)]
        at += 63
    assert (tr.fanout_split_ticks, tr.fanout_class_cut_ticks) == (1, 0)


@pytest.mark.parametrize("profile", [CM, GCM], ids=["cm", "gcm"])
def test_a_class_cut_tick_is_byte_equal_to_one_launch_in_the_top_class(
        profile, oracle, monkeypatch):
    """28 rows that fit a top class of 64: as 16 + 12 (a launch costs 4
    rows) and as ONE 64-row launch (a launch costs more than any
    padding), CM and per-row GCM: the same bytes, the oracle's."""
    protect = oracle.protect_gcm if profile is GCM else oracle.protect_cm
    legs_of = (20, 8) if profile is CM else (7,) * 4
    got = {}
    for cost, launches in ((4, 2), (10 ** 6, 1)):
        monkeypatch.setattr(translator_mod, "LAUNCH_COST_ROWS", cost)
        tr, keys = _translator(profile, legs_of, 64)
        b, pls, index = _batch(np.random.default_rng(16),
                               range(len(legs_of)))
        pend = tr.translate_async(b, index)
        assert pend.launches == launches
        assert all(pg is None for _l, pg, _r, _n in pend._parts)
        assert tr.fanout_class_cut_ticks == launches - 1
        wire, recv = pend.result()
        got[launches] = [wire.to_bytes(j) for j in range(28)]
    assert got[2] == got[1]
    j = 0
    for s, n in enumerate(legs_of):
        for _ in range(n):
            assert got[2][j] == protect(
                *_pair(keys[int(recv[j]), 1]), pls[s], 900 + s), j
            j += 1


def test_gcm_conferences_of_8_split_and_open_under_the_oracle(oracle):
    """Under the AEAD suite a tick of conferences of 8 (7 legs: the
    per-row form) over the top class splits the same way."""
    rng = np.random.default_rng(14)
    tr, keys = _translator(GCM, (7,) * 10, 64)
    b, pls, index = _batch(rng, range(10))
    pend = tr.translate_async(b, index)
    assert pend.launches == 2                 # 70 rows: 64 + 6
    assert all(pg is None for _l, pg, _r, _n in pend._parts)
    wire, recv = pend.result()
    assert wire.batch_size == 70
    for j in range(70):
        s = j // 7
        assert wire.to_bytes(j) == oracle.protect_gcm(
            *_pair(keys[int(recv[j]), 1]), pls[s], 900 + s), j


#: (senders of 63 legs + one of `last`, the real rows of each launch)
#: at the real classes and the shipped launch cost
REAL = {"over_the_top_class": (66, 0, [4096, 62]),
        "fits_it_and_pads_less_as_1024_plus_256": (18, 30, [1024, 140])}


@pytest.mark.slow
@pytest.mark.parametrize("case", sorted(REAL))
def test_the_real_classes_cut_the_tick(case, oracle):
    """66 packets of 63 legs: 4,158 rows in two launches (4,096 + 62 ->
    64); 18 and one of 30 legs: 1,164 rows as 1,024 + 140 -> 256, not
    one launch of 4,096.  Byte-equal to the oracle."""
    senders, last, sizes = REAL[case]
    legs_of = (63,) * senders + (last,) * bool(last)
    rng = np.random.default_rng(15)
    tr, keys = _translator(CM, legs_of, ROW_CLASSES[-1])
    b, pls, index = _batch(rng, range(len(legs_of)))
    pend = tr.translate_async(b, index)
    assert [len(r) for _l, _pg, r, _n in pend._parts] == sizes
    assert tr.fanout_class_cut_ticks == int(sum(sizes) <= ROW_CLASSES[-1])
    wire, recv = pend.result()
    assert wire.batch_size == sum(legs_of)
    for j in range(0, wire.batch_size, 7):
        assert wire.to_bytes(j) == oracle.protect_cm(
            *_pair(keys[int(recv[j]), 1]), pls[j // 63], 900 + j // 63)


# ------------------------------------------------------ the served bridge

def _serve(oracle, tap_of) -> dict:
    """Admit ROWS endpoints in two conferences of 64 as `sut.py` does
    and drive ROUNDS; the record."""
    from libjitsi_tpu.service import lifecycle as lifecycle_mod
    from libjitsi_tpu.service import supervisor as supervisor_mod
    from libjitsi_tpu.service.sfu_bridge import SfuBridge

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    bridge = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                       capacity=ROWS, profile=CM, recv_window_ms=0)
    cost0 = translator_mod.LAUNCH_COST_ROWS
    reg = bridge.loop.metrics
    sup = supervisor_mod.BridgeSupervisor(
        bridge, supervisor_mod.SupervisorConfig(deadline_ms=60_000.0),
        metrics=reg)
    lc = lifecycle_mod.StreamLifecycleManager(
        bridge, supervisor=sup,
        config=lifecycle_mod.LifecycleConfig(
            install_batch=64, max_pending=512, pkts_per_stream=1,
            max_conference_size=CONF),
        metrics=reg)
    lc.enable_placement(1)
    tap = tap_of(bridge)
    # `translate` wrapped on the instance, as the benchmark's fault
    # `bridge-bitflip` wraps it (`benchmarks/sut.py:break_fanout`)
    whole, inner = [], bridge.translator.translate

    def translate(batch, index):
        whole.append(batch.batch_size)
        return inner(batch, index)

    bridge.translator.translate = translate
    keys = _keys(40, ROWS)
    now = [2000.0]
    rec = {"sent": {}, "rounds": [], "nack": None}
    socks = []

    def tick(n=1):
        """`n` ticks; the record of each that dispatched a fan-out
        (`expand`) or collected one (`fanout_wait`): a round of
        packets is dispatched by the tick that reads them and
        collected by the next, which reads none and collects at its
        end."""
        got = []
        for _ in range(n):
            now[0] += 0.02
            sup.tick(now=now[0])
            if {"expand", "fanout_wait"} & set(sup.last_counts):
                got.append(({k: dict(v)
                             for k, v in sup.last_counts.items()},
                            dict(sup.last_ledger),
                            dict(sup.last_self_ledger), sup.last_tick_s))
        bridge.flush_egress()
        return got

    def merged(ticks):
        """A round's two ticks as one record: counts and ledgers
        summed, so that what one serial tick booked is read as it
        was."""
        counts, led, self_led, tick_s = {}, {}, {}, 0.0
        for c, inc, own, s in ticks:
            for stage, kv in c.items():
                mine = counts.setdefault(stage, {})
                for k, v in kv.items():
                    mine[k] = mine.get(k, 0) + v
            for total, part in ((led, inc), (self_led, own)):
                for stage, sec in part.items():
                    total[stage] = total.get(stage, 0.0) + sec
            tick_s += s
        return counts, led, self_led, tick_s

    def drain():
        out = []
        for r, s in enumerate(socks):
            while True:
                try:
                    pkt = s.recv(2048)
                except BlockingIOError:
                    break
                if len(pkt) >= 12 and (pkt[1] & 0x7F) == PT:
                    out.append((r, pkt))
        return out

    try:
        # a launch costs COST rows while this bridge serves, so that the
        # classes its small ladder warms cut a tick as the real ones do
        translator_mod.LAUNCH_COST_ROWS = COST
        for i in range(ROWS):
            if i == CONF:
                # the first room is full: its 65th member is refused,
                # by name, while the table has room for 64 more
                rec["refused"] = lc.request_join(
                    SSRC_BASE + ROWS, _pair(keys[0, 0]),
                    _pair(keys[0, 1]), conference=0)
            ok, why = lc.request_join(SSRC_BASE + i, _pair(keys[i, 0]),
                                      _pair(keys[i, 1]),
                                      conference=i // CONF)
            assert ok, why
        while lc.admits < ROWS:
            tick()
            assert sup.ticks < 64, f"{lc.admits}/{ROWS} live"
        rec["warm_rows"] = sorted(lc._warm_rows)
        rec["launch_rows"] = bridge.translator.launch_rows
        rec["rejected"] = dict(lc.admit_rejected)
        sid_of = {ssrc: sid for sid, ssrc in bridge._ssrc_of.items()}
        for i in range(ROWS):
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            s.bind(("127.0.0.1", 0))
            s.setblocking(False)
            socks.append(s)
        rng = np.random.default_rng(43)
        seq = np.full(ROWS, 300, dtype=np.int64)

        def fresh(i):
            s = int(seq[i])
            seq[i] += 1
            plain = _plain(rng, SSRC_BASE + i, s)
            rec["sent"][(SSRC_BASE + i, s)] = plain
            return oracle.protect_cm(*_pair(keys[i, 0]), plain, s)

        # the bridge learns a leg's address from its own packets: one
        # member says how it keeps them, the others' are written as it
        # would have (128 first packets are 8,064 fan-out rows)
        socks[0].sendto(fresh(0), ("127.0.0.1", bridge.port))
        tick(2)
        loop = bridge.loop
        ip = loop.addr_ip[sid_of[SSRC_BASE]]
        assert loop.addr_port[sid_of[SSRC_BASE]] == \
            socks[0].getsockname()[1]
        for i in range(ROWS):
            loop.addr_ip[sid_of[SSRC_BASE + i]] = ip
            loop.addr_port[sid_of[SSRC_BASE + i]] = \
                socks[i].getsockname()[1]
        drain()
        rec["sent"].clear()
        tap.handed.clear()
        events0 = compile_stats().compile_events
        recompiles0 = lc.datapath_recompiles
        for n in ROUNDS:
            # senders of both conferences once a tick can hold both
            senders = [(k * 5) % CONF + CONF * (k % 2 if n > 9 else 0)
                       for k in range(n)]
            assert len(set(senders)) == n
            for i in senders:
                socks[i].sendto(fresh(i), ("127.0.0.1", bridge.port))
            del whole[:]
            ticks = tick(2)
            assert ["expand" in c for c, *_ in ticks] == [True, False] \
                and ["fanout_wait" in c for c, *_ in ticks] == \
                [False, True], "dispatched by one tick, collected by " \
                "the next"
            rec["rounds"].append({"packets": n, "senders": senders,
                                  "tick": merged(ticks), "got": drain(),
                                  "whole": list(whole)})
        rec["compiles"] = compile_stats().compile_events - events0
        rec["recompiles"] = lc.datapath_recompiles - recompiles0
        # a row of the second round's SMALL launch (126 rows that fit
        # the top class, cut 64 + 62: rows 64..125, the second
        # packet's last receivers), asked for again
        assert ROUNDS[1] == 2
        sender = rec["rounds"][1]["senders"][-1]
        r = max(i for i in range(CONF) if i != sender)
        lost = 300                      # that member's first packet
        cl = SrtpStreamTable(capacity=1)
        cl.add_stream(0, *_pair(keys[r, 0]))
        blob = rtcp.build_compound([rtcp.build_nack(rtcp.Nack(
            sender_ssrc=SSRC_BASE + r,
            media_ssrc=SSRC_BASE + sender, lost_seqs=[lost]))])
        socks[r].sendto(
            cl.protect_rtcp(PacketBatch.from_payloads(
                [blob], stream=[0])).to_bytes(0),
            ("127.0.0.1", bridge.port))
        for _ in range(3):
            now[0] += 0.02
            sup.tick(now=now[0])
        bridge.flush_egress()
        rec["nack"] = {"receiver": r, "sender": sender,
                       "seq": lost, "got": drain(),
                       "retransmitted": bridge.retransmitted}
        rec["keys"] = keys
        rec["health"] = sup.health()
        rec["metrics"] = reg.render()
        rec["handed"] = tap.handed
        rec["ports"] = [s.getsockname()[1] for s in socks]
        rec["translator"] = (bridge.translator.fanout_launches,
                             bridge.translator.fanout_split_ticks,
                             bridge.translator.fanout_class_cut_ticks)
        rec["launch_rows_by_class"] = dict(
            bridge.translator.fanout_launch_rows)
        return rec
    finally:
        translator_mod.LAUNCH_COST_ROWS = cost0
        for s in socks:
            s.close()
        bridge.close()


@pytest.fixture(scope="module")
def served(oracle, egress_tap):
    return _serve(oracle, egress_tap)


def _class_cut(packets: int) -> bool:
    """The classes cut a tick that fits the ladder's top class."""
    return len(SIZES[packets]) > 1 and \
        packets * (CONF - 1) <= WARMED[-1]


def test_the_ladders_top_class_is_the_fanouts_bound(served):
    assert served["warm_rows"] == list(WARMED)
    assert served["launch_rows"] == WARMED[-1]


@pytest.mark.parametrize("k", range(len(ROUNDS)))
def test_every_delivery_once_under_its_receivers_key(served, oracle, k):
    """(a) each packet reaches each of the 63 others of its conference
    exactly once, as the oracle's bytes under that receiver's key, and
    nobody else."""
    rnd, keys = served["rounds"][k], served["keys"]
    got = {}
    for r, pkt in rnd["got"]:
        key = (r, int.from_bytes(pkt[8:12], "big"),
               int.from_bytes(pkt[2:4], "big"))
        assert key not in got, f"delivered twice: {key}"
        got[key] = pkt
    assert len(got) == rnd["packets"] * (CONF - 1)
    for (r, ssrc, seq), pkt in got.items():
        mk, ms = _pair(keys[r, 1])
        plain = oracle.unprotect_cm(mk, ms, pkt, seq)
        assert plain is not None, f"bad tag for receiver {r}"
        sent = served["sent"][(ssrc, seq)]
        # the bridge stamps abs-send-time: header past the X bit and the
        # whole payload are the sender's
        assert plain[1:12] == sent[1:12]
        assert plain[oracle.payload_off(plain):] == sent[12:]
        # and the wire bytes are the oracle's over that plaintext
        assert oracle.protect_cm(mk, ms, plain, seq) == pkt
        assert (ssrc - SSRC_BASE) // CONF == r // CONF
        assert ssrc - SSRC_BASE != r
        assert ssrc - SSRC_BASE in rnd["senders"]


@pytest.mark.parametrize("k", range(len(ROUNDS)))
def test_a_tick_of_one_launch_is_translates_whole(served, k):
    """One rule decides: where the plan made one launch of the tick's
    rows the bridge collects it through `translate` (the seam the
    benchmark's fault `bridge-bitflip` wraps, which finds the launch
    in flight and dispatches none), where it cut them a launch at a
    time."""
    rnd = served["rounds"][k]
    one = len(SIZES[rnd["packets"]]) == 1
    assert rnd["whole"] == [rnd["packets"]] * one


def test_per_socket_order_is_the_hand_overs(served):
    """(a) every socket receives what was handed over for its port, in
    that order, launch after launch."""
    got = [x for rnd in served["rounds"] for x in rnd["got"]]
    assert len(got) == len(served["handed"]) == sum(ROUNDS) * (CONF - 1)
    for r, port in enumerate(served["ports"]):
        # (the retransmission leaves by the synchronous call, untapped)
        assert [p for rr, p in got if rr == r] == \
            [p for to, p in served["handed"] if to == port]


def test_nothing_compiles_whatever_the_backlog(served):
    """(b) ticks of 1 to 37 packets (one to eleven launches, of 16, 64
    and 256 rows) after the ladder: no compile event, no data-path
    recompile, nothing shed."""
    assert [r["packets"] for r in served["rounds"]] == list(ROUNDS)
    assert served["compiles"] == 0 and served["recompiles"] == 0
    h = served["health"]
    assert not h["shed"] and not h["quarantined"]


def test_a_nack_for_a_row_of_the_small_launch_is_answered(served):
    """(c) the cache holds a slab a launch, the remainder's small one
    of a class-cut tick too: the retransmission is the delivery's
    bytes."""
    n = served["nack"]
    first = [p for rnd in served["rounds"] for r, p in rnd["got"]
             if r == n["receiver"]
             and int.from_bytes(p[8:12], "big") == SSRC_BASE + n["sender"]
             and int.from_bytes(p[2:4], "big") == n["seq"]]
    assert len(first) == 1
    assert n["retransmitted"] == 1
    assert [p for r, p in n["got"] if r == n["receiver"]] == first


@pytest.mark.parametrize("k", range(len(ROUNDS)))
def test_spans_a_launch_and_the_leaves_still_tile_the_tick(served, k):
    """(f) `launches` and `class_cut` on `expand`; one `fanout_dispatch`
    / `fanout_wait` / `fanout_d2h` / `nack_cache` / `egress` a launch,
    each saying which where the tick has several; every launch
    collected, and found ready or waited for; the leaves still cover
    the round's two ticks (the one that dispatched and the one that
    collected)."""
    rnd = served["rounds"][k]
    counts, led, self_led, tick_s = rnd["tick"]
    sizes = SIZES[rnd["packets"]]
    n = len(sizes)
    rows = rnd["packets"] * (CONF - 1)
    exp = counts["expand"]
    assert exp["launches"] == n and exp["legs_max"] == CONF - 1
    assert exp["rows"] == rows == sum(sizes)
    assert exp["rows_padded"] == exp["row_class"] \
        == sum(map(_round_fanout_rows, sizes))
    assert exp["class_cut"] == _class_cut(rnd["packets"])
    # one packed plane each way a launch
    assert counts["fanout_dispatch"]["h2d_arrays"] == n
    assert counts["fanout_put"]["h2d_arrays"] == n
    assert counts["fanout_d2h"]["d2h_arrays"] == n
    assert counts["egress"]["queued"] == n
    wait = counts["fanout_wait"]
    assert wait["collected"] == n and 0 <= wait["ready"] <= n
    assert wait["hidden_us"] > 0
    assert counts["nack_cache"]["rows"] == counts["egress"]["rows"] == rows
    for stage in ("fanout_dispatch", "fanout_wait", "fanout_d2h",
                  "nack_cache", "egress"):
        # the ordinals 0..n-1 summed; a tick's only launch books none
        assert counts.get(stage, {}).get("launch", 0) == n * (n - 1) // 2
    leaves = sum(led.get(s, 0.0) for s in LEAF_STAGES
                 if s not in ("supervise", "gc"))
    assert leaves <= tick_s and leaves > 0.9 * tick_s
    # self times sum to the time inside the outermost spans.  The
    # tick that collected read no media, so it collected at its end:
    # every launch's wait, copy back, cache insert and hand-over are
    # outermost spans there (inside `reverse_chain` where the next
    # tick has media: tests/test_observability.py) (a collection of
    # the interpreter's that lands between two outermost spans is one
    # of its own)
    outside = sum(self_led.values()) - (
        led["ingress"] + led["demux"] + led["reverse_chain"]
        + led["supervise"] + sum(led[s] for s in (
            "fanout_wait", "fanout_d2h", "nack_cache", "egress")))
    assert -1e-6 <= outside <= led.get("gc", 0.0) + 1e-6


def test_metrics_count_launches_split_and_class_cut_ticks(served):
    launches, split, class_cut = served["translator"]
    # + the latch tick's one launch
    assert launches == 1 + sum(len(SIZES[p]) for p in ROUNDS)
    assert split == sum(len(SIZES[p]) > 1 for p in ROUNDS) == 6
    assert class_cut == sum(_class_cut(p) for p in ROUNDS) == 2
    text = served["metrics"]
    assert f"fanout_launches_total {launches}" in text
    assert f"fanout_split_ticks_total {split}" in text
    assert f"fanout_class_cut_ticks_total {class_cut}" in text
    # the launches by the class they were padded to (the latch tick's
    # 63 rows: one more of 64)
    by_class = {c: 0 for c in WARMED}
    for c in [64] + [_round_fanout_rows(n) for p in ROUNDS
                     for n in SIZES[p]]:
        by_class[c] += 1
    assert served["launch_rows_by_class"] == by_class
    assert sum(by_class.values()) == launches
    for c, n in by_class.items():
        assert f'fanout_launch_rows_total{{rows="{c}"}} {n}' in text
    assert 'lifecycle_admit_rejected{reason="conference_full"} 1' in text


# ------------------------------------------------------------ the room cap

def test_the_65th_member_is_refused_by_name(served):
    """(g) `max_conference_size` 64: the room's 65th join."""
    from libjitsi_tpu.service.lifecycle import ADMIT_REASONS

    assert served["refused"] == (False, "conference_full")
    assert "conference_full" in ADMIT_REASONS
    assert served["rejected"] == {"conference_full": 1}


@pytest.mark.parametrize("cap,fourth", [(3, (False, "conference_full")),
                                        (0, (True, "queued")),
                                        (4, (True, "queued"))])
def test_room_cap_is_an_admission_rule(cap, fourth):
    """A cap of 3 refuses the fourth member of a room and nobody of
    another room; 0 states nothing."""
    from libjitsi_tpu.service.lifecycle import (LifecycleConfig,
                                                StreamLifecycleManager)
    from libjitsi_tpu.service.sfu_bridge import SfuBridge

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    bridge = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                       capacity=16, recv_window_ms=0)
    try:
        lc = StreamLifecycleManager(
            bridge, config=LifecycleConfig(max_conference_size=cap))
        lc.enable_placement(1)
        keys = _keys(3, 8)
        answers = [lc.request_join(0x900 + i, _pair(keys[i, 0]),
                                   _pair(keys[i, 1]), conference=5)
                   for i in range(4)]
        assert answers[:3] == [(True, "queued")] * 3
        assert answers[3] == fourth
        assert lc.request_join(0x910, _pair(keys[4, 0]), _pair(keys[4, 1]),
                               conference=6) == (True, "queued")
    finally:
        bridge.close()


def test_a_program_without_the_room_cap_refuses_the_configuration():
    """The parent's `LifecycleConfig` (its fields, copied) does not know
    `max_conference_size`: handed the configuration file's `lifecycle`
    group as `benchmarks/sut.py` hands it over, it raises at once."""
    import dataclasses
    import json

    from libjitsi_tpu.service.lifecycle import LifecycleConfig

    with open(os.path.join(_ROOT, "benchmarks", "configs",
                           "audio-sfu-cm-10k-conf64.json")) as f:
        group = json.load(f)["lifecycle"]
    assert group["max_conference_size"] == 64
    assert LifecycleConfig(**group).max_conference_size == 64
    parent = dataclasses.make_dataclass("ParentLifecycleConfig", [
        (f.name, f.type, dataclasses.field(default=f.default))
        for f in dataclasses.fields(LifecycleConfig)
        if f.name != "max_conference_size"])
    with pytest.raises(TypeError, match="max_conference_size"):
        parent(**group)
