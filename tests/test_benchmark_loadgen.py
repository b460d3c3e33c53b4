"""The benchmark's generator, as tier-1 cases.

`benchmarks/test_loadgen.py` holds the generator's own cases (the
schedule, listeners, spurts, `analyze`, `judge`: numpy only, seconds);
they are imported here so that the repo's tier-1 run counts them.  Beside
them: the `meeting-paced` schedule of `audio-sfu-cm-10k-conf64` held to
its arithmetic: conferences of 64, 3 speakers each always on, 61
listeners a conference that send exactly one packet.
"""

import os
import sys

import numpy as np
import pytest

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
sys.path.insert(0, _BENCH)

from test_loadgen import *  # noqa: E402,F401,F403
from test_loadgen import _cell_plan, _load, loadgen  # noqa: E402

CELL = "audio-sfu-cm-10k-conf64.meeting-paced"
A = 7                   # the arithmetic below is the issue's, at A = 7


@pytest.fixture(scope="module")
def meeting():
    plan = _cell_plan(CELL)
    # the plan at the issue's A whatever knee the traffic file records
    plan["active"] = loadgen.active_conferences(160, A)
    return plan, loadgen.build_schedule(plan)


def test_meeting_cell_is_conferences_of_64_with_3_speakers(meeting):
    plan, _sched = meeting
    assert (plan["conf_size"], plan["speakers"], plan["rows"]) == \
        (64, 3, 10240)
    assert plan["talk_spurt"] is None and plan["burst_factor"] == 1.0
    assert len(plan["active"]) == A
    assert len(loadgen.plan_endpoints(plan)) == 448      # sockets
    # spread as `active_conferences` spreads them: first, last, between
    assert plan["active"][0] == 0 and plan["active"][-1] == 159


def test_meeting_rate_is_its_arithmetic(meeting):
    """21 speakers at 50 packets/s: 1,050 packets/s in, 63 deliveries a
    packet = 66,150 deliveries/s due."""
    plan, sched = meeting
    period = sched["period_ns"]
    assert period == 20_000_000
    # one whole second well inside the plan
    lo = 10 * 10 ** 9
    in_s = (sched["due_ns"] >= lo) & (sched["due_ns"] < lo + 10 ** 9)
    assert int(in_s.sum()) == 21 * 50 == 1050
    assert len(np.unique(sched["sock"][in_s])) == 21       # speakers
    assert int(in_s.sum()) * (plan["conf_size"] - 1) == 66150


def test_meeting_listeners_send_exactly_one_packet(meeting):
    plan, sched = meeting
    per_sock = np.bincount(sched["sock"], minlength=448)
    speaking = np.zeros(448, dtype=bool)
    for k in range(A):
        speaking[64 * k:64 * k + 3] = True
    assert (per_sock[~speaking] == 1).all()
    assert int((~speaking).sum()) == 61 * A
    assert (per_sock[speaking] > 2000).all()
    # every member's first packet lies in the plan's first period
    first = {}
    for s, d in zip(sched["sock"].tolist(), sched["due_ns"].tolist()):
        first.setdefault(s, d)
    assert len(first) == 448 and max(first.values()) < 20_000_000


def test_meeting_traffic_file_states_its_pitch():
    traffic = _load(_BENCH, "traffic", "meeting-paced.json")
    rate = traffic["rate"]
    knee = rate["knee_active_conferences"]["audio-sfu-cm-10k-conf64"]
    # 0.65 of the knee rounded down, or one under it by ISSUE 40's rule,
    # written as the share it is
    a = loadgen.resolve_rate(traffic, "audio-sfu-cm-10k-conf64")
    assert a == int(np.floor(rate["share_of_knee"] * knee + 1e-9))
    assert a in (int(0.65 * knee), int(0.65 * knee) - 1)
    assert traffic["speakers_per_conference"] == 3
    assert traffic["attempted"] == "offered"
    assert traffic["require_no_shedding"] is True


# ------------------------------------------ the webinar room's schedule

WEBINAR = "audio-sfu-cm-10k-webinar1k.presenter-paced"
ROOM = 512          # ISSUE 47's fallback size (PERF.md section 6, PR 47)


@pytest.fixture(scope="module")
def webinar():
    plan = _cell_plan(WEBINAR)
    return plan, loadgen.build_schedule(plan)


def test_webinar_cell_is_one_live_room_of_512_with_one_presenter(webinar):
    plan, _sched = webinar
    assert (plan["conf_size"], plan["speakers"], plan["rows"]) == \
        (ROOM, 1, 10240)
    assert plan["talk_spurt"] is None and plan["burst_factor"] == 1.0
    assert plan["active"] == [0]                   # A = 1: room 0
    assert len(loadgen.plan_endpoints(plan)) == ROOM     # sockets


def test_webinar_rate_is_its_arithmetic(webinar):
    """One presenter at 50 packets/s, 511 deliveries a packet: 25,550
    deliveries/s due, 1,022,000 a 40 s window."""
    plan, sched = webinar
    assert sched["period_ns"] == 20_000_000
    lo = 10 * 10 ** 9
    in_s = (sched["due_ns"] >= lo) & (sched["due_ns"] < lo + 10 ** 9)
    assert int(in_s.sum()) == 50
    assert set(sched["sock"][in_s].tolist()) == {0}     # the presenter
    assert int(in_s.sum()) * (plan["conf_size"] - 1) == 25550
    in_w = (sched["due_ns"] >= lo) & (sched["due_ns"] < lo + 40 * 10 ** 9)
    assert int(in_w.sum()) * (plan["conf_size"] - 1) == 1_022_000


def test_webinar_every_other_member_sends_exactly_one_packet(webinar):
    """The 7 silent panelists and the 504 visitors each reach the
    bridge once, inside the plan's first period, and then listen."""
    plan, sched = webinar
    per_sock = np.bincount(sched["sock"], minlength=ROOM)
    assert (per_sock[1:] == 1).all() and per_sock[0] > 2000
    first = {}
    for s, d in zip(sched["sock"].tolist(), sched["due_ns"].tolist()):
        first.setdefault(s, d)
    assert len(first) == ROOM and max(first.values()) < 20_000_000


def test_webinar_files_state_the_rule_and_the_pitch():
    traffic = _load(_BENCH, "traffic", "presenter-paced.json")
    assert traffic["rate"] == {"active_conferences": 1}
    assert loadgen.resolve_rate(traffic, "audio-sfu-cm-10k-webinar1k") == 1
    assert traffic["speakers_per_conference"] == 1
    assert traffic["talk_spurt"] is None
    assert traffic["attempted"] == "offered"
    assert traffic["require_no_shedding"] is True
    config = _load(_BENCH, "configs", "audio-sfu-cm-10k-webinar1k.json")
    assert config["lifecycle"] == {
        "install_batch": 64, "max_pending": 512,
        "max_conference_participants": 8}
    assert config["conference_sizes"] == [ROOM]
    assert config["egress_multiplier"] == ROOM - 1
    cm = _load(_BENCH, "configs", "audio-sfu-cm-10k.json")
    for key in ("profile", "capacity", "replay_window", "ingest_max_batch",
                "packet_period_ms", "supervisor", "bridge_class"):
        assert config[key] == cm[key], key
    assert {k: v for k, v in config["guarantees"].items()
            if k != "roles"} == cm["guarantees"]
