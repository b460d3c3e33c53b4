"""The generator's schedule and the run's checks, without a bridge.

    python3 -m pytest benchmarks/test_loadgen.py        (seconds, numpy only)

What a traffic file may now say and the run measures: fewer speakers
than members (listeners that reach the bridge with one packet and then
only receive), talk spurts (a sample that follows the schedule really
sent), and the check that fails a run whose members never latched.
Not part of the repo's tier-1 tests (`tests/`); the benchmark's own runs
never run it.
"""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import loadgen  # noqa: E402

CONFIG = {"profile": "AES_CM_128_HMAC_SHA1_80", "capacity": 64,
          "conference_sizes": [8]}

# sha256 over `sock`, `index`, `due_ns`, `due_of` of the schedule that
# commit 53fd8d5's `build_schedule` gives each cell (seed 2890390001, a
# plan of 54 s as `run.py` makes it for a 40 s window): these cells send
# what they sent before members who do not speak became reachable
PARENT_SCHEDULES = {
    "audio-sfu-cm-10k.talk-paced":
        "9bf3463793a8f04004adf5509cebfb9358ef72db5863cbf7d0a1882b12fc6ac2",
    "audio-sfu-cm-10k.talk-sat":
        "2ccfd3a02fa127bb82ecb2e6c2e3baae478b3a857abf0af68a164bab66c48be9",
    "audio-sfu-gcm-10k.talk-paced":
        "2727fd4a56f3e62dc393eed2d79c0dba0121de380105ec9f264ca0eeef466f64",
    "audio-sfu-cm-40k-mesh4.talk-paced":
        "e3ce8983b9798f181e3cc2dbaf8672ebb7e2daad9b49a03321681458936c6b0f",
}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _cell_plan(name, seed=2890390001):
    bench = _load(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == name)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = _load(ROOT, conf["file"])
    traffic = _load(HERE, "traffic", cell["traffic"] + ".json")
    a = loadgen.resolve_rate(traffic, cell["config"])
    return loadgen.make_plan(config, traffic, seed, a, 54.0,
                             sample_over_s=43.0)


def _plan(seed=5, n_active=2, duration_s=6.0, **traffic):
    return loadgen.make_plan(CONFIG, traffic, seed, n_active, duration_s)


def _events(plan):
    """What `Generator.expected_events` counts, without the children."""
    gen = loadgen.Generator.__new__(loadgen.Generator)
    gen.sched = loadgen.build_schedule(plan)
    return gen.expected_events(), gen.sched


@pytest.mark.parametrize("cell", sorted(PARENT_SCHEDULES))
def test_cells_of_the_parent_send_what_they_sent(cell):
    plan = _cell_plan(cell)
    events, sched = _events(plan)
    h = hashlib.sha256()
    for k in ("sock", "index", "due_ns", "due_of"):
        h.update(k.encode())
        h.update(str(sched[k].dtype).encode())
        h.update(str(sched[k].shape).encode())
        h.update(sched[k].tobytes())
    assert h.hexdigest() == PARENT_SCHEDULES[cell]
    # every member speaks, always: the schedule's length is the count
    # the sample's rate used to be derived from
    slots = int(np.ceil(plan["duration_s"] * 1e3 / plan["period_ms"]))
    assert events == len(plan["active"]) * plan["speakers"] * slots


def _by_socket(plan, sched):
    n_sock = len(loadgen.plan_endpoints(plan))
    return [(sched["index"][sched["sock"] == s],
             sched["due_ns"][sched["sock"] == s]) for s in range(n_sock)]


def test_every_member_reaches_the_bridge_in_the_first_period():
    plan = _plan(speakers_per_conference=2)
    sched = loadgen.build_schedule(plan)
    cs, first = plan["conf_size"], plan["first_index"]
    for s, (idx, due) in enumerate(_by_socket(plan, sched)):
        assert int((due < sched["period_ns"]).sum()) == 1
        assert idx[0] == first and 0 <= due[0] < sched["period_ns"]
        if s % cs < 2:       # a speaker: contiguous from there
            assert (idx == first + np.arange(len(idx))).all()
            assert len(idx) == sched["due_of"].shape[1]
        else:                # a listener: that one packet, no more
            assert len(idx) == 1
            assert sched["due_of"][s, 0] == due[0]
            assert (sched["due_of"][s, 1:] == -1).all()


def test_spurt_that_starts_off_sends_one_packet_and_resumes_next_index():
    plan = _plan(seed=11, n_active=8, duration_s=20.0,
                 speakers_per_conference=2,
                 talk_spurt={"on_s": 1.0, "off_s": 1.6})
    sched = loadgen.build_schedule(plan)
    period, first = sched["period_ns"], plan["first_index"]
    started_off = 0
    for s, (idx, due) in enumerate(_by_socket(plan, sched)):
        assert idx[0] == first and due[0] < period
        assert (idx == first + np.arange(len(idx))).all()
        if s % plan["conf_size"] < 2 and len(idx) > 1 \
                and due[1] - due[0] > period:
            started_off += 1     # silent from the first packet to the spurt
            assert idx[1] == first + 1
    assert started_off >= 4      # 16 speakers, half of them start off
    # a speaker talks 1.0 / 2.6 of the time: far fewer events than slots
    slots = sched["due_of"].shape[1]
    talk = len(sched["due_ns"]) - 6 * len(plan["active"])
    assert 0.2 < talk / (16 * slots) < 0.6


@pytest.mark.parametrize("traffic", [
    {}, {"speakers_per_conference": 2},
    {"speakers_per_conference": 2,
     "talk_spurt": {"on_s": 1.0, "off_s": 1.6}},
    {"speakers_per_conference": 3, "client_loss_pct": 5.0,
     "client_reorder_pct": 2.0}], ids=["all-speak", "listeners", "spurts",
                                       "lossy"])
def test_expected_events_is_the_schedules_length(traffic):
    plan = _plan(**traffic)
    events, sched = _events(plan)
    assert events == len(sched["due_ns"]) == len(sched["index"])
    assert events == int((sched["due_of"] >= 0).sum())
    # the first packet of every socket is never lost or withheld
    assert (sched["due_of"][:, 0] >= 0).all()


def test_no_latch_withholds_the_listeners_only():
    sound = loadgen.build_schedule(_plan(speakers_per_conference=2))
    plan = _plan(speakers_per_conference=2)
    plan["fault"] = "no-latch"
    broken = loadgen.build_schedule(plan)
    n_lis = 6 * len(plan["active"])
    assert len(sound["due_ns"]) - len(broken["due_ns"]) == n_lis
    assert int((broken["due_of"][:, 0] < 0).sum()) == n_lis
    spk = np.isin(sound["sock"] % 8, (0, 1))
    for k in ("sock", "index", "due_ns"):
        assert (sound[k][spk] == broken[k]).all()


def _synthetic_got(plan, sched, t0, receives):
    """Deliveries of every scheduled packet to the members `receives`
    picks of its conference, 5 ms after it was due."""
    cs = plan["conf_size"]
    eps = loadgen.plan_endpoints(plan)
    rows = []
    for s, idx, due in zip(sched["sock"].tolist(), sched["index"].tolist(),
                           sched["due_ns"].tolist()):
        c = s // cs
        for rx in range(c * cs, (c + 1) * cs):
            if rx != s and receives(rx % cs):
                t = t0 + due + 5_000_000
                rows.append((rx, loadgen.SSRC_BASE + int(eps[s]),
                             idx & 0xFFFF, loadgen.RTP_PT, 0, 100, t, t))
    return {"recs": np.array(rows, dtype=loadgen.REC),
            "late_ns": np.zeros(1, dtype=np.int64), "samples": [],
            "sender": {"send_errors": 0, "sent": len(sched["due_ns"])},
            "receivers": [{"rx_drops": 0, "overflow": False}]}


@pytest.mark.parametrize("speakers,listeners_receive", [
    (2, True), (2, False), (3, False)])
def test_analyze_counts_the_listeners_deliveries(speakers,
                                                 listeners_receive):
    plan = _plan(speakers_per_conference=speakers)
    sched = loadgen.build_schedule(plan)
    t0 = 1_000_000_000_000
    got = _synthetic_got(
        plan, sched, t0,
        lambda member: listeners_receive or member < speakers)
    w0, w1 = t0 + int(1e9), t0 + int(5e9)
    res = loadgen.analyze(plan, sched, got, t0, w0, w1, int(1e9))
    cs = plan["conf_size"]
    slots_in_w = 4 * 50
    assert res["offered"] == 2 * speakers * slots_in_w * (cs - 1)
    assert res["foreign"] == res["duplicates"] == res["unknown"] == 0
    if listeners_receive:
        assert res["lost"] == 0
    else:
        # the parent's defect, now the control `no-latch`
        assert res["lost"] * (cs - 1) == res["offered"] * (cs - speakers)


def _judge(unlatched):
    import run

    win = {"unlatched_members": unlatched,
           "counters": {"compile_events": 0, "datapath_recompiles": 0},
           "after": dict.fromkeys(("shed", "quarantined", "level",
                                   "quarantine_total", "refused"), 0)}
    client = {"foreign": 0, "duplicates": 0, "unknown": 0,
              "rx_overflow": False}
    sample = {"checked": 5000, "bad_tag": 0, "bad_bytes": 0}
    return run.judge({}, win, client, sample)


@pytest.mark.parametrize("unlatched", [0, 1])
def test_judge_fails_on_a_member_that_never_latched(unlatched, capsys):
    checks = _judge(unlatched)
    capsys.readouterr()
    failed = [c[0] for c in checks if not c[4]]
    assert failed == (["unlatched_members"] if unlatched else [])
    assert checks[0][:4] == ("unlatched_members", unlatched, "==", 0)


def test_latency_metric_takes_a_suffix_as_the_rate_does():
    import run

    bench = {"end_to_end": [
        {"name": n, "unit": "ms"} for n in
        ("added_latency_p95_ms", "added_latency_p95_ms.burst", "setup_s")]}
    lat = np.arange(1, 101, dtype=np.int64) * 1_000_000
    res = {"client": {"latency_ns": lat, "delivered_in_window": 0},
           "seconds": 1.0}
    out = run.e2e_values([m["name"] for m in bench["end_to_end"]], res,
                         3.0, bench)
    assert out["added_latency_p95_ms"]["value"] == \
        out["added_latency_p95_ms.burst"]["value"] == \
        pytest.approx(np.percentile(lat, 95) / 1e6)
    assert out["setup_s"]["value"] == 3.0


def test_the_new_readers_read_the_ticks():
    import run

    rx = np.array([0, 10, 20, 30, 40, 0, 50], dtype=np.int64)
    ctx = {"ticks": {"rx": rx, "tick_s": rx / 1e3, "stage": {}}}
    layer = [{"name": "tick_p95_ms.paced", "unit": "ms"},
             {"name": "ingress_pkts_per_tick_p95.paced",
              "unit": "packets"}]
    out = run.layer_values(layer, ctx)
    busy = rx[rx > 0]
    assert out["ingress_pkts_per_tick_p95.paced"]["value"] == \
        pytest.approx(np.percentile(busy, 95))
    assert out["tick_p95_ms.paced"]["value"] == \
        pytest.approx(np.percentile(busy, 95))
    ctx["ticks"]["rx"] = np.zeros(3, dtype=np.int64)
    ctx["ticks"]["tick_s"] = np.zeros(3)
    assert run.layer_values(layer, ctx) == {}
