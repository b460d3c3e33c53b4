"""Observability plane: Histogram bucket semantics, the exposition
validator, TimingRing reentrancy, label escaping, callable array
sources (stale-array regression), PipelineTracer ledgers, the flight
recorder, and the HTTP server — plus a slow soak twin of
scripts/obs_smoke.py.
"""

import json
import types
import urllib.request

import numpy as np
import pytest

from libjitsi_tpu.service.obs_server import ObservabilityServer
from libjitsi_tpu.service.supervisor import (BridgeSupervisor,
                                             SupervisorConfig)
from libjitsi_tpu.utils.flight import FlightRecorder
from libjitsi_tpu.utils.metrics import (Histogram, MetricsRegistry,
                                        TimingRing, count_exemplars,
                                        escape_label_value,
                                        exponential_buckets,
                                        validate_exposition)
from libjitsi_tpu.utils.tracing import PipelineTracer


# ------------------------------------------------------------ histogram

def test_histogram_bucket_boundaries_are_inclusive():
    h = Histogram((1.0, 2.0, 5.0))
    for v in (0.5, 1.0, 1.5, 2.0, 5.0, 99.0):
        h.observe(v)
    # le semantics: 1.0 lands in the le="1" bucket, 5.0 in le="5"
    assert h.bucket_counts.tolist() == [2, 2, 1, 1]
    assert h.cumulative().tolist() == [2, 4, 5, 6]
    assert h.count == 6
    assert h.sum == pytest.approx(0.5 + 1.0 + 1.5 + 2.0 + 5.0 + 99.0)


def test_histogram_vectorized_fill_matches_scalar_loop():
    rng = np.random.default_rng(7)
    vals = rng.exponential(0.05, size=2000)
    buckets = exponential_buckets(0.001, 2.0, 10)
    ha, hb = Histogram(buckets), Histogram(buckets)
    ha.observe_array(vals)
    for v in vals:
        hb.observe(float(v))
    assert ha.bucket_counts.tolist() == hb.bucket_counts.tolist()
    assert ha.count == hb.count == 2000
    assert ha.sum == pytest.approx(hb.sum)


def test_histogram_rejects_empty_and_infinite_buckets():
    with pytest.raises(ValueError):
        Histogram(())
    with pytest.raises(ValueError):
        Histogram((1.0, float("inf")))


def test_histogram_render_is_cumulative_with_inf_bucket():
    m = MetricsRegistry()
    h = m.histogram("pkt_bytes", (100, 200), help_="sizes")
    h.observe_array(np.array([50.0, 150.0, 150.0, 999.0]))
    text = m.render()
    assert "# TYPE libjitsi_tpu_pkt_bytes histogram" in text
    assert 'libjitsi_tpu_pkt_bytes_bucket{le="100"} 1' in text
    assert 'libjitsi_tpu_pkt_bytes_bucket{le="200"} 3' in text
    assert 'libjitsi_tpu_pkt_bytes_bucket{le="+Inf"} 4' in text
    assert "libjitsi_tpu_pkt_bytes_count 4" in text
    assert validate_exposition(text) == []


def test_registry_histogram_factory_is_create_or_get():
    m = MetricsRegistry()
    a = m.histogram("x", (1, 2))
    b = m.histogram("x", (5, 6))          # existing wins; buckets kept
    assert a is b
    assert a.uppers.tolist() == [1.0, 2.0]


# ------------------------------------------------------------ exemplars

def test_histogram_exemplar_slots_last_wins_and_tail_signal():
    h = Histogram((0.01, 0.1), exemplars=True)
    assert h.observe(0.005, exemplar={"trace_id": "1"}) is False
    assert h.observe(0.007, exemplar={"trace_id": "2"}) is False
    assert h.observe(5.0, exemplar={"trace_id": "3"}) is True  # +Inf
    assert h.exemplars[0][0] == {"trace_id": "2"}   # last wins
    assert h.exemplars[0][1] == pytest.approx(0.007)
    assert h.exemplars[-1][0] == {"trace_id": "3"}
    assert h.exemplars[1] is None                   # untouched slot
    # observe_same spreads n observations, one exemplar
    assert h.observe_same(0.05, 4, exemplar={"trace_id": "4"}) is False
    assert h.exemplars[1][0] == {"trace_id": "4"}


def test_exemplars_render_only_on_openmetrics():
    m = MetricsRegistry()
    h = m.histogram("journey_seconds", (0.01, 0.1), exemplars=True)
    h.observe(0.005, exemplar={"trace_id": "42"})
    plain = m.render()
    om = m.render(openmetrics=True)
    assert count_exemplars(plain) == 0
    assert count_exemplars(om) == 1
    assert '# {trace_id="42"} 0.005' in om
    assert om.rstrip().endswith("# EOF")
    assert validate_exposition(plain) == []
    assert validate_exposition(om, openmetrics=True) == []


@pytest.mark.parametrize("breakage,needle", [
    # exemplar allowed on _bucket lines only
    ('# TYPE h histogram\nh_bucket{le="1"} 1\nh_bucket{le="+Inf"} 1\n'
     'h_sum 1\nh_count 1 # {t="1"} 0.5\n# EOF\n', "bucket"),
    # exemplar label set over the 128-rune OpenMetrics cap
    ('# TYPE h histogram\nh_bucket{le="1"} 1 # {t="' + "x" * 140
     + '"} 0.5\nh_bucket{le="+Inf"} 1\nh_sum 1\nh_count 1\n# EOF\n',
     "128"),
    # exemplar value must be numeric
    ('# TYPE h histogram\nh_bucket{le="1"} 1 # {t="1"} oops\n'
     'h_bucket{le="+Inf"} 1\nh_sum 1\nh_count 1\n# EOF\n', "numeric"),
    # OpenMetrics requires the EOF terminator, last
    ('# TYPE g gauge\ng 1\n', "# EOF"),
])
def test_openmetrics_validator_rejects_seeded_breakage(breakage, needle):
    errors = validate_exposition(breakage, openmetrics=True)
    assert errors and any(needle in e for e in errors), errors


def test_exemplar_in_plain_exposition_is_a_violation():
    text = ('# TYPE h histogram\nh_bucket{le="1"} 1 # {t="1"} 0.5\n'
            'h_bucket{le="+Inf"} 1\nh_sum 1\nh_count 1\n')
    errors = validate_exposition(text)     # 0.0.4 format: no exemplars
    assert errors and any("exemplar" in e.lower() for e in errors)


# ------------------------------------------------------------ validator

def test_validator_accepts_full_registry_render():
    m = MetricsRegistry()
    m.register_array("rx", np.array([1, 2, 3]), help_="per stream",
                     kind="counter")
    m.register_scalar("up", lambda: 1)
    m.histogram("sizes", (10, 100)).observe_array(
        np.array([5.0, 50.0, 500.0]))
    ring = m.timing("stage_ingress")
    for v in (0.001, 0.002, 0.003):
        ring.record(v)
    assert validate_exposition(m.render()) == []


@pytest.mark.parametrize("text,needle", [
    # buckets must be cumulative
    ('# TYPE h histogram\nh_bucket{le="1"} 5\nh_bucket{le="2"} 3\n'
     'h_bucket{le="+Inf"} 5\nh_sum 1\nh_count 5\n', "cumulative"),
    # +Inf bucket required
    ('# TYPE h histogram\nh_bucket{le="1"} 2\nh_sum 1\nh_count 2\n',
     '+Inf'),
    # +Inf must equal _count
    ('# TYPE h histogram\nh_bucket{le="1"} 1\nh_bucket{le="+Inf"} 2\n'
     'h_sum 1\nh_count 3\n', "_count"),
    # _sum required
    ('# TYPE h histogram\nh_bucket{le="1"} 1\nh_bucket{le="+Inf"} 1\n'
     'h_count 1\n', "_sum"),
    # every family typed exactly once
    ('# TYPE g gauge\n# TYPE g gauge\ng 1\n', "duplicate"),
    # samples without a TYPE line
    ('untyped_metric 4\n', "no # TYPE"),
    # summary quantiles must be numeric in [0, 1]
    ('# TYPE s summary\ns{quantile="p99"} 1\ns_sum 1\ns_count 1\n',
     "quantile"),
])
def test_validator_rejects_seeded_breakage(text, needle):
    errors = validate_exposition(text)
    assert errors and any(needle in e for e in errors), errors


# ------------------------------------------------- timing-ring reentrancy

def test_timing_ring_nested_with_blocks_record_both():
    ring = TimingRing()
    with ring:
        with ring:                       # reentrant: inner must not
            pass                         # clobber the outer's t0
    assert ring.count == 2
    durations = ring._buf[:2]
    assert durations[1] >= durations[0]  # outer (recorded 2nd) >= inner


def test_timing_ring_overlapping_span_tokens():
    ring = TimingRing()
    a = ring.span()
    b = ring.span()                      # overlapping, non-LIFO
    a.stop()
    b.stop()
    assert ring.count == 2
    assert a.stop() == a.seconds         # idempotent stop


# -------------------------------------------------------------- escaping

def test_hostile_label_values_are_escaped():
    hostile = 'pwn" } 1\nfake_metric{x="y'
    esc = escape_label_value(hostile)
    assert "\n" not in esc and '"' not in esc.replace('\\"', "")
    m = MetricsRegistry()
    m.register_array("rx", np.array([7]), by="stream")
    m.set_stream_name(0, hostile)
    text = m.render()
    assert hostile not in text
    assert validate_exposition(text) == []
    # the escaped value round-trips through the parser
    from libjitsi_tpu.utils.metrics import parse_exposition
    _types, samples, errors = parse_exposition(text)
    assert not errors
    byname = {n: lab for n, lab, _v in samples}
    assert byname["libjitsi_tpu_rx"]["name"] == hostile


def test_hostile_help_text_is_escaped():
    m = MetricsRegistry()
    m.register_scalar("up", lambda: 1,
                      help_="line1\nline2 \\ backslash")
    text = m.render()
    assert "# HELP libjitsi_tpu_up line1\\nline2 \\\\ backslash" in text
    assert validate_exposition(text) == []


# ------------------------------------- callable sources (stale arrays)

CAP = 8


class _DummyLoop:
    def __init__(self):
        self.registry = types.SimpleNamespace(capacity=CAP)
        self.recv_window_ms = 1
        self.inbound_drop = np.zeros(CAP, dtype=bool)
        self.inbound_dropped = np.zeros(CAP, dtype=np.int64)
        self.inbound_dropped_total = 0


class _DummyBridge:
    def __init__(self):
        self.loop = _DummyLoop()
        self.degraded = False
        self._ssrc_of = {}
        self.rx_table = types.SimpleNamespace(
            auth_fail=np.zeros(CAP, dtype=np.int64),
            replay_reject=np.zeros(CAP, dtype=np.int64))
        self.speaker = types.SimpleNamespace(dominant=0)

    def tick(self, now=None):
        return {"rx": 0}


def test_register_array_accepts_callable_source():
    m = MetricsRegistry()
    holder = {"arr": np.array([1, 2])}
    m.register_array("live", lambda: holder["arr"], kind="counter")
    assert 'libjitsi_tpu_live{stream="0"} 1' in m.render()
    holder["arr"] = np.array([9, 9])     # rebind, not mutate
    assert 'libjitsi_tpu_live{stream="0"} 9' in m.render()


def test_supervisor_scrape_survives_table_rebind():
    """Chaos-style kill/restore regression: the exporter must follow
    the supervisor's CURRENT bridge objects, not the arrays captured at
    registration time (the stale-array bug)."""
    reg = MetricsRegistry()
    bridge = _DummyBridge()
    sup = BridgeSupervisor(bridge, SupervisorConfig(deadline_ms=1000.0),
                           metrics=reg)
    bridge.rx_table.auth_fail[3] = 2
    assert 'libjitsi_tpu_srtp_auth_fail{stream="3"} 2' in reg.render()
    # "restore": a whole new table object, as recover() produces
    bridge.rx_table = types.SimpleNamespace(
        auth_fail=np.zeros(CAP, dtype=np.int64),
        replay_reject=np.zeros(CAP, dtype=np.int64))
    bridge.rx_table.auth_fail[3] = 41
    text = reg.render()
    assert 'libjitsi_tpu_srtp_auth_fail{stream="3"} 41' in text, \
        "exporter kept reading the pre-restore array"
    assert sup is not None


# --------------------------------------------------------------- tracer

def test_tracer_feeds_rings_and_ledger():
    m = MetricsRegistry()
    tr = PipelineTracer(m, annotate=False)
    with tr.span("ingress"):
        with tr.span("recovery"):        # nested spans both record
            pass
    assert m.timings["stage_ingress"].count == 1
    assert m.timings["stage_recovery"].count == 1
    led = tr.take_ledger()
    assert set(led) == {"ingress", "recovery"}
    assert led["ingress"] >= led["recovery"] >= 0.0
    assert tr.last_ledger == led
    assert tr.take_ledger() == {}        # drained
    stage, secs = PipelineTracer.dominant(led)
    assert stage == "ingress" and secs == led["ingress"]
    assert PipelineTracer.dominant({}) == (None, 0.0)


def _spin(seconds):
    import time
    t_end = time.perf_counter() + seconds
    while time.perf_counter() < t_end:
        pass


def test_tracer_self_time_of_nested_and_twice_entered_spans():
    """Self time is a span's time less its children's: self times of a
    tick sum to the outermost span's inclusive time, the inclusive
    ledger is what it always was, and a stage entered twice sums in
    both."""
    tr = PipelineTracer(MetricsRegistry(), annotate=False)
    tr.tick = 41
    with tr.span("reverse_chain") as outer:
        _spin(0.002)
        with tr.span("unprotect") as mid:
            for _ in range(2):              # once per size class
                with tr.span("unprotect_wait") as leaf:
                    _spin(0.002)
                assert leaf.parent is mid and leaf.tick == 41
        assert mid.parent is outer and outer.parent is None
        with tr.span("forward_chain"):
            _spin(0.001)
    led = tr.take_ledger()
    own, self_led = dict(led), tr.last_self_ledger
    assert set(led) == set(self_led) == {
        "reverse_chain", "unprotect", "unprotect_wait", "forward_chain"}
    assert sum(self_led.values()) == pytest.approx(led["reverse_chain"])
    assert self_led["unprotect_wait"] == led["unprotect_wait"] >= 0.004
    assert self_led["forward_chain"] == led["forward_chain"]
    assert self_led["reverse_chain"] == pytest.approx(
        led["reverse_chain"] - led["unprotect"] - led["forward_chain"])
    assert 0.0 <= self_led["unprotect"] < led["unprotect_wait"]
    # the container's inclusive time still holds everything inside it
    assert led["reverse_chain"] >= led["unprotect"] \
        >= led["unprotect_wait"]
    assert PipelineTracer.dominant(self_led)[0] == "unprotect_wait"
    assert PipelineTracer.dominant(led)[0] == "reverse_chain"
    assert tr.last_ledger == own and tr.take_ledger() == {}
    assert tr.last_self_ledger == {}        # drained with the first


def test_tracer_fold_adds_late_spans_to_the_drained_tick():
    tr = PipelineTracer(MetricsRegistry(), annotate=False)
    with tr.span("egress", rows=3):
        pass
    led = tr.take_ledger()
    with tr.span("supervise"):
        with tr.span("gc", generation=2):
            pass
    with tr.span("egress", rows=2):
        pass
    before = led["egress"]
    assert tr.take_ledger(fold=True) is led     # in place: same dicts
    assert set(led) == {"egress", "supervise", "gc"}
    assert led["egress"] > before
    assert tr.last_self_ledger["supervise"] == pytest.approx(
        led["supervise"] - led["gc"])
    assert tr.last_counts == {"egress": {"rows": 5},
                              "gc": {"generation": 2}}
    assert tr.take_ledger() == {}


def test_tracer_counts_reach_ledger_and_profiler_stats(tmp_path):
    """`span(stage, **counts)` and `note()` land in the counts ledger
    and, inside a profiler session, as stats of the host event, beside
    the tick id every span of the tick carries."""
    import glob

    import jax
    from jax.profiler import ProfileData

    tr = PipelineTracer(MetricsRegistry())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tr.tick_root(7, wall_ns=123) as root:
            with tr.span("expand", rows=594) as sp:
                sp.note(rows_padded=1024, width=256)
            with tr.span("expand", rows=6):
                pass
            root.set_metadata(rx=85)
    finally:
        jax.profiler.stop_trace()
    tr.take_ledger()
    assert tr.last_counts == {
        "expand": {"rows": 600, "rows_padded": 1024, "width": 256}}
    assert "tick" not in tr.last_ledger         # the root books nothing
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    got = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("stage:"):
                    got.setdefault(ev.name, []).append(dict(ev.stats))
    assert got["stage:tick"] == [{"tick": 7, "wall_ns": 123, "rx": 85}]
    assert got["stage:expand"] == [
        {"tick": 7, "rows": 594, "rows_padded": 1024, "width": 256},
        {"tick": 7, "rows": 6}]


def test_sfu_tick_emits_every_leaf_and_self_times_tile_it(
        sfu_with_traffic):
    """One steady SfuBridge tick with traffic (it collects the fan-out
    the tick before it dispatched, then dispatches its own) opens every
    leaf stage, and `tracing.LEAF_STAGES` is exactly that set (with
    `gc`, which the next test forces); self times sum to the outermost
    spans' time: the collection lies inside `reverse_chain` too."""
    import gc

    from libjitsi_tpu.utils import tracing

    sfu, sup, send = sfu_with_traffic
    gc.disable()                   # no `gc` span lands in this tick
    try:
        send.until_forwarded()
        led, self_led = sup.last_ledger, sup.last_self_ledger
        containers = set(tracing.CONTAINER_STAGES)
        assert set(led) - containers == set(tracing.LEAF_STAGES) - {"gc"}
        assert set(led) >= containers
        assert set(tracing.STAGES) >= set(led)
        # a leaf has no child: both ledgers agree on it
        for stage in set(led) - containers:
            assert self_led[stage] == led[stage], stage
        # the device seam: `unprotect_wait` is a container now, tiled
        # by its dispatch, block and copy-back (its own lines are a
        # few span entries); each dispatch holds its put
        assert self_led["unprotect_wait"] < 0.2 * led["unprotect_wait"]
        assert led["unprotect_wait"] >= (
            led["unprotect_dispatch"] + led["unprotect_block"]
            + led["unprotect_d2h"]) >= 0.8 * led["unprotect_wait"]
        for seam in ("unprotect", "fanout"):
            assert 0.0 < led[seam + "_put"] < led[seam + "_dispatch"]
            assert self_led[seam + "_dispatch"] == pytest.approx(
                led[seam + "_dispatch"] - led[seam + "_put"])
        assert sum(self_led.values()) == pytest.approx(
            led["ingress"] + led["demux"] + led["reverse_chain"]
            + led["supervise"])
        counts = sup.last_counts
        assert counts["demux"]["rows"] == 3
        assert counts["unprotect_wait"]["rows"] == 3
        assert counts["unprotect_wait"]["rows_padded"] >= 3
        assert counts["route"]["packets"] == 3
        assert counts["expand"]["rows"] == 6
        assert counts["expand"]["rows_padded"] >= 6
        assert counts["fanout_dispatch"]["h2d_bytes"] > 0
        assert counts["fanout_d2h"]["d2h_bytes"] > 0
        # the launch collected says what the collection found
        wait = counts["fanout_wait"]
        assert wait["collected"] == 1 and wait["ready"] in (0, 1)
        assert wait["hidden_us"] > 0
        # a put books what its call books, where it happens
        assert counts["unprotect_put"] == {
            k: counts["unprotect_wait"][k]
            for k in ("h2d_arrays", "h2d_bytes")}
        assert counts["unprotect_d2h"] == {
            k: counts["unprotect_wait"][k]
            for k in ("d2h_arrays", "d2h_bytes")}
        assert counts["fanout_put"] == counts["fanout_dispatch"]
        assert counts["nack_cache"]["rows"] == 6
        assert counts["egress"]["rows"] == 6
        assert counts["egress"]["bytes"] > 6 * 12
        assert "gc_pause_seconds_total" in sfu.loop.metrics.render()
    finally:
        gc.enable()


def test_sfu_warm_tick_is_two_launches_one_array_each_way(
        sfu_with_traffic, warmed_launch_guard):
    """A warmed SfuBridge tick with traffic stages ONE array for the
    unprotect and one for the fan-out, copies one back from each,
    compiles nothing and starts no `convert_element_type` program."""
    import time

    sfu, sup, send = sfu_with_traffic
    send.until_forwarded()
    send.until_forwarded()           # the forwarding tick's shapes: warm
    sfu.flush_egress()               # nothing in flight, all counted
    before = sfu.forwarded
    send()                           # the clients' own protect: outside
    time.sleep(0.01)
    sup.tick(now=50.0)               # dispatches a fan-out
    send()
    time.sleep(0.01)
    with warmed_launch_guard():
        sup.tick(now=50.0)           # collects it, dispatches the next
    sfu.flush_egress()               # `forwarded` counts at the reap
    assert sfu.forwarded == before + 12
    counts = sup.last_counts
    up, down = counts["unprotect_wait"], counts["fanout_d2h"]
    assert up["h2d_arrays"] == up["d2h_arrays"] == 1
    assert up["h2d_bytes"] == up["d2h_bytes"] == 16 * (192 + 32 + 32)
    assert counts["fanout_dispatch"] == {"h2d_arrays": 1,
                                         "h2d_bytes": up["h2d_bytes"]}
    assert down == {"d2h_arrays": 1, "d2h_bytes": up["h2d_bytes"]}


def test_sfu_tick_spans_in_profile_match_the_ledger(sfu_with_traffic,
                                                    tmp_path):
    """Read back through the benchmark's `xstats`: every `stage:*`
    event of a profiled tick carries a tick id, the root its `wall_ns`;
    the leaf durations that lie INSIDE a tick's root on the profiler's
    clock agree with that tick's ledger within 2 % (a span is the time
    of the tick that runs it); and the spans that collect a fan-out
    (`fanout_wait`, `fanout_d2h`, `nack_cache`, `egress`) carry the id
    of the tick that DISPATCHED it, the one before, so a reader that
    joins by `tick` pairs a dispatch with its own wait."""
    import os
    import sys
    import time

    import jax

    from libjitsi_tpu.utils import tracing

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "benchmarks"))
    import xstats

    sfu, sup, send = sfu_with_traffic
    send.until_forwarded()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    ledgers = {}
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            send()
            time.sleep(0.01)
            t_wall = time.time_ns()
            with jax.profiler.TraceAnnotation("bench:tick"):
                sup.tick(now=50.0)
            ledgers[sfu.loop.trace_id] = (dict(sup.last_ledger),
                                          dict(sup.last_counts), t_wall)
    finally:
        jax.profiler.stop_trace()
    import reduce
    path = reduce.find_xplane(str(tmp_path))
    evs = xstats.load(path)["host"]
    assert evs and all("tick" in st for _n, _s, _d, st in evs)
    roots = [(s, s + d, st) for n, s, d, st in evs if n == "stage:tick"]
    assert [st["tick"] for _s, _e, st in roots] == sorted(ledgers)
    leaves = [k for k in tracing.LEAF_STAGES if k not in ("supervise",
                                                          "gc")]
    collection = ("fanout_wait", "fanout_d2h", "nack_cache", "egress")
    for lo, hi, root in roots:
        led, counts, t_wall = ledgers[root["tick"]]
        assert root["rx"] == 3
        assert 0 <= root["wall_ns"] - t_wall < 50e6
        mine = [(n.split(":")[1], d, st) for n, s, d, st in evs
                if lo <= s < hi and n != "stage:tick"]
        by_profile = sum(d for n, d, _st in mine if n in leaves) / 1e9
        by_ledger = sum(led[k] for k in leaves)
        assert by_profile == pytest.approx(by_ledger, rel=0.02)
        for n, _d, st in mine:
            assert st["tick"] == root["tick"] - (n in collection), n
        (exp,) = [st for n, _d, st in mine if n == "expand"]
        assert exp["rows"] == counts["expand"]["rows"] == 6
        (wait,) = [st for n, _d, st in mine if n == "fanout_wait"]
        assert wait["collected"] == 1 and wait["ready"] in (0, 1)
        assert wait["hidden_us"] == counts["fanout_wait"]["hidden_us"] > 0
    ctx = {"trace": {"xplane": path}}
    assert xstats.count_ratio_pct(ctx, "fanout_wait", "ready",
                                  "collected") in (
        pytest.approx(0.0), pytest.approx(100.0 / 3),
        pytest.approx(200.0 / 3), pytest.approx(100.0))
    ctx = {"trace": {"xplane": path}}
    assert xstats.count_ratio_pct(ctx, "expand", "rows", "rows_padded") \
        == pytest.approx(600.0 / exp["rows_padded"])
    # from the batch in hand to the last datagram handed over, joined
    # by `tick`: across the tick boundary, so over a whole
    # `reverse_chain` and the pause between two ticks
    assert xstats.residence_p50_ms(ctx) > 10.0 + 1e3 * min(
        led["reverse_chain"] for led, _c, _t in ledgers.values())
    assert xstats.scope_share_pct(ctx, "jit__fanout_protect", "auth") \
        is None                     # no device plane off the chip
    assert xstats.slice_events({"trace": None}) is None


def test_sfu_tick_gc_span_and_hook_removed_by_close(sfu_with_traffic):
    import gc

    sfu, sup, send = sfu_with_traffic
    n_hooks = len(gc.callbacks)
    send.until_forwarded()
    before = sup.gc_pause_s
    gc.collect()
    assert sup.gc_pause_s > before
    sup.tick(now=50.0)
    assert sup.last_ledger["gc"] > 0.0
    assert sup.last_counts["gc"]["generation"] == 2
    sup.close()
    assert len(gc.callbacks) == n_hooks - 1
    paused = sup.gc_pause_s
    gc.collect()
    assert sup.gc_pause_s == paused         # the hook is gone


def test_supervisor_gc_hook_goes_with_the_supervisor():
    import gc

    class _Loop:
        registry = types.SimpleNamespace(capacity=CAP)
        tracer = PipelineTracer(MetricsRegistry(), annotate=False)

    n_hooks = len(gc.callbacks)
    sup = BridgeSupervisor(types.SimpleNamespace(loop=_Loop()))
    assert len(gc.callbacks) == n_hooks + 1
    del sup
    gc.collect()
    assert len(gc.callbacks) == n_hooks


# ------------------------------------------------------ flight recorder

def test_flight_recorder_rings_are_bounded_and_ordered():
    fr = FlightRecorder(per_stream=4, global_events=3)
    for i in range(10):
        fr.record("x", sid=1, tick=i)
        fr.record("g", tick=i)
    d = fr.dump(1)
    assert len(d["events"]) == 4
    assert [e["tick"] for e in d["events"]] == [6, 7, 8, 9]
    assert len(d["global"]) == 3
    seqs = [e["seq"] for e in d["events"]]
    assert seqs == sorted(seqs)          # merged-timeline ordering
    assert fr.events_recorded == 20
    assert fr.streams() == [1]
    fr.clear(1)
    assert fr.dump(1)["events"] == []


def test_flight_recorder_header_sampling_is_capped_spread():
    """Default sampling is a deterministic stride reservoir: capped at
    max_headers rows, spread over the burst, ALWAYS including the last
    row (the old first-N sampling was blind to burst tails)."""
    fr = FlightRecorder(max_headers=3)
    sids = [5] * 10 + [6]
    seqs = list(range(100, 110)) + [777]
    lens = [60] * 11
    fr.record_headers(sids, seqs, lens, tick=2, trace=9)
    ev5 = fr.dump(5)["events"][0]
    assert ev5["kind"] == "hdr" and ev5["n"] == 3
    assert ev5["total"] == 10 and ev5["mode"] == "spread"
    assert ev5["trace"] == 9
    assert ev5["headers"][0] == [100, 60]     # first row kept
    assert ev5["headers"][-1] == [109, 60]    # last row ALWAYS kept
    assert fr.dump(6)["events"][0]["headers"] == [[777, 60]]


def test_flight_recorder_burst_tail_regression():
    """A 1k-packet burst must leave at least one header from the burst
    TAIL on record — both in spread mode (stride reservoir includes the
    final row) and, for a priority-marked stream, the full tail."""
    fr = FlightRecorder(max_headers=16)
    n = 1000
    sids = [3] * n
    seqs = list(range(n))
    lens = [60] * n
    fr.record_headers(sids, seqs, lens, tick=0)
    ev = fr.dump(3)["events"][-1]
    tail_seqs = set(range(n - 16, n))
    assert any(h[0] in tail_seqs for h in ev["headers"]), \
        "spread sample kept nothing from the burst tail"
    assert ev["headers"][-1][0] == n - 1

    # priority mark (set by a journey-tail overflow or a NACK/RTX/FEC
    # event) biases the NEXT sample to the whole tail, then clears
    fr.mark_priority(3)
    fr.record_headers(sids, seqs, lens, tick=1)
    ev = fr.dump(3)["events"][-1]
    assert ev["mode"] == "tail"
    assert [h[0] for h in ev["headers"]] == list(range(n - 16, n))
    fr.record_headers(sids, seqs, lens, tick=2)   # mark consumed
    assert fr.dump(3)["events"][-1]["mode"] == "spread"


def test_flight_recorder_priority_kinds_mark_stream():
    """NACK/RTX/FEC events auto-mark their stream: the next header
    sample keeps the burst tail the event is about."""
    fr = FlightRecorder(max_headers=2)
    fr.record("rtx_served", sid=7, tick=0, seq=55)
    fr.record_headers([7] * 5, [10, 11, 12, 13, 14], [60] * 5, tick=1)
    ev = fr.dump(7)["events"][-1]
    assert ev["mode"] == "tail"
    assert [h[0] for h in ev["headers"]] == [13, 14]


def test_flight_dump_is_json_serializable():
    fr = FlightRecorder()
    fr.record("q", sid=np.int64(3), tick=np.int32(1),
              n=np.int64(5))
    json.dumps(fr.dump(3))               # plain dicts by construction


# ------------------------------------------------------------ http server

def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5) as r:
        return r.status, r.read().decode("utf-8")


def test_obs_server_serves_metrics_health_and_debug():
    m = MetricsRegistry()
    m.register_scalar("up", lambda: 1)
    fr = FlightRecorder()
    fr.record("hdr", sid=4, tick=0, n=1, headers=[[10, 60]])
    sup = types.SimpleNamespace(
        health=lambda: {"state": "healthy"}, flight=fr, postmortems=[])
    with ObservabilityServer(metrics=m, supervisor=sup) as srv:
        code, text = _get(srv.port, "/metrics")
        assert code == 200 and "libjitsi_tpu_up 1" in text
        assert validate_exposition(text) == []
        code, body = _get(srv.port, "/healthz")
        assert code == 200 and json.loads(body)["ok"]
        code, body = _get(srv.port, "/debug/streams")
        assert json.loads(body)["streams"] == [4]
        code, body = _get(srv.port, "/debug/streams/4")
        assert code == 200
        assert json.loads(body)["events"][0]["kind"] == "hdr"
        code, body = _get(srv.port, "/debug/postmortems")
        assert code == 200 and json.loads(body) == []


def test_obs_server_negotiates_openmetrics_and_serves_slo():
    from libjitsi_tpu.utils.slo import SloEngine, SloSpec

    m = MetricsRegistry()
    h = m.histogram("journey_seconds", (0.01, 0.1), exemplars=True)
    h.observe(0.005, exemplar={"trace_id": "7"})
    state = {"bad": 1.0, "total": 100.0}
    m.register_scalar("bad_things", lambda: state["bad"],
                      kind="counter")
    m.register_scalar("all_things", lambda: state["total"],
                      kind="counter")
    slo = SloEngine(m, [SloSpec("r", objective=0.99,
                                bad_metric="bad_things",
                                total_metric="all_things")])
    slo.on_tick()
    sup = types.SimpleNamespace(
        health=lambda: {"state": "healthy"}, flight=None,
        postmortems=[])
    with ObservabilityServer(metrics=m, supervisor=sup,
                             slo=slo) as srv:
        # plain scrape: 0.0.4 content type, no exemplars
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/metrics")
        with urllib.request.urlopen(req, timeout=5) as r:
            plain, ctype = r.read().decode("utf-8"), \
                r.headers.get("Content-Type", "")
        assert "text/plain" in ctype
        assert count_exemplars(plain) == 0
        # Accept negotiation flips to OpenMetrics: exemplars + # EOF
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/metrics",
            headers={"Accept":
                     "application/openmetrics-text; version=1.0.0"})
        with urllib.request.urlopen(req, timeout=5) as r:
            om, ctype = r.read().decode("utf-8"), \
                r.headers.get("Content-Type", "")
        assert "application/openmetrics-text" in ctype
        assert validate_exposition(om, openmetrics=True) == []
        assert count_exemplars(om) == 1 and 'trace_id="7"' in om
        # /debug/slo mirrors SloEngine.status()
        code, body = _get(srv.port, "/debug/slo")
        doc = json.loads(body)
        assert code == 200 and doc["ticks"] == 1
        assert doc["slos"][0]["name"] == "r"


def test_obs_server_debug_device_and_slo_attribution():
    """/debug/device serves per-device memory stats; /debug/slo picks
    up the supervisor's host/device attribution when it offers one."""
    from libjitsi_tpu.utils.slo import SloEngine, SloSpec

    m = MetricsRegistry()
    slo = SloEngine(m, [SloSpec("r", objective=0.99,
                                bad_metric="bad_things",
                                total_metric="all_things")])
    m.register_scalar("bad_things", lambda: 0)
    m.register_scalar("all_things", lambda: 1)
    slo.on_tick()
    phases = {"host_python": 0.02, "device_compute": 0.001}
    sup = types.SimpleNamespace(
        health=lambda: {"state": "healthy"}, flight=None,
        postmortems=[],
        phase_attribution=lambda: {
            "bound": "host", "phase": "host_python",
            "phase_share": 0.95, "phases": phases})
    with ObservabilityServer(metrics=m, supervisor=sup,
                             slo=slo) as srv:
        code, body = _get(srv.port, "/debug/device")
        doc = json.loads(body)
        assert code == 200 and doc["devices"]
        assert "device" in doc["devices"][0]
        assert "bytes_in_use" in doc["devices"][0]
        code, body = _get(srv.port, "/debug/slo")
        attr = json.loads(body)["attribution"]
        assert code == 200 and attr["bound"] == "host"
        assert attr["phases"]["host_python"] == 0.02


def test_obs_server_slo_404_when_absent():
    sup = types.SimpleNamespace(
        health=lambda: {"state": "healthy"}, flight=None,
        postmortems=[])
    with ObservabilityServer(supervisor=sup) as srv:
        try:
            code, body = _get(srv.port, "/debug/slo")
        except urllib.error.HTTPError as e:
            code, body = e.code, e.read().decode("utf-8")
        assert code == 404 and "no slo engine" in body


def test_obs_server_healthz_503_when_stalled_and_404s():
    sup = types.SimpleNamespace(
        health=lambda: {"state": "stalled"}, flight=None,
        postmortems=[])
    with ObservabilityServer(supervisor=sup) as srv:
        try:
            code, body = _get(srv.port, "/healthz")
        except urllib.error.HTTPError as e:
            code, body = e.code, e.read().decode("utf-8")
        assert code == 503 and not json.loads(body)["ok"]
        try:
            code, _ = _get(srv.port, "/debug/streams/abc")
        except urllib.error.HTTPError as e:
            code = e.code
        assert code == 404
        try:
            code, _ = _get(srv.port, "/nope")
        except urllib.error.HTTPError as e:
            code = e.code
        assert code == 404


# --------------------------------------------------- process families

def test_process_families_render_and_validator_bounds():
    """The un-namespaced process families every /metrics response
    carries: a well-formed pair validates; a zero/negative start time
    (the classic uninitialized-clock bug Prometheus restart detection
    would silently swallow) and a negative scrape duration are format
    violations."""
    from libjitsi_tpu.utils.metrics import process_families_text

    good = process_families_text(0.002)
    assert validate_exposition(good) == []
    assert "# TYPE process_start_time_seconds gauge" in good
    assert "# TYPE scrape_duration_seconds gauge" in good
    # default start stamp is this process's import time: a real epoch
    line = [ln for ln in good.splitlines()
            if ln.startswith("process_start_time_seconds ")][0]
    assert float(line.split()[1]) > 1e9

    bad_start = process_families_text(0.002, start_time_s=0.0)
    errors = validate_exposition(bad_start)
    assert any("positive unix time" in e for e in errors)

    bad_dur = process_families_text(-0.5)
    errors = validate_exposition(bad_dur)
    assert any("scrape_duration_seconds" in e and ">= 0" in e
               for e in errors)


# ------------------------------------------------- histogram vec render

def test_histogram_vec_zero_observation_child_renders_valid():
    """A labeled child created but never observed (a hop that carried
    no traffic yet) must still render a complete, validator-clean
    bucket/sum/count triple of zeros under the family's single # TYPE
    line — not a half-family the scraper chokes on."""
    m = MetricsRegistry()
    vec = m.histogram_vec("hop_seconds", (0.01, 0.1), "hop")
    vec.labels("local").observe(0.005)
    vec.labels("b1-b2")                  # created, zero observations
    text = m.render()
    assert validate_exposition(text) == []
    assert text.count("# TYPE libjitsi_tpu_hop_seconds histogram") == 1
    assert ('libjitsi_tpu_hop_seconds_bucket{hop="b1-b2",le="+Inf"} 0'
            in text)
    assert 'libjitsi_tpu_hop_seconds_count{hop="b1-b2"} 0' in text
    assert 'libjitsi_tpu_hop_seconds_count{hop="local"} 1' in text
    # OpenMetrics rendering of the empty child is also clean
    assert validate_exposition(m.render(openmetrics=True),
                               openmetrics=True) == []


# -------------------------------------------------- offline fleet merge

def test_trace_report_merges_saved_bridge_scrapes(tmp_path):
    """scripts/trace_report.py --merge-bridges over SAVED exposition
    files (the offline twin of /debug/fleet): a trace id whose journey
    exemplars appear on two bridges' scrapes is stitched; a bridge-local
    id is not."""
    import sys
    sys.path.insert(0, "scripts")
    import trace_report

    def scrape(hop, observes):
        m = MetricsRegistry()
        vec = m.histogram_vec("packet_journey_seconds", (0.01, 0.1),
                              "hop", exemplars=True)
        for tid, seconds in observes:
            vec.labels(hop).observe(seconds,
                                    exemplar={"trace_id": tid})
        return m.render(openmetrics=True)

    a, b = tmp_path / "a.om", tmp_path / "b.om"
    # distinct buckets: exemplar slots are per-bucket, last wins
    a.write_text(scrape("local", [("77", 0.004), ("88", 0.05)]))
    b.write_text(scrape("b1-b2", [("77", 0.004)]))
    doc = trace_report.merge_bridges([str(a), str(b)])
    assert doc["errors"] == {}
    assert set(doc["bridges"]) == {"a.om", "b.om"}
    assert doc["bridges"]["a.om"]["exemplars"] == 2
    assert doc["stitched_trace_ids"] == ["77"]
    by_id = {j["trace_id"]: j for j in doc["journeys"]}
    assert by_id["77"]["stitched"]
    assert {s["hop"] for s in by_id["77"]["spans"]} \
        == {"local", "b1-b2"}
    assert not by_id["88"]["stitched"]
    text = trace_report.format_fleet(doc)
    assert "stitched journeys (seen on >1 bridge): 1" in text
    # the CLI exit contract: merged scrapes with no errors -> 0
    assert trace_report.main(["--merge-bridges", str(a), str(b)]) == 0


# ------------------------------------------------------------ dashboards

def test_checked_in_dashboards_are_fresh():
    """Round-trip: regenerating the recording rules + dashboard from
    the live registry must reproduce the checked-in files byte-for-byte
    (a metrics change that shifts the scrape surface fails here until
    scripts/gen_dashboards.py is re-run)."""
    import os
    import sys
    sys.path.insert(0, "scripts")
    import gen_dashboards

    texts = gen_dashboards.generate()
    assert set(texts) == set(gen_dashboards.FILES)
    for name, text in texts.items():
        path = os.path.join(gen_dashboards.OUT_DIR, name)
        assert os.path.exists(path), f"dashboards/{name} not checked in"
        with open(path) as fh:
            on_disk = fh.read()
        assert on_disk == text, \
            (f"dashboards/{name} is stale — "
             "re-run scripts/gen_dashboards.py")
    # every PromQL family referenced exists in the registry the
    # generator saw: burn-rate rules name each stock SLO
    rules = texts["recording_rules.yaml"]
    for slo_name in ("journey_p99", "residual_loss", "auth_fail"):
        assert f"slo: {slo_name}" in rules
    dash = json.loads(texts["bridge_dashboard.json"])
    assert dash["panels"], "dashboard generated with no panels"
    # alertmanager routing: per-SLO fast-burn routes page, slow-burn
    # routes ticket, and fast inhibits slow on the same slo label
    am = texts["alertmanager.yaml"]
    for slo_name in ("journey_p99", "residual_loss", "auth_fail"):
        assert f'- slo = "{slo_name}"' in am
    assert am.count("receiver: rtc-oncall-pager") == 3
    assert "alertname = SloFastBurn" in am
    assert "inhibit_rules:" in am and "equal: [slo]" in am


# ------------------------------------------------------------- soak twin

@pytest.mark.slow
def test_obs_smoke_soak():
    """The tier-1 smoke with 5x the ticks: histograms keep their
    invariants and the validator stays clean under sustained load."""
    import sys
    sys.path.insert(0, "scripts")
    import obs_smoke

    obs_smoke.run(ticks=200)
