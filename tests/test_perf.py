"""Performance-attribution plane: the phase split read off the span
tree, HistogramVec exposition, compile-cache stats, and the
trace_report occupancy analyzer.

The load-bearing property: every tick's six phases sum to the tick's
wall time (host_python is the clamped residual), each device call's
put / dispatch / block / copy-back spans land in the phase the one
table in utils/tracing.py names, and nothing times a phase: no fence,
no probe, no second code path on some ticks.
"""

import gzip
import json
import os
import socket
import sys
import time

import numpy as np
import pytest

import libjitsi_tpu
from libjitsi_tpu.utils.compile_cache import CompileCacheStats
from libjitsi_tpu.utils.metrics import (MetricsRegistry,
                                        validate_exposition)
from libjitsi_tpu.utils.perf import (DEVICE_PHASES, HOST_PHASES, PHASES,
                                     LoopPerf, classify_bound,
                                     host_share)
from libjitsi_tpu.utils.tracing import (PHASE_OF_STAGE, PipelineTracer,
                                        phase_split)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts"))


def _perf(**kw):
    reg = MetricsRegistry()
    tracer = PipelineTracer(reg, annotate=False)
    return LoopPerf(tracer, reg, **kw), tracer, reg


# -------------------------------------------------------- phase split

def test_tick_phases_sum_to_wall():
    prof, tracer, _reg = _perf()
    t0 = time.perf_counter()
    prof.begin_tick()
    with tracer.span("ingress"):
        time.sleep(0.004)
    with tracer.span("unprotect_block"):
        time.sleep(0.002)
    prof.end_tick()
    outer_wall = time.perf_counter() - t0
    phases = prof.last_phases
    assert set(phases) == set(PHASES)
    assert phases["idle"] >= 0.004
    assert phases["device_compute"] >= 0.002
    assert phases["host_python"] >= 0.0
    total = sum(phases.values())
    # the six phases sum to the loop's wall: bounded above by the
    # outer measurement and below by what we provably slept
    assert 0.006 <= total <= outer_wall + 1e-4
    # residual construction: total - mapped spans == host_python
    explicit = phases["idle"] + phases["device_compute"]
    assert phases["host_python"] == pytest.approx(total - explicit)


def test_phase_split_books_self_times_by_the_one_table():
    """The ledger a supervisor drains -> the split: a container's SELF
    time (the dispatch less its put), the mesh's host stages and every
    unmapped span to `host_python`, a booked off-tick stage nowhere."""
    tracer = PipelineTracer(MetricsRegistry(), annotate=False)
    t0 = time.perf_counter()
    with tracer.span("ingress"):
        time.sleep(0.001)
    with tracer.span("unprotect_wait"):
        with tracer.span("unprotect_dispatch"):
            with tracer.span("owner_plan"):
                time.sleep(0.002)
            with tracer.span("unprotect_put"):
                time.sleep(0.001)
            time.sleep(0.001)
        with tracer.span("unprotect_block"):
            time.sleep(0.001)
        with tracer.span("unprotect_d2h"):
            with tracer.span("mesh_scatter"):
                time.sleep(0.002)
    with tracer.span("route"):
        time.sleep(0.001)
    tracer.book("egress_send", 5.0, rows=3)
    wall = time.perf_counter() - t0
    tracer.take_ledger()
    led = tracer.last_self_ledger
    assert "egress_send" not in led and "egress_send" in tracer.last_ledger
    phases = phase_split(led, wall)
    assert list(phases) == list(PHASES)
    assert sum(phases.values()) == pytest.approx(wall, abs=1e-12)
    assert phases["idle"] == led["ingress"]
    assert phases["h2d_transfer"] == led["unprotect_put"]
    assert 0.001 <= phases["dispatch"] == led["unprotect_dispatch"] \
        < 0.002                     # less the plan and the put
    assert phases["device_compute"] == led["unprotect_block"]
    assert phases["d2h_transfer"] == led["unprotect_d2h"] < 0.001
    assert phases["host_python"] >= (led["owner_plan"]
                                     + led["mesh_scatter"] + led["route"])
    # a caller whose clock is not the spans' (a test's fake one): the
    # residual is clamped, never negative
    assert phase_split(led, 0.0)["host_python"] == 0.0
    assert set(PHASE_OF_STAGE.values()) == set(PHASES) - {"host_python"}


def test_phase_totals_accumulate_across_ticks():
    prof, tracer, _reg = _perf()
    for _ in range(3):
        prof.begin_tick()
        with tracer.span("ingress"):
            time.sleep(0.001)
        prof.end_tick()
        # nobody drains this tracer: each tick's split is of what the
        # ledger GAINED in it
        assert 0.001 <= prof.last_phases["idle"] < 0.003
    assert prof.phase_totals["idle"] >= 0.003
    assert tracer.self_ledger["ingress"] >= 0.003


def test_a_drained_loop_leaves_the_split_to_its_supervisor():
    """Where a supervisor drains the tracer the loop's hooks do nothing
    (the tick pays nothing for the split) and `take` is fed from the
    drain; a loop left alone again takes its own."""
    prof, tracer, reg = _perf()
    prof.drained_by_supervisor = True
    prof.begin_tick()
    with tracer.span("fanout_wait"):
        time.sleep(0.001)
    prof.end_tick()
    assert prof.last_phases == {} and not any(prof.phase_totals.values())
    tracer.take_ledger()
    prof.take(phase_split(tracer.last_self_ledger, 0.004))
    assert prof.last_phases["device_compute"] >= 0.001
    assert sum(prof.phase_totals.values()) == pytest.approx(0.004)
    assert reg.get_histogram_vec("tick_phase_seconds").count == 6
    prof.drained_by_supervisor = False
    prof.begin_tick()
    prof.end_tick()
    assert prof.last_phases["device_compute"] == 0.0
    assert 0.0 < sum(prof.last_phases.values()) < 0.001


def test_classify_bound_and_host_share():
    host = {"host_python": 0.01, "dispatch": 0.004,
            "device_compute": 0.002, "idle": 0.001}
    dev = {"host_python": 0.001, "h2d_transfer": 0.002,
           "device_compute": 0.02, "d2h_transfer": 0.003}
    assert classify_bound(host) == "host"
    assert classify_bound(dev) == "device"
    assert classify_bound({"idle": 1.0}) == "idle"
    assert classify_bound({}) == "unknown"
    assert classify_bound({"host_python": 0.0}) == "unknown"
    assert host_share(host) == pytest.approx(0.014 / 0.016)
    assert host_share({}) == 0.0
    assert set(HOST_PHASES) | set(DEVICE_PHASES) | {"idle"} == \
        set(PHASES)


# ----------------------------------------------------- metrics surface

def test_profiler_metrics_render_and_validate():
    prof, tracer, reg = _perf(inflight_fn=lambda: 2)
    prof.begin_tick()
    with tracer.span("fanout_wait"):
        time.sleep(0.001)
    prof.note_h2d(1234)
    prof.end_tick()
    text = reg.render()
    assert not validate_exposition(text)
    ns = reg.ns
    assert f"# TYPE {ns}_tick_phase_seconds histogram" in text
    for p in PHASES:       # every tick observes all six
        assert f'{ns}_tick_phase_seconds_count{{phase="{p}"}} 1' in text
    assert f'{ns}_tick_phase_seconds_bucket{{phase="device_compute",' \
        f'le="1e-05"}} 0' in text
    assert f"{ns}_dispatch_inflight_ticks 2" in text
    assert f"{ns}_h2d_bytes_total 1234" in text
    assert f"# TYPE {ns}_compile_events counter" in text
    assert "phase_sampled" not in text and "probe_overhead" not in text


def test_histogram_vec_children_and_count():
    reg = MetricsRegistry()
    vec = reg.histogram_vec("demo_seconds", (0.1, 1.0), "phase")
    vec.labels("a").observe(0.05)
    vec.labels("a").observe(0.5)
    vec.labels("b").observe(2.0)
    assert vec.labels("a") is vec.labels("a")       # create-or-get
    assert vec.count == 3
    assert reg.get_histogram_vec("demo_seconds") is vec
    assert reg.histogram_vec("demo_seconds", (9.9,), "phase") is vec
    text = reg.render()
    assert not validate_exposition(text)
    assert f'{reg.ns}_demo_seconds_bucket{{phase="a",le="0.1"}} 1' \
        in text
    assert f'{reg.ns}_demo_seconds_bucket{{phase="b",le="+Inf"}} 1' \
        in text
    assert f'{reg.ns}_demo_seconds_count{{phase="b"}} 1' in text


# ------------------------------------------ the split on a served tick

SSRC_BASE = 0x46000000
MEMBERS = 8
SERVED = {
    "cm": ("AES_CM_128_HMAC_SHA1_80", 0),
    "gcm": ("AEAD_AES_128_GCM", 0),
    "mesh4": ("AES_CM_128_HMAC_SHA1_80", 4),
}


def _key_pair(raw, salt_len) -> tuple:
    b = bytes(raw)
    return b[:16], b[16:16 + salt_len]


@pytest.fixture(scope="module", params=sorted(SERVED))
def served(request):
    """One conference of 8 on an `SfuBridge` under a supervisor (CM,
    GCM, and CM on a mesh of four of the host devices `conftest.py`
    forces): six ticks of two packets each, then two ticks of none.
    The record holds, a tick, what the supervisor kept of it."""
    import jax

    from libjitsi_tpu.mesh import make_media_mesh
    from libjitsi_tpu.rtp import header as rtp_header
    from libjitsi_tpu.service.sfu_bridge import SfuBridge
    from libjitsi_tpu.service.supervisor import (BridgeSupervisor,
                                                 SupervisorConfig)
    from libjitsi_tpu.transform.srtp import SrtpProfile, SrtpStreamTable

    name, n_mesh = SERVED[request.param]
    profile = SrtpProfile[name]
    salt_len = profile.policy.salt_len
    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    kwargs = {"mesh": make_media_mesh(jax.devices()[:n_mesh])} \
        if n_mesh else {}
    bridge = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                       capacity=16, profile=profile, recv_window_ms=0,
                       **kwargs)
    sup = BridgeSupervisor(bridge, SupervisorConfig(deadline_ms=60_000.0),
                           metrics=bridge.loop.metrics)
    keys = np.random.default_rng([46, 0x70]).integers(
        0, 256, (MEMBERS, 2, 30), dtype=np.uint8)
    sink = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sink.bind(("127.0.0.1", 0))
    client = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    ticks = []
    try:
        sids = bridge.stage_endpoints(
            [(SSRC_BASE + i, _key_pair(keys[i, 0], salt_len),
              _key_pair(keys[i, 1], salt_len), None)
             for i in range(MEMBERS)], conferences=[0] * MEMBERS)
        bridge.commit_endpoints(sids)
        for sid in sids:          # as the bridge would have latched them
            bridge.loop.addr_ip[sid] = 0x7F000001
            bridge.loop.addr_port[sid] = sink.getsockname()[1]
        senders = []
        for i in range(2):
            tab = SrtpStreamTable(capacity=1, profile=profile)
            tab.add_stream(0, *_key_pair(keys[i, 0], salt_len))
            senders.append(tab)
        for t in range(8):
            if t < 6:
                for i, tab in enumerate(senders):
                    b = rtp_header.build(
                        [b"phase-%02d" % t * 8], [700 + t], [960 * t],
                        [SSRC_BASE + i], [111], stream=[0])
                    client.sendto(tab.protect_rtp(b).to_bytes(0),
                                  ("127.0.0.1", bridge.port))
                time.sleep(0.01)
            rx0 = bridge.loop.rx_packets
            sup.tick(now=4000.0 + 0.02 * t)
            ticks.append({
                "rx": bridge.loop.rx_packets - rx0,
                "tick_s": sup.last_tick_s,
                "self": dict(sup.last_self_ledger),
                "wait": sup.last_ledger.get("unprotect_wait", 0.0),
                "phases": dict(sup.last_phases),
                "loop_phases": bridge.loop.perf.last_phases,
                "health": sup.health()["last_phases"]})
        bridge.flush_egress()
        assert bridge.loop.perf.drained_by_supervisor
        yield {"ticks": ticks, "bridge": bridge, "sup": sup,
               "rx": bridge.loop.rx_packets,
               "forwarded": bridge.forwarded}
    finally:
        sup.close()
        bridge.close()
        sink.close()
        client.close()


def test_served_tick_is_split_by_its_spans(served):
    """Every tick with media: the copy in is there (`h2d_transfer` was
    always 0 on this path), `device_compute` is the two waits and not
    the container round the unprotect, the six sum to the tick the
    ladder judges."""
    media = [t for t in served["ticks"] if t["rx"]]
    assert len(media) == 6 and served["forwarded"] == 12 * (MEMBERS - 1)
    for t in served["ticks"]:
        led, ph = t["self"], t["phases"]
        assert list(ph) == list(PHASES) and t["health"] == ph
        # ONE split a tick: the supervisor's, handed to the loop's
        # totals and histogram
        assert t["loop_phases"] == ph
        assert sum(ph.values()) == pytest.approx(t["tick_s"], abs=1e-9)
        assert ph["host_python"] > 0.0
        for phase in set(PHASES) - {"host_python"}:
            assert ph[phase] == pytest.approx(sum(
                led.get(s, 0.0) for s, p in PHASE_OF_STAGE.items()
                if p == phase)), phase
    for t in media:
        led, ph = t["self"], t["phases"]
        assert ph["h2d_transfer"] >= led["unprotect_put"] > 0.0
        assert 0.0 < ph["device_compute"] <= (
            led["unprotect_block"] + led.get("fanout_wait", 0.0)) * (
                1 + 1e-9)
        assert ph["device_compute"] < t["wait"] \
            + led.get("fanout_wait", 0.0)
    bridge = served["bridge"]
    assert bridge.loop.perf.phase_totals == pytest.approx({
        p: sum(t["phases"][p] for t in served["ticks"])
        for p in PHASES})
    assert bridge.loop.metrics.get_histogram_vec(
        "tick_phase_seconds").labels("h2d_transfer").count == 8
    for piece in (bridge.rx_table, bridge.tx_table, bridge.translator):
        assert not hasattr(piece, "perf")
        assert piece is bridge.tx_table or \
            piece.tracer is bridge.loop.tracer


def test_collecting_tick_books_the_wait_it_runs(served):
    """A fan-out is dispatched by one tick and collected by the next:
    the collection's `fanout_wait` / `fanout_d2h` are the collecting
    tick's time and its split books them, with media (inside
    `_on_media`) and without (at the end of `bridge.tick`, after the
    loop's own tick and inside the tick the supervisor splits)."""
    ticks = served["ticks"]
    assert "fanout_wait" not in ticks[0]["self"]      # nothing to collect
    for t in ticks[1:6]:
        assert t["self"]["fanout_wait"] > 0.0
        assert t["phases"]["device_compute"] == pytest.approx(
            t["self"]["unprotect_block"] + t["self"]["fanout_wait"])
    late = ticks[6]
    assert late["rx"] == 0 and "unprotect_block" not in late["self"]
    assert late["phases"]["device_compute"] == \
        late["self"]["fanout_wait"] > 0.0
    assert late["phases"]["d2h_transfer"] == late["self"]["fanout_d2h"]
    assert "fanout_wait" not in ticks[7]["self"]
    assert ticks[7]["phases"]["device_compute"] == 0.0


def test_byte_counters_are_the_formula_of_the_packets_read(served):
    """`loop.perf.h2d_bytes` / `d2h_bytes` count the unprotect's input
    by formula, a packet read (its row of the recv plane and its
    length word in, the row back), as they did: the benchmark's
    `h2d_bytes_per_tick` / `d2h_bytes_per_tick` read them."""
    loop = served["bridge"].loop
    assert served["rx"] == 12
    width = loop.engine.capacity
    assert width == 1504      # the ledger's x 1,508 in and x 1,504 back
    assert loop.perf.h2d_bytes == served["rx"] * (width + 4)
    assert loop.perf.d2h_bytes == served["rx"] * width


# -------------------------------------- the split on the chain path

@pytest.mark.parametrize("depth", [1, 2])
def test_chain_loop_has_a_split_every_tick_on_one_code_path(depth):
    """A `MediaLoop` with a transform chain and no supervisor (a
    `MediaStream`, the loop-echo harness): every tick has its split;
    at depth 1 `send_media` runs `transform` on every one of 32 ticks
    (1 in 16 went through `transform_async` + a fence "so that the
    phases split out"), at depth 2 the async seams book dispatch and
    copy-back."""
    from libjitsi_tpu.core.packet import PacketBatch
    from libjitsi_tpu.io import UdpEngine
    from libjitsi_tpu.io.loop import MediaLoop
    from libjitsi_tpu.rtp import header as rtp_header
    from libjitsi_tpu.service.media_stream import StreamRegistry
    from libjitsi_tpu.transform import (SrtpTransformEngine,
                                        TransformEngineChain)
    from libjitsi_tpu.transform.srtp import SrtpStreamTable

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    mk, ms = bytes(range(16)), bytes(range(30, 44))
    reg = StreamRegistry(libjitsi_tpu.configuration_service(), capacity=4)
    rx_tab, tx_tab = SrtpStreamTable(capacity=4), SrtpStreamTable(capacity=4)
    rx_tab.add_stream(1, mk, ms)
    tx_tab.add_stream(1, ms + b"\x00\x01", ms)
    chain = TransformEngineChain([SrtpTransformEngine(tx_tab, rx_tab)])
    calls = {"transform": 0, "transform_async": 0}
    tr = chain.rtp_transformer
    for fn in calls:
        def counted(*a, _inner=getattr(tr, fn), _fn=fn, **kw):
            calls[_fn] += 1
            return _inner(*a, **kw)
        setattr(tr, fn, counted)

    def echo(batch, ok):
        rows = np.nonzero(ok)[0]
        return PacketBatch(batch.data[rows],
                           np.asarray(batch.length)[rows],
                           batch.stream[rows])

    loop = MediaLoop(UdpEngine(port=0, max_batch=16), reg, on_media=echo,
                     chain=chain, recv_window_ms=0, pipeline_depth=depth)
    reg.map_ssrc(0xBEEF46, 1)
    c_tx = SrtpStreamTable(capacity=1)
    c_tx.add_stream(0, mk, ms)
    client = UdpEngine(port=0, max_batch=16)
    splits = []
    try:
        for t in range(32):
            b = rtp_header.build([b"\xab" * 80], [t], [960 * t],
                                 [0xBEEF46], [96], stream=[0])
            client.send_batch(c_tx.protect_rtp(b), "127.0.0.1",
                              loop.engine.port)
            time.sleep(0.002)
            loop.tick()
            splits.append(dict(loop.perf.last_phases))
        loop.drain()
    finally:
        loop.engine.close()
        client.close()
    assert loop.rx_packets == loop.tx_packets == 32
    for ph in splits:
        assert list(ph) == list(PHASES)
        assert ph["host_python"] > 0.0 and ph["idle"] > 0.0
    total = {p: sum(ph[p] for ph in splits) for p in PHASES}
    assert loop.perf.phase_totals == pytest.approx(total)
    if depth == 1:
        assert calls == {"transform": 32, "transform_async": 0}
        assert all(ph["device_compute"] > 0.0 for ph in splits)
        assert total["dispatch"] == total["d2h_transfer"] == 0.0
    else:
        assert calls == {"transform": 0, "transform_async": 32}
        assert min(total["dispatch"], total["d2h_transfer"]) > 0.0


# -------------------------------------------------- compile-cache stats

def test_compile_cache_stats_listener_contract():
    st = CompileCacheStats()
    st.on_event("/jax/compilation_cache/cache_hit")
    st.on_event("/jax/compilation_cache/cache_miss")
    st.on_event("/jax/compilation_cache/cache_miss")
    st.on_event("/jax/unrelated/event")
    st.on_duration("/jax/core/compile", 0.25)
    st.on_duration("/jax/backend_compile", 0.5)
    st.on_duration("/jax/unrelated", 99.0)
    assert st.hits == 1
    assert st.misses == 2
    assert st.compile_events == 2
    assert st.compile_seconds == pytest.approx(0.75)


# -------------------------------------------------------- trace report

def _slice(pid, name, ts, dur):
    return {"ph": "X", "pid": pid, "tid": 1, "name": name,
            "ts": ts, "dur": dur}


def _device_events():
    """Synthetic Chrome trace: host pid 1, device pid 2; device busy
    [0,100) and [300,400) us over a 0..1000 us capture."""
    return [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "python host"}},
        {"ph": "M", "pid": 2, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        _slice(1, "host_stuff", 0, 1000),
        _slice(2, "fusion.1", 0, 60),
        _slice(2, "copy.h2d", 60, 40),
        _slice(2, "fusion.1", 300, 100),
    ]


def test_trace_report_occupancy_math():
    import trace_report

    rep = trace_report.build_report(_device_events())
    assert rep["device_tracks"] == ["/device:TPU:0"]
    assert rep["trace_wall_s"] == pytest.approx(1000e-6)
    assert rep["device_busy_s"] == pytest.approx(200e-6)
    assert rep["device_idle_pct"] == pytest.approx(80.0)
    assert rep["device_transfer_s"] == pytest.approx(40e-6)
    # one gap: busy [0,100) then [300,400) -> 200us stall
    assert rep["largest_dispatch_gaps_s"][0] == pytest.approx(200e-6)
    top = dict(rep["top_kernels"])
    assert top["fusion.1"] == pytest.approx(160e-6)
    text = trace_report.format_report(rep)
    assert "device idle" in text and "80.0 %" in text


def test_trace_report_host_only_capture_degrades_gracefully():
    import trace_report

    events = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "python host"}},
        _slice(1, "host_stuff", 0, 500),
    ]
    rep = trace_report.build_report(events)
    assert "error" in rep and "no device track" in rep["error"]
    assert "NOTE:" in trace_report.format_report(rep)
    assert "error" in trace_report.build_report([])


def test_trace_report_loads_gzipped_trace(tmp_path):
    import trace_report

    doc = {"traceEvents": _device_events()}
    path = tmp_path / "run" / "x.trace.json.gz"
    path.parent.mkdir()
    with gzip.open(path, "wt") as f:
        json.dump(doc, f)
    found = trace_report.find_trace_file(str(tmp_path))
    assert found == str(path)
    rep = trace_report.build_report(trace_report.load_events(found))
    assert rep["device_idle_pct"] == pytest.approx(80.0)
    with pytest.raises(FileNotFoundError):
        trace_report.find_trace_file(str(tmp_path / "run" / "empty"))
