#!/usr/bin/env python
"""Observability-plane smoke: a real SfuBridge over loopback UDP with a
supervisor and an ObservabilityServer attached, scraped over HTTP.

Drives media + a NACK through the bridge for N ticks, then asserts:

- /metrics parses under the exposition validator with ZERO errors;
- the five pipeline-stage summaries (ingress, reverse_chain, recovery,
  forward_chain, egress) are present with p50/p99 quantiles;
- real histogram families expose cumulative buckets ending in +Inf;
- an OpenMetrics scrape (Accept negotiation) carries at least one
  VALID exemplar on packet_journey_seconds buckets plus the `# EOF`
  terminator, and the default scrape stays exemplar-free;
- packet_journey_seconds is hop-labeled (`hop="local"` on the bridge's
  own journeys), and /debug/fleet on two peered ObservabilityServers
  stitches at least one trace id across bridges after a trunk frame
  carries the trace extension from A to B;
- the SLO engine exports slo_burn_rate gauges and serves /debug/slo;
- a hostile SDES stream name round-trips escaped, not raw;
- /healthz reports ok and /debug/streams serves a flight dump;
- the tick_phase_seconds histogram carries every tick's phase
  split, dispatch_inflight_ticks and the h2d/d2h byte counters are
  live, and /debug/device serves device-memory stats;
- the capacity model exports capacity_headroom_users /
  capacity_bottleneck / capacity_estimate_confidence and serves
  /debug/capacity; process_start_time_seconds and
  scrape_duration_seconds ride every scrape un-namespaced;
- a synthetic host-dominant overload escalates with the HOST phase
  named on the ladder_escalate event and /debug/slo attribution.

Prints OBS_SMOKE_OK on success; any failure raises (exit != 0).
Tier-1 runs this after the jitlint gate (scripts/tier1.sh).
"""

import argparse
import json
import sys
import time
import urllib.request

sys.path.insert(0, ".")

HOSTILE_NAME = 'evil "name\nwith\\slashes'
ACCEPT_OM = "application/openmetrics-text; version=1.0.0"


def _get(port, path, accept=None):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}")
    if accept is not None:
        req.add_header("Accept", accept)
    with urllib.request.urlopen(req, timeout=5) as r:
        return r.status, r.read().decode("utf-8"), \
            r.headers.get("Content-Type", "")


def _fleet_smoke(srv_a, om_a: str, exemplar_line: str) -> None:
    """Stand up bridge B as a second registry + ObservabilityServer,
    relay one trunk frame from A carrying a trace id A's scrape
    already exemplifies, record the hop on B, and assert the peered
    /debug/fleet stitches that id across both bridges."""
    import re
    import time

    from libjitsi_tpu.io.loop import JOURNEY_BUCKETS
    from libjitsi_tpu.mesh.cascade import TrunkRelay, TrunkTrace
    from libjitsi_tpu.service.obs_server import ObservabilityServer
    from libjitsi_tpu.utils.metrics import MetricsRegistry

    m = re.search(r'trace_id="(\d+)"', exemplar_line)
    assert m, f"unparseable exemplar line: {exemplar_line}"
    tid = int(m.group(1))

    # bridge B: its own registry with a hop-labeled journey vec (the
    # shape CascadeSupervisor.register_metrics installs)
    reg_b = MetricsRegistry()
    vec_b = reg_b.histogram_vec("packet_journey_seconds",
                                JOURNEY_BUCKETS, "hop",
                                help_="journey latency", exemplars=True)

    # the trunk wire actually carries the trace: frame on A's relay,
    # open on B's — the extension survives the SRTP-protected hop
    key_ab = (b"\xa0" * 16, b"\xa1" * 14)
    key_ba = (b"\xb0" * 16, b"\xb1" * 14)
    relay_a = TrunkRelay(key_ab, key_ba)
    relay_b = TrunkRelay(key_ba, key_ab)
    trace = TrunkTrace(bridge_id=0, hop=0, trace_id=tid,
                       t0=time.perf_counter())
    _seq, wire = relay_a.frame_media(
        7, bytes([0x80, 96]) + b"\x00" * 60, now=0.0, trace=trace)
    opened = relay_b.open_media(wire, now=0.0)
    assert opened is not None and opened[3] is not None, \
        "trace extension did not survive the trunk hop"
    rtr = opened[3]
    assert rtr.trace_id == tid, f"trace id mangled: {rtr}"
    vec_b.labels(f"b{rtr.bridge_id}-b1").observe(
        max(time.perf_counter() - rtr.t0, 1e-4),
        exemplar={"trace_id": str(rtr.trace_id),
                  "origin": str(rtr.bridge_id)})

    srv_b = ObservabilityServer(metrics=reg_b, name="bridge-b").start()
    try:
        srv_a.name = "bridge-a"
        srv_a.add_peer("bridge-b", f"http://127.0.0.1:{srv_b.port}")
        srv_b.add_peer("bridge-a", f"http://127.0.0.1:{srv_a.port}")
        for port in (srv_a.port, srv_b.port):
            code, body, _ = _get(port, "/debug/fleet")
            assert code == 200, f"/debug/fleet -> {code}"
            fleet = json.loads(body)
            assert not fleet["errors"], f"peer scrape failed: {fleet}"
            assert str(tid) in fleet["stitched_trace_ids"], \
                (f"trace {tid} not stitched across bridges: "
                 f"{fleet['stitched_trace_ids']}")
            spans = [j for j in fleet["journeys"]
                     if j["trace_id"] == str(tid)][0]["spans"]
            hops = {s["hop"] for s in spans}
            assert "local" in hops and "b0-b1" in hops, \
                f"journey lacks origin+remote spans: {spans}"
    finally:
        srv_a.peers.clear()
        srv_b.stop()


def run(ticks: int = 40) -> None:
    import libjitsi_tpu
    from libjitsi_tpu.service.obs_server import ObservabilityServer
    from libjitsi_tpu.service.sfu_bridge import SfuBridge
    from libjitsi_tpu.service.supervisor import (BridgeSupervisor,
                                                 SupervisorConfig)
    from libjitsi_tpu.utils.metrics import (count_exemplars,
                                            validate_exposition)
    from libjitsi_tpu.utils import tracing
    from libjitsi_tpu.utils.slo import SloEngine, default_slos

    sys.path.insert(0, "tests")
    from test_sfu_bridge import _Endpoint

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    sfu = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                    capacity=8, recv_window_ms=0)
    slo = SloEngine(sfu.loop.metrics, default_slos())
    sup = BridgeSupervisor(sfu, SupervisorConfig(deadline_ms=1000.0),
                           metrics=sfu.loop.metrics, slo=slo)
    from libjitsi_tpu.utils.capacity import CapacityModel
    CapacityModel().attach(sup, registry=sfu.loop.metrics)
    srv = ObservabilityServer(metrics=sfu.loop.metrics,
                              supervisor=sup).start()
    try:
        eps = [_Endpoint(0x200 + 9 * k, sfu.port) for k in range(3)]
        names = [HOSTILE_NAME, "alice", None]
        for e, name in zip(eps, names):
            sfu.add_endpoint(e.ssrc, e.rx_key, e.tx_key, name=name)
        for e in eps:
            for other in eps:
                if other is not e:
                    e.expect_sender(other.ssrc)

        now = 100.0
        for t in range(ticks):
            if t % 4 == 0:
                for e in eps:
                    e.send_media()
            sup.tick(now=now)
            now += 0.02
            for e in eps:
                e.drain()
        assert sfu.forwarded > 0, "no media forwarded"
        # exercise the RTX path (egress span + rtx_served flight event)
        eps[0].send_nack(eps[1].ssrc, [501])
        for _ in range(10):
            sup.tick(now=now)
        sfu.emit_feedback(now=now)

        code, text, ctype = _get(srv.port, "/metrics")
        assert code == 200, f"/metrics -> {code}"
        assert "text/plain" in ctype, f"default scrape ctype: {ctype}"
        errors = validate_exposition(text)
        assert not errors, "exposition invalid:\n" + "\n".join(errors)
        ns = sfu.loop.metrics.ns
        # every leaf of the SFU tick but `gc` (no collection is forced
        # here) and the containers round them
        for stage in set(tracing.STAGES) - {"gc", "decode", "mixer"}:
            fam = f"{ns}_stage_{stage}_seconds"
            assert f"# TYPE {fam} summary" in text, f"missing {fam}"
            for q in ('quantile="0.5"', 'quantile="0.99"'):
                assert f"{fam}{{{q}}}" in text, f"missing {fam}{{{q}}}"
        assert f"# TYPE {ns}_packet_size_bytes histogram" in text
        assert f'{ns}_packet_size_bytes_bucket{{le="+Inf"}}' in text
        assert HOSTILE_NAME not in text, "raw hostile name leaked"
        assert 'evil \\"name\\nwith\\\\slashes' in text, \
            "escaped stream name missing"

        # OpenMetrics negotiation: exemplars + # EOF, validator-clean
        code, om, ctype = _get(srv.port, "/metrics", accept=ACCEPT_OM)
        assert code == 200, f"/metrics (OM) -> {code}"
        assert "application/openmetrics-text" in ctype, \
            f"OM scrape ctype: {ctype}"
        om_errors = validate_exposition(om, openmetrics=True)
        assert not om_errors, \
            "OpenMetrics exposition invalid:\n" + "\n".join(om_errors)
        journey = f"{ns}_packet_journey_seconds"
        assert f"# TYPE {journey} histogram" in om, f"missing {journey}"
        n_ex = count_exemplars(om)
        assert n_ex >= 1, "no exemplars in the OpenMetrics scrape"
        ex_lines = [ln for ln in om.splitlines()
                    if ln.startswith(f"{journey}_bucket") and " # " in ln]
        assert ex_lines, "no exemplar on packet_journey_seconds buckets"
        assert 'trace_id="' in ex_lines[0], \
            f"exemplar lacks trace_id: {ex_lines[0]}"
        assert count_exemplars(text) == 0, \
            "default (non-OpenMetrics) scrape leaked exemplars"
        # the journey family is hop-labeled: local journeys land under
        # hop="local"; cross-bridge ingests add hop="bX-bY" children
        assert f'{journey}_count{{hop="local"}}' in om, \
            "packet_journey_seconds lost its hop label axis"

        # ---- cross-bridge fleet view: a trunk frame carries one of
        # this bridge's REAL trace ids (pulled from its own exemplars)
        # to a second bridge's registry; the peered /debug/fleet must
        # stitch that id across both scrapes
        _fleet_smoke(srv, om, ex_lines[0])

        # SLO engine: burn-rate gauges in the scrape + /debug/slo JSON
        assert f"# TYPE {ns}_slo_burn_rate gauge" in text, \
            "slo_burn_rate family missing"
        assert f'{ns}_slo_burn_rate{{slo="journey_p99",window="1m"}}' \
            in text, "journey_p99 1m burn-rate sample missing"
        code, body, _ = _get(srv.port, "/debug/slo")
        slo_doc = json.loads(body)
        assert code == 200, f"/debug/slo -> {code}"
        assert slo_doc["ticks"] > 0, "SLO engine never ticked"
        names = {s["name"] for s in slo_doc["slos"]}
        assert {"journey_p99", "residual_loss", "auth_fail"} <= names, \
            f"missing stock SLOs: {names}"
        for s in slo_doc["slos"]:
            assert set(s["burn"]) == {"1m", "5m", "30m", "6h"}, \
                f"bad windows on {s['name']}: {set(s['burn'])}"

        code, body, _ = _get(srv.port, "/healthz")
        health = json.loads(body)
        assert code == 200 and health["ok"], f"unhealthy: {health}"

        code, body, _ = _get(srv.port, "/debug/streams")
        sids = json.loads(body)["streams"]
        assert sids, "flight recorder saw no streams"
        code, body, _ = _get(srv.port, "/debug/streams/%d" % sids[0])
        dump = json.loads(body)
        assert code == 200 and dump["events"], "empty flight dump"
        kinds = {e["kind"] for e in dump["events"]}
        assert "hdr" in kinds, f"no header samples in dump: {kinds}"

        # the phase split is read off the spans every tick, so the
        # phase histogram family must carry samples and the
        # dispatch-depth gauge must be present (0 on the sync path is
        # fine)
        code, text, _ = _get(srv.port, "/metrics")
        phase_fam = f"{ns}_tick_phase_seconds"
        assert f"# TYPE {phase_fam} histogram" in text, \
            "tick_phase_seconds family missing"
        for ph in ("host_python", "dispatch", "device_compute", "idle"):
            assert f'{phase_fam}_bucket{{phase="{ph}",le="+Inf"}}' \
                in text, f"phase {ph} missing from scrape"
        assert f'{phase_fam}_count{{phase="host_python"}} 0' not in \
            text, "no tick reached the phase histogram"
        assert f"# TYPE {ns}_dispatch_inflight_ticks gauge" in text, \
            "dispatch_inflight_ticks gauge missing"
        assert f"# TYPE {ns}_h2d_bytes_total counter" in text
        h2d = [ln for ln in text.splitlines()
               if ln.startswith(f"{ns}_h2d_bytes_total ")]
        assert h2d and float(h2d[0].split()[1]) > 0, \
            f"h2d byte accounting never ran: {h2d}"

        # /debug/device: live device-memory stats JSON
        code, body, _ = _get(srv.port, "/debug/device")
        assert code == 200, f"/debug/device -> {code}"
        devices = json.loads(body)["devices"]
        assert devices and "device" in devices[0], \
            f"bad /debug/device doc: {devices}"

        # capacity model: headroom/bottleneck/confidence gauges in the
        # scrape and the /debug/capacity JSON document
        assert f"# TYPE {ns}_capacity_headroom_users gauge" in text, \
            "capacity_headroom_users gauge missing"
        assert f'{ns}_capacity_bottleneck{{resource="rows"}}' in text, \
            "capacity_bottleneck resource axis missing"
        assert f"# TYPE {ns}_capacity_estimate_confidence gauge" \
            in text, "capacity_estimate_confidence gauge missing"
        code, body, _ = _get(srv.port, "/debug/capacity")
        assert code == 200, f"/debug/capacity -> {code}"
        cap_doc = json.loads(body)
        assert cap_doc["ticks"] > 0, "capacity model never ticked"
        assert set(cap_doc["resources"]) >= {"rows", "host",
                                             "tick_budget"}, \
            f"capacity resources missing: {set(cap_doc['resources'])}"

        # process-level families ride every scrape UN-namespaced (the
        # Prometheus convention) and the validator vouches for them
        start_lines = [ln for ln in text.splitlines()
                       if ln.startswith("process_start_time_seconds ")]
        assert start_lines and float(start_lines[0].split()[1]) > 1e9, \
            f"process_start_time_seconds missing/bogus: {start_lines}"
        dur = [ln for ln in text.splitlines()
               if ln.startswith("scrape_duration_seconds ")]
        assert dur and float(dur[0].split()[1]) >= 0, \
            f"scrape_duration_seconds missing: {dur}"
        assert "# TYPE process_start_time_seconds gauge" in text
        assert "# TYPE scrape_duration_seconds gauge" in text

        # host-bound overload drill: ticks that spend 5 ms where no
        # span covers them (the interpreter's time, by the rule in
        # utils/tracing.py) while the watchdog is overrun — the
        # resulting ladder_escalate event must NAME the host phase
        sup.watchdog.deadline_s = 1e-9
        bridge_tick = sfu.tick

        def slow_tick(now=None):
            time.sleep(0.005)
            return bridge_tick(now=now)

        sfu.tick = slow_tick
        for _ in range(sup.cfg.overload_after):
            sup.tick(now=now)
            now += 0.02
        evs = [e for e in sup.flight.dump_all()["global"]
               if e.get("kind") == "ladder_escalate"]
        assert evs, "overrun ticks produced no ladder_escalate"
        ev = evs[-1]
        assert ev.get("phase") == "host_python", \
            f"escalation did not name the host phase: {ev}"
        assert ev.get("bound") == "host", \
            f"escalation not attributed host-bound: {ev}"
        code, body, _ = _get(srv.port, "/debug/slo")
        attr = json.loads(body).get("attribution", {})
        assert attr.get("bound") == "host", \
            f"/debug/slo attribution missing host bound: {attr}"
    finally:
        srv.stop()
        sfu.close()
        libjitsi_tpu.stop()
    print("OBS_SMOKE_OK")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ticks", type=int, default=40)
    args = ap.parse_args()
    run(ticks=args.ticks)


if __name__ == "__main__":
    main()
