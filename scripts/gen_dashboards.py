#!/usr/bin/env python
"""Generate Prometheus recording rules + a Grafana dashboard from the
LIVE MetricsRegistry of an assembled bridge.

The generator builds a real SfuBridge + BridgeSupervisor + SloEngine
(the same assembly obs_smoke drives), pre-touches the pipeline-stage
timing rings, and then walks `registry.families()` — the registered
families are the single source of truth, so a rule expression can
never reference a metric the tree stopped exporting (the jitlint
`drift` checker guards the opposite direction: an SloSpec naming an
unregistered family).

Outputs (checked into dashboards/; tier-1 freshness test regenerates
and diffs, so a metrics change that shifts the surfaces fails loudly
until this script is re-run):

  dashboards/recording_rules.yaml   burn-rate + stage-p99 + journey
                                    recording rules (hand-rolled YAML:
                                    no pyyaml dependency)
  dashboards/bridge_dashboard.json  one Grafana dashboard, a panel per
                                    family group, deterministic JSON
  dashboards/alertmanager.yaml      severity routing for the burn-rate
                                    rules: fast-burn pages, slow-burn
                                    tickets, fast inhibits slow

Run from the repo root:  python scripts/gen_dashboards.py [--check]
`--check` regenerates in memory and exits 1 when the checked-in files
are stale.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, ".")

OUT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "dashboards")

#: the SRE-workbook burn thresholds the SloEngine alerts on; recording
#: rules precompute the per-pair burn (min of both windows — BOTH must
#: burn, so the min IS the pair's alertable burn rate)
BURN_PAIRS = (("fast", "1m", "5m", 14.4), ("slow", "30m", "6h", 6.0))


def build_registry():
    """Assemble the real bridge + supervisor + SLO engine and return
    (registry, slo_engine, closer)."""
    import libjitsi_tpu
    from libjitsi_tpu.mesh.cascade import CascadeTrunk, TrunkConfig
    from libjitsi_tpu.service.sfu_bridge import SfuBridge
    from libjitsi_tpu.service.supervisor import (CascadeSupervisor,
                                                 SupervisorConfig)
    from libjitsi_tpu.utils.slo import SloEngine, default_slos
    from libjitsi_tpu.utils.tracing import STAGES

    libjitsi_tpu.init()
    sfu = SfuBridge(libjitsi_tpu.configuration_service(), port=0,
                    capacity=8, recv_window_ms=0)
    slo = SloEngine(sfu.loop.metrics, default_slos())
    # the cascade assembly is a superset of the plain supervisor's
    # surfaces: trunk relay/liveness families plus the per-bridge
    # sliced media SLO ride the same registry
    trunk = CascadeTrunk((b"\x00" * 16, b"\x01" * 14),
                         (b"\x02" * 16, b"\x03" * 14),
                         TrunkConfig(), port=0)
    sup = CascadeSupervisor(sfu, trunk, SupervisorConfig(),
                            metrics=sfu.loop.metrics, slo=slo)
    from libjitsi_tpu.service.lifecycle import StreamLifecycleManager
    lc = StreamLifecycleManager(sfu, supervisor=sup)
    lc.register_metrics(sfu.loop.metrics)
    # the capacity model's headroom/bottleneck/confidence gauges are
    # part of the stock assembly's scrape surface (utils/capacity.py)
    from libjitsi_tpu.utils.capacity import CapacityModel
    CapacityModel().attach(sup, registry=sfu.loop.metrics)
    # stage timing rings are created lazily by the first span; touch
    # them all so the generated surfaces cover every pipeline stage
    for stage in STAGES:
        sfu.loop.metrics.timing(f"stage_{stage}")
    assert sup.slo is slo

    def closer():
        trunk.close()
        sfu.close()
        libjitsi_tpu.stop()
    return sfu.loop.metrics, slo, closer


# ------------------------------------------------------ recording rules

def gen_rules_yaml(registry, slo) -> str:
    """Prometheus recording-rule groups from the live registry."""
    ns = registry.ns
    fams = dict(registry.families())
    out = [
        "# Generated by scripts/gen_dashboards.py — do not edit.",
        "# Regenerate: python scripts/gen_dashboards.py",
        "groups:",
    ]

    burn = f"{ns}_slo_burn_rate"
    if burn in fams:
        out.append("- name: libjitsi-slo-burn")
        out.append("  rules:")
        for spec in slo.specs:
            for pair, w_short, w_long, threshold in BURN_PAIRS:
                expr = (f"min({burn}{{slo=\"{spec.name}\","
                        f"window=\"{w_short}\"}}, "
                        f"{burn}{{slo=\"{spec.name}\","
                        f"window=\"{w_long}\"}})")
                out.append(f"  - record: slo:{spec.name}:"
                           f"{pair}_burn_rate")
                out.append(f"    expr: {expr}")
                out.append("    labels:")
                out.append(f"      slo: {spec.name}")
                out.append(f"      threshold: \"{threshold}\"")

    stage_fams = sorted(n for n, kind in fams.items()
                        if kind == "summary" and "_stage_" in n)
    if stage_fams:
        out.append("- name: libjitsi-stage-latency")
        out.append("  rules:")
        for fam in stage_fams:
            stage = fam[len(f"{ns}_stage_"):-len("_seconds")]
            out.append(f"  - record: stage:{stage}:p99_seconds")
            out.append(f"    expr: {fam}{{quantile=\"0.99\"}}")

    journey = f"{ns}_packet_journey_seconds"
    if fams.get(journey) == "histogram":
        out.append("- name: libjitsi-journey")
        out.append("  rules:")
        out.append("  - record: journey:p99_seconds")
        out.append("    expr: histogram_quantile(0.99, "
                   f"rate({journey}_bucket[5m]))")
        out.append("  - record: journey:p50_seconds")
        out.append("    expr: histogram_quantile(0.50, "
                   f"rate({journey}_bucket[5m]))")
        # per-hop tail: the journey family is a HistogramVec sliced by
        # `hop` ("local" plus one b<i>-b<j> child per trunk peer), and
        # the CascadeSupervisor's hop_journey SLO burns on exactly this
        # quantile — the rule keeps dashboard and admission aligned
        out.append("  - record: journey:hop:p99_seconds")
        out.append("    expr: histogram_quantile(0.99, "
                   f"sum by (hop, le) (rate({journey}_bucket[5m])))")
    return "\n".join(out) + "\n"


# --------------------------------------------------------- alertmanager

def gen_alertmanager_yaml(registry, slo) -> str:
    """Alertmanager routing for the burn-rate recording rules: a
    fast-burn alert (14.4x, error budget gone in hours) PAGES, a
    slow-burn alert (6x over 6h) files a TICKET, and an inhibit rule
    silences the slow ticket while the matching fast page is firing —
    the SRE-workbook severity split, one route per SLO so ownership
    can later diverge per objective."""
    out = [
        "# Generated by scripts/gen_dashboards.py — do not edit.",
        "# Regenerate: python scripts/gen_dashboards.py",
        "route:",
        "  receiver: team-rtc-tickets",
        "  group_by: [alertname, slo]",
        "  group_wait: 30s",
        "  group_interval: 5m",
        "  repeat_interval: 4h",
        "  routes:",
    ]
    for spec in slo.specs:
        for pair, _w_short, _w_long, _threshold in BURN_PAIRS:
            receiver = ("rtc-oncall-pager" if pair == "fast"
                        else "team-rtc-tickets")
            out.append(f"  - receiver: {receiver}")
            out.append("    matchers:")
            out.append(f"    - alertname = "
                       f"Slo{pair.capitalize()}Burn")
            out.append(f"    - slo = \"{spec.name}\"")
            if pair == "fast":
                out.append("    continue: false")
    out += [
        "receivers:",
        "- name: rtc-oncall-pager",
        "  webhook_configs:",
        "  - url: http://oncall-gateway.internal/v1/page",
        "    send_resolved: true",
        "- name: team-rtc-tickets",
        "  webhook_configs:",
        "  - url: http://ticket-gateway.internal/v1/ticket",
        "    send_resolved: true",
        "inhibit_rules:",
        "# a firing fast-burn page silences the slow-burn ticket for",
        "# the same SLO: one incident, one thread",
        "- source_matchers:",
        "  - alertname = SloFastBurn",
        "  target_matchers:",
        "  - alertname = SloSlowBurn",
        "  equal: [slo]",
    ]
    return "\n".join(out) + "\n"


# ------------------------------------------------------------ dashboard

def _panel(pid, title, exprs, x, y, w=12, h=8):
    return {
        "id": pid,
        "title": title,
        "type": "timeseries",
        "gridPos": {"h": h, "w": w, "x": x, "y": y},
        "targets": [{"expr": e, "refId": chr(ord("A") + i)}
                    for i, e in enumerate(exprs)],
    }


def gen_dashboard_json(registry, slo) -> str:
    """One Grafana dashboard: SLO burn, journey latency, stage p99s,
    recovery-ladder counters, supervisor state — every expression
    references a family the live registry registered."""
    ns = registry.ns
    fams = dict(registry.families())
    panels = []
    pid = 1

    def add(title, exprs, x, y):
        nonlocal pid
        exprs = [e for e in exprs if e is not None]
        if exprs:
            panels.append(_panel(pid, title, exprs, x, y))
            pid += 1

    def fam(name, suffix=""):
        full = f"{ns}_{name}"
        return f"{full}{suffix}" if full in fams else None

    add("SLO burn rate (fast pair)",
        [f"slo:{s.name}:fast_burn_rate" for s in slo.specs]
        if fam("slo_burn_rate") else [], 0, 0)
    add("SLO state (0 ok / 1 slow / 2 fast)",
        [fam("slo_state"), fam("slo_alerts_total")], 12, 0)
    add("Packet journey latency",
        ["journey:p99_seconds", "journey:p50_seconds"]
        if fam("packet_journey_seconds") else [], 0, 8)
    add("Pipeline stage p99",
        [f"stage:{n[len(f'{ns}_stage_'):-len('_seconds')]}:p99_seconds"
         for n, kind in sorted(fams.items())
         if kind == "summary" and "_stage_" in n], 12, 8)
    add("Loss-recovery ladder",
        [fam("recovery_nacks_sent", suffix=""),
         fam("recovery_nacks_abandoned"),
         fam("recovery_rtx_requests_served"),
         fam("recovery_fec_packets_sent")], 0, 16)
    add("Supervisor / overload",
        [fam("supervisor_overload_level"),
         fam("supervisor_ticks_overrun"),
         fam("supervisor_streams_shed"),
         fam("bridge_forwarded")], 12, 16)
    add("Host/device phase split",
        [f"sum by (phase) (rate({ns}_tick_phase_seconds_sum[5m]))"]
        if fam("tick_phase_seconds") else [], 0, 24)
    add("Dispatch depth / transfer bytes",
        [fam("dispatch_inflight_ticks"),
         fam("h2d_bytes_total", suffix="" ) and
         f"rate({ns}_h2d_bytes_total[5m])",
         fam("d2h_bytes_total") and
         f"rate({ns}_d2h_bytes_total[5m])"], 12, 24)
    add("Broadcast conferences",
        [fam("bcast_listeners"),
         fam("speaker_promotions_total") and
         f"rate({ns}_speaker_promotions_total[5m])"], 0, 32)
    add("Bridge cascade / trunk failover",
        [fam("trunk_relay_pps"),
         fam("trunk_rtt_seconds", suffix="{quantile=\"0.99\"}"),
         fam("trunk_state_up"),
         fam("trunk_failovers_total") and
         f"increase({ns}_trunk_failovers_total[1h])",
         fam("cascade_orphans_adopted") and
         f"increase({ns}_cascade_orphans_adopted[1h])",
         fam("slo_slice_burn_rate") and
         f"{ns}_slo_slice_burn_rate{{slo=\"bridge_media\","
         f"window=\"5m\"}}"], 0, 40)
    add("Cross-bridge journeys",
        ["journey:hop:p99_seconds",
         fam("packet_journey_seconds") and
         f"sum by (hop) (rate({ns}_packet_journey_seconds_count[5m]))",
         fam("trunk_one_way_delay_seconds")]
        if fam("packet_journey_seconds") else [], 12, 40)
    add("Capacity headroom (users to saturation)",
        [fam("capacity_headroom_users"),
         fam("capacity_bottleneck", suffix="{resource=~\".*\"}"),
         fam("capacity_estimate_confidence"),
         fam("capacity_forecast_refusals") and
         f"increase({ns}_capacity_forecast_refusals[1h])"], 0, 48)
    add("SRTP keystream cache",
        [fam("srtp_keystream_hits") and
         f"rate({ns}_srtp_keystream_hits[5m])",
         fam("srtp_keystream_misses") and
         f"rate({ns}_srtp_keystream_misses[5m])",
         fam("srtp_keystream_evictions") and
         f"rate({ns}_srtp_keystream_evictions[5m])",
         fam("srtp_keystream_fill_seconds") and
         f"rate({ns}_srtp_keystream_fill_seconds[5m])"], 12, 32)

    dash = {
        "title": "libjitsi-tpu bridge",
        "uid": "libjitsi-tpu-bridge",
        "schemaVersion": 39,
        "editable": True,
        "refresh": "10s",
        "time": {"from": "now-1h", "to": "now"},
        "panels": panels,
        "templating": {"list": []},
        "annotations": {"list": []},
    }
    return json.dumps(dash, indent=1, sort_keys=True) + "\n"


# -------------------------------------------------------------- driver

FILES = ("recording_rules.yaml", "bridge_dashboard.json",
         "alertmanager.yaml")


def generate():
    registry, slo, closer = build_registry()
    try:
        return {
            "recording_rules.yaml": gen_rules_yaml(registry, slo),
            "bridge_dashboard.json": gen_dashboard_json(registry, slo),
            "alertmanager.yaml": gen_alertmanager_yaml(registry, slo),
        }
    finally:
        closer()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="diff against checked-in files; exit 1 if "
                         "stale")
    args = ap.parse_args()
    texts = generate()
    if args.check:
        stale = []
        for name, text in texts.items():
            path = os.path.join(OUT_DIR, name)
            on_disk = open(path).read() if os.path.exists(path) else ""
            if on_disk != text:
                stale.append(name)
        if stale:
            print("stale dashboards (re-run scripts/gen_dashboards.py):"
                  f" {', '.join(stale)}")
            return 1
        print("dashboards fresh")
        return 0
    os.makedirs(OUT_DIR, exist_ok=True)
    for name, text in texts.items():
        with open(os.path.join(OUT_DIR, name), "w") as fh:
            fh.write(text)
        print(f"wrote dashboards/{name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
