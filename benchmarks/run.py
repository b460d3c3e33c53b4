#!/usr/bin/env python3
"""One run of one benchmark cell: the served bridge under the open-loop
talk generator, on the chips the cell asks for.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metrics
are all data: `BENCHMARK.json` names them, `configs/`, `traffic/` and
`layers/` hold them.  The last line of stdout is one JSON object
(`correct`, `attempted`, `failed`, `metrics`, `device`, and `breakdown`
with `--trace 1`); everything else goes on earlier lines or under
`benchmarks/out/`.  Without a TPU of a kind listed in `peaks.json` the
run exits non-zero and prints no result.

Beyond the driver's four arguments (see README.md):
  --fault client-key-bit | bridge-bitflip | no-latch
                        a run that must say false
  --sweep A1,A2,...     knee sweep: one set-up, one window per A
  --seeds s1,s2,...     one set-up, one window per traffic seed
  --faults f1,f2,...    control windows appended to a --seeds run
  --rows N              rehearsal off the chip at N installed rows; the
                        last line says `correct: false` and names the
                        device, so it can never pass for a chip run
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

# The compile cache is the harness's first job: every run is a new
# process, and only files in the checkout outlast it.  Fixed path inside
# the checkout; the machine's size cap (which evicts each program just
# before it is wanted) is lifted for this process.
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(HERE, ".cache")
os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

import loadgen  # noqa: E402
import readers  # noqa: E402

# defaults of the traffic file's timing keys
LEAD_MIN_S = 3.0       # address latch + warm ticks, at the cell's rate
LEAD_MAX_S = 12.0      # no still second of compile stats by then: fail
STILL_S = 1.0
GRACE_S = 1.0          # a delivery later than window end + this is lost
TRACE_SLICE_S = 3.0
INDEX_STRIDE = 4000    # packet indices set aside per window in a process


def say(msg: str) -> None:
    print(f"[bench +{time.time() - T_START:7.1f}s] {msg}", flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve_cell(name: str):
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, conf["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")

    def mine(m, e2e_names):
        if "workloads" in m:
            return name in m["workloads"]
        return e2e_names is None or m["moves"] in e2e_names

    e2e = [m["name"] for m in bench["end_to_end"] if mine(m, None)]
    layer = [m for m in bench["per_layer"] if mine(m, set(e2e))]
    return bench, cell, config, traffic, e2e, layer


def build_native() -> float:
    """Rebuild the UDP engine from the committed source (the .so is
    git-ignored and `io/udp.py` trusts file times)."""
    t0 = time.perf_counter()
    script = os.path.join(ROOT, "libjitsi_tpu", "native", "build.sh")
    if not os.path.exists(script):
        raise SystemExit(f"{script} is missing: not a checkout of the "
                         "repository")
    subprocess.run(["sh", script], check=True, capture_output=True)
    return time.perf_counter() - t0


def device_gate(chips: int, rehearsal: bool):
    import jax

    devs = jax.devices()
    dev = devs[0]
    peaks = load_json(HERE, "peaks.json")
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    if not rehearsal:
        if dev.platform != "tpu":
            raise SystemExit(f"JAX found platform {dev.platform!r}, not "
                             "'tpu': the benchmark has no CPU mode "
                             "(rehearse with --rows N)")
        if dev.device_kind not in peaks:
            raise SystemExit(f"device kind {dev.device_kind!r} is not in "
                             "peaks.json")
        if len(devs) < chips:
            raise SystemExit(f"the cell needs {chips} chips, JAX reports "
                             f"{len(devs)}")
    return ({"platform": dev.platform, "kind": dev.device_kind,
             "count": len(devs)},
            peaks.get(dev.device_kind) or next(
                v for k, v in peaks.items() if not k.startswith("_")))


def memory_peak() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


# ------------------------------------------------------------ one window

def drive_window(system, gen, timing, seconds: float, trace_dir):
    """Latch + warm ticks, then the measured window, then the grace.
    The main thread calls `sup.tick()` back to back with the wall clock
    as `now`.  Returns the window's bounds and what the tick thread saw.
    """
    import jax

    sup, loop, stats = system.sup, system.loop, system.stats
    now_ns = time.time_ns
    members = system.member_sids(loadgen.plan_endpoints(gen.plan))
    t0 = gen.go(system.port)
    # ---- lead-in: addresses latch on the first packets, every shape
    # the traffic drives runs once, and compile stats must stand still
    # for STILL_S of traffic before the window may open
    last_events, last_change = stats.compile_events, now_ns()
    while True:
        sup.tick(now=time.time())
        t = now_ns()
        if stats.compile_events != last_events:
            last_events, last_change = stats.compile_events, t
        if (t >= t0 + timing["lead_min_s"] * 1e9
                and t - last_change >= STILL_S * 1e9):
            break
        if t > t0 + timing["lead_max_s"] * 1e9:
            say("lead-in: compile stats never stood still")
            break
    unlatched = system.unlatched(members)
    w0 = now_ns()
    w1 = w0 + int(seconds * 1e9)
    gen.until(w1 + (0 if trace_dir else int(0.3e9)))
    c0 = system.counters()
    # the interpreter's collector pauses the tick thread: time each
    # collection of the window (a log line, not a metric)
    gc_pauses, gc_t0 = [], [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_t0[0] = time.perf_counter()
        else:
            gc_pauses.append((info["generation"],
                              time.perf_counter() - gc_t0[0]))

    gc.callbacks.append(on_gc)
    tick_s, rx, t_end, ledgers = [], [], [], []
    slice_c0 = slice_i0 = None
    tracing = False
    t_trace = w1 - int(min(TRACE_SLICE_S, seconds / 2) * 1e9)
    while True:
        t = now_ns()
        if t >= w1:
            break
        if trace_dir and not tracing and t >= t_trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing = True
            slice_c0, slice_i0 = system.counters(), len(rx)
        n0 = loop.rx_packets
        if tracing:
            with jax.profiler.TraceAnnotation("bench:tick"):
                sup.tick(now=time.time())
        else:
            sup.tick(now=time.time())
        tick_s.append(sup.last_tick_s)
        rx.append(loop.rx_packets - n0)
        t_end.append(now_ns())
        ledgers.append(sup.last_ledger)
    c1 = system.counters()
    gc.callbacks.remove(on_gc)
    trace_slice = None
    if tracing:
        # the sender stopped at w1: flush what is in flight, then stop
        # the profiler (which stalls this thread) outside the window
        for _ in range(3):
            sup.tick(now=time.time())
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        say(f"trace: stop_trace took {time.perf_counter() - t_stop:.1f}s")
        trace_slice = {
            "rx": np.asarray(rx[slice_i0:], dtype=np.int64),
            "rx_packets": c1["rx_packets"] - slice_c0["rx_packets"],
            "forwarded": c1["forwarded"] - slice_c0["forwarded"]}
    t_grace = now_ns() + int((timing["grace_s"] + 0.4) * 1e9)
    while now_ns() < t_grace:
        sup.tick(now=time.time())
    stages = {}
    for i, led in enumerate(ledgers):
        for k, v in led.items():
            stages.setdefault(k, np.zeros(len(ledgers)))[i] = v
    return {"t0": t0, "w0": w0, "w1": w1, "gc_pauses": gc_pauses,
            "unlatched_members": unlatched,
            "counters": {k: c1[k] - c0[k] for k in c0},
            "after": system.counters(),
            "ticks": {"tick_s": np.asarray(tick_s), "stage": stages,
                      "rx": np.asarray(rx, dtype=np.int64),
                      "t_end": np.asarray(t_end, dtype=np.int64)},
            "trace_slice": trace_slice}


def window_timing(traffic: dict) -> dict:
    return {"lead_min_s": float(traffic.get("lead_min_s", LEAD_MIN_S)),
            "lead_max_s": float(traffic.get("lead_max_s", LEAD_MAX_S)),
            "grace_s": float(traffic.get("grace_s", GRACE_S))}


def reduce_window_trace(trace_dir, trace_slice, out_dir):
    import reduce

    path = reduce.find_xplane(trace_dir)
    with open(os.path.join(out_dir, "trace_lines.json"), "w") as f:
        json.dump(reduce.describe_xplane(path), f, indent=1)
    trace = reduce.load_xplane(path)
    ticks = trace["host"].get("bench:tick")
    if ticks:
        lo = min(s for s, _d in ticks)
        hi = max(s + d for s, d in ticks)
        trace = reduce.clip_trace(trace, lo, hi)
    red = reduce.reduce_trace(trace)
    red["slice"] = trace_slice
    red["xplane"] = path
    return red


def judge(traffic, win, client, sample) -> list:
    """Every number `correct` rests on, beside its limit: a list of
    `(name, number, "==" or ">=", limit, passed)`."""
    c, after = win["counters"], win["after"]
    checks = [
        # members of the plan's conferences whose address the bridge
        # had not learnt when the window opened: they receive nothing
        ("unlatched_members", win["unlatched_members"], "==", 0),
        ("sample_opened", sample["checked"], ">=",
         traffic.get("sample_min", 4096)),
        ("sample_bad_tag", sample["bad_tag"], "==", 0),
        ("sample_bad_bytes", sample["bad_bytes"], "==", 0),
        ("foreign_deliveries", client["foreign"], "==", 0),
        ("duplicate_deliveries", client["duplicates"], "==", 0),
        ("unknown_deliveries", client["unknown"], "==", 0),
        ("client_record_overflow", int(client["rx_overflow"]), "==", 0),
        ("compile_events_in_window", c["compile_events"], "==", 0),
        ("datapath_recompiles_in_window", c["datapath_recompiles"],
         "==", 0),
    ]
    if traffic.get("require_no_shedding", True):
        for k in ("shed", "quarantined", "level", "quarantine_total",
                  "refused"):
            checks.append((f"supervisor_{k}", after[k], "==", 0))
    out = []
    for name, got, op, lim in checks:
        good = bool(got >= lim if op == ">=" else got == lim)
        out.append((name, int(got), op, int(lim), good))
        say(check_line(out[-1]))
    return out


def check_line(check) -> str:
    name, got, op, lim, good = check
    return (f"check {name}: {got} (limit {op} {lim})"
            + ("" if good else "  <-- FAILS"))


def log_window(seconds, win, client, sample, fanout) -> None:
    """What a reader of the run's log wants beside the result line."""
    t = win["ticks"]
    busy = t["rx"] > 0
    say(f"window: {seconds:.0f}s, {len(t['rx'])} ticks "
        f"({int(busy.sum())} with packets), bridge read "
        f"{win['counters']['rx_packets']} packets, sent "
        f"{win['counters']['forwarded']}; offered "
        f"{client['offered_packets']} packets = {client['offered']} "
        f"deliveries due, {client['received_due']} received, "
        f"{client['lost']} lost; sender sent {client['sent']}, late p99 "
        f"{client['late_p99_ms']:.3f} ms max {client['late_max_ms']:.3f} "
        f"ms, send errors {client['send_errors']}; client socket drops "
        f"{client['rx_drops']}; rtcp datagrams {client['rtcp']}")
    if len(client["latency_ns"]) > 8:
        say(f"latency ({client['stamp']} receive stamps) over "
            f"{len(client['latency_ns'])} deliveries / "
            f"{int(busy.sum())} ticks: p50 {client['lat_p50_ms']:.3f} ms "
            f"p99 {client['lat_p99_ms']:.3f} ms; first-quarter p50 "
            f"{client['lat_first_quarter_p50_ms'] or -1:.3f} last-quarter "
            f"p50 {client['lat_last_quarter_p50_ms'] or -1:.3f}; tick p50 "
            f"{np.median(t['tick_s'][busy]) * 1e3:.2f} ms")
        say("latency percentiles ms: " + json.dumps(
            client["lat_percentiles_ms"]))
    order = np.argsort(t["tick_s"])[::-1][:3]
    say("slowest ticks (s into window, ms, packets, stage ms): "
        + "; ".join(
            f"{(t['t_end'][i] - win['w0']) / 1e9:.1f}s "
            f"{t['tick_s'][i] * 1e3:.0f}ms {int(t['rx'][i])}p "
            + str({k: round(float(v[i]) * 1e3, 1)
                   for k, v in t["stage"].items()})
            for i in order))
    quarter = np.minimum(3, (t["t_end"] - win["w0"]) * 4
                         // (win["w1"] - win["w0"]))
    say("by quarter of the window, tick p50 ms / packets per tick: "
        + "; ".join(
            f"{np.median(t['tick_s'][quarter == q]) * 1e3:.1f} / "
            f"{np.mean(t['rx'][quarter == q]):.0f}"
            for q in range(4) if (quarter == q).any()))
    if busy.any():
        # against the program's row classes as they are today (64, 256,
        # 1,024, 4,096): a log line, no metric reads it
        rows = t["rx"][busy] * fanout
        say("packets a tick (ticks with packets): " + " ".join(
            f"p{q} {np.percentile(t['rx'][busy], q):.0f}"
            for q in (50, 90, 95, 99, 100))
            + f"; fan-out rows a tick (x {fanout} receivers), share of "
            "ticks: " + " ".join(
                f"{lo + 1}-{hi}: {100 * ((rows > lo) & (rows <= hi)).mean():.2f}%"
                for lo, hi in ((0, 64), (64, 256), (256, 1024),
                               (1024, 4096), (4096, 1 << 30))))
    gen2 = [p for g, p in win["gc_pauses"] if g == 2]
    say(f"interpreter gc in the window: {len(win['gc_pauses'])} "
        f"collections {sum(p for _g, p in win['gc_pauses']):.3f}s, of "
        f"them full (gen 2): {len(gen2)}, {sum(gen2):.3f}s, longest "
        f"{max(gen2, default=0.0) * 1e3:.0f} ms")
    say(f"sample: {sample['checked']} deliveries opened over "
        f"{sample['receivers']} receivers")


def run_window(system, config, traffic, peaks, gen, plan, sched, seconds,
               trace, out_dir) -> dict:
    trace_dir = os.path.join(out_dir, "trace") if trace else None
    timing = window_timing(traffic)
    win = drive_window(system, gen, timing, seconds, trace_dir)
    got = gen.finish()
    client = loadgen.analyze(plan, sched, got, win["t0"], win["w0"],
                             win["w1"], int(timing["grace_s"] * 1e9))
    sample = loadgen.verify_sample(plan, got)
    log_window(seconds, win, client, sample, system.fanout)
    checks = judge(traffic, win, client, sample)
    red = None
    if trace:
        red = reduce_window_trace(trace_dir, win["trace_slice"], out_dir)
    ctx = {"ticks": win["ticks"], "counters": win["counters"],
           "client": client,
           "trace": red, "peaks": peaks, "config": config,
           "traffic": traffic,
           "system": {"default_deadline_ms": system.default_deadline_ms,
                      "fanout": system.fanout,
                      "suite": config["profile"],
                      "mean_length": float(np.mean([
                          len(loadgen.plain_packet(plan, 1, i))
                          for i in range(2000)]))}}
    if traffic.get("attempted", "offered") == "forwarded":
        attempted = client["forwarded_due"]
        failed = attempted - client["forwarded_received"]
    else:
        attempted, failed = client["offered"], client["lost"]
    failed += sample["bad_tag"] + sample["bad_bytes"]
    return {"ctx": ctx, "win": win, "client": client, "sample": sample,
            "checks": checks, "correct": all(c[4] for c in checks),
            "attempted": int(attempted),
            "failed": int(failed), "seconds": seconds}


def layer_values(layer_metrics, ctx) -> dict:
    out = {}
    for m in layer_metrics:
        base = os.path.join(HERE, "layers", m["name"])
        if os.path.exists(base + ".py"):
            spec = importlib.util.spec_from_file_location(
                "layer_" + m["name"].replace(".", "_"), base + ".py")
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            v = mod.read(ctx)
        else:
            spec = load_json(base + ".json")
            v = readers.KINDS[spec["kind"]](ctx, spec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def e2e_values(names, res, setup_s, bench) -> dict:
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    c = res["client"]
    have = {"setup_s": setup_s}
    for n in names:
        # `delivered_pps`, or `delivered_pps.<suffix>` where a kind of
        # cell holds the same rate to a bound of its own
        if n.split(".")[0] == "delivered_pps":
            have[n] = c["delivered_in_window"] / res["seconds"]
        # `added_latency_p<q>_ms[.<suffix>]`: that percentile over all
        # deliveries
        m = re.fullmatch(r"added_latency_p(\d+)_ms(\..+)?", n)
        if m and len(c["latency_ns"]):
            have[n] = float(np.percentile(c["latency_ns"],
                                          int(m.group(1)))) / 1e6
    return {n: {"value": float(have[n]), "unit": units[n]}
            for n in names if have.get(n) is not None}


# ------------------------------------------------------------------ main

def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--fault", default="")
    ap.add_argument("--sweep", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--active", type=int, default=0)
    ap.add_argument("--traffic", default="",
                    help="another traffic file (rehearsals only)")
    ap.add_argument("--set", action="append", default=[],
                    help="lifecycle.<field>=<int>, for the set-up trial")
    args = ap.parse_args()

    bench, cell, config, traffic, e2e_names, layer_metrics = \
        resolve_cell(args.workload)
    seconds = float(args.seconds or bench["run_seconds"])
    rehearsal = args.rows > 0
    if args.traffic:
        if not rehearsal:
            raise SystemExit("--traffic is for rehearsals (--rows N)")
        traffic = load_json(HERE, "traffic", args.traffic + ".json")
    if rehearsal:
        config = dict(config, capacity=args.rows,
                      supervisor=dict(config["supervisor"],
                                      deadline_ms=60_000.0))
    for kv in args.set:
        k, v = kv.split("=")
        sect, field = k.split(".")
        config = dict(config, **{sect: dict(config[sect],
                                            **{field: int(v)})})
    n_active = args.active or loadgen.resolve_rate(traffic,
                                                   cell["config"])
    out_dir = os.path.join(HERE, "out", f"{args.workload}.{args.seed}"
                           f".t{args.trace}")
    os.makedirs(out_dir, exist_ok=True)

    # windows of this process: (seed, active conferences, fault)
    if args.sweep:
        windows = [(args.seed, int(a), "") for a in args.sweep.split(",")]
    elif args.seeds:
        windows = [(int(s), n_active, "") for s in args.seeds.split(",")]
        windows += [(windows[-1][0] + 1 + i, n_active, f) for i, f in
                    enumerate(x for x in args.faults.split(",") if x)]
    else:
        windows = [(args.seed, n_active, args.fault)]
    n_recv = int(traffic.get("receiver_processes", 4))
    timing = window_timing(traffic)

    def spawn(k):
        seed, a, fault = windows[k]
        plan = loadgen.make_plan(
            config, traffic, seed, a,
            timing["lead_max_s"] + seconds + timing["grace_s"] + 1.0,
            first_index=loadgen.FIRST_INDEX + k * INDEX_STRIDE,
            fault=fault if fault in loadgen.CLIENT_FAULTS else "",
            sample_over_s=timing["lead_min_s"] + seconds)
        # keys are installed once, from the first window's seed
        plan["key_seed"] = windows[0][0]
        return plan, loadgen.Generator(
            plan, os.path.join(out_dir, f"gen{k}"), n_recv)

    split = {"start": time.time() - T_START}
    # the generator children first: they never import JAX, and protect
    # the window's packets under the oracle while the bridge comes up
    plan, gen = spawn(0)
    system = None
    try:
        split["native_build"] = build_native()
        t = time.perf_counter()
        device, peaks = device_gate(int(cell["chips"]), rehearsal)
        from libjitsi_tpu.utils.compile_cache import enable_compile_cache

        cache_dir = enable_compile_cache()
        split["jax_import_and_device"] = time.perf_counter() - t
        t = time.perf_counter()
        import sut

        system = sut.System(config, say)
        split["bridge_construct"] = time.perf_counter() - t
        adm = system.admit_all(windows[0][0])
        split["admission"] = adm["admit_s"]
        say(f"bridge socket SO_RCVBUF {system.socket_rcvbuf()} bytes; "
            f"compile cache {cache_dir}")
        results = []
        for k in range(len(windows)):
            if k:
                # a chained window starts as a process does: the last
                # window's sockets are closed, so what it latched is
                # stale (and a port may now be another endpoint's)
                system.forget_addresses()
                plan, gen = spawn(k)
            t = time.perf_counter()
            info = gen.wait_ready()
            if k == 0:
                split["generator_wait"] = time.perf_counter() - t
            say(f"generator ready: {info['packets']} packets protected "
                f"in {info['protect_s']:.1f}s (waited "
                f"{time.perf_counter() - t:.1f}s); window {k}: seed "
                f"{windows[k][0]}, {len(plan['active'])} active "
                f"conferences, fault {windows[k][2] or 'none'}")
            mend = (system.break_fanout()
                    if windows[k][2] == "bridge-bitflip" else None)
            res = run_window(system, config, traffic, peaks, gen, plan,
                             gen.sched, seconds, bool(args.trace),
                             out_dir)
            gen = None
            if mend:
                mend()
            res["setup_s"] = res["win"]["w0"] / 1e9 - T_START
            if k == 0:
                split["latch_and_warm_ticks"] = \
                    (res["win"]["w0"] - res["win"]["t0"]) / 1e9
            results.append(res)
            c = res["client"]
            say("window result: " + json.dumps({
                "seed": windows[k][0], "active": len(plan["active"]),
                "fault": windows[k][2], "correct": res["correct"],
                "offered_pps": c["offered"] / seconds,
                "delivered_pps": c["delivered_in_window"] / seconds,
                "lost": c["lost"], "lat_p50_ms": c["lat_p50_ms"],
                "lat_p99_ms": c["lat_p99_ms"],
                "lat_q1_p50_ms": c["lat_first_quarter_p50_ms"],
                "lat_q4_p50_ms": c["lat_last_quarter_p50_ms"],
                "tick_p50_ms": readers.tick_quantile(
                    res["ctx"], {"q": 50, "scale": 1e3}),
                "rx_per_tick": float(np.mean(
                    res["ctx"]["ticks"]["rx"])),
                "compiles": res["win"]["counters"]["compile_events"],
                "late_p99_ms": c["late_p99_ms"],
                "rx_drops": c["rx_drops"]}))
            if args.sweep and (
                    res["win"]["counters"]["compile_events"]
                    or c["lost"] > 0.2 * c["offered"]):
                say("sweep: stopping, the bridge is past its knee "
                    "(compiles in the window or a fifth of the "
                    "deliveries lost)")
                break
        stats = system.stats
        say(f"compile cache: hits {stats.hits} misses {stats.misses}; "
            f"compile events {stats.compile_events} "
            f"({stats.compile_seconds:.0f}s thread-summed)")
        say("set-up split (s): " + json.dumps(
            {k: round(v, 2) for k, v in split.items()}))
        device["memory_peak_bytes"] = memory_peak()
    finally:
        if gen is not None:
            gen.close()
        if system is not None:
            system.close()

    res = results[0]
    if args.trace:
        metrics = layer_values(layer_metrics, res["ctx"])
        red = res["ctx"]["trace"] or {}
        device["busy_s"] = red.get("busy_s", 0.0)
        device["window_s"] = red.get("window_s", 0.0)
        say("trace: " + json.dumps({k: red.get(k) for k in (
            "window_s", "busy_s", "idle_pct", "program_s",
            "program_launches", "idle_by_stage_s", "top_ops")}))
    else:
        metrics = e2e_values(e2e_names, res, res["setup_s"], bench)
    line = {"correct": all(r["correct"] for r in results) and not
            rehearsal,
            "attempted": res["attempted"], "failed": res["failed"],
            "metrics": metrics, "device": device}
    if args.trace and "breakdown" in (res["ctx"]["trace"] or {}):
        line["breakdown"] = res["ctx"]["trace"]["breakdown"]
    if len(results) > 1:
        line["windows"] = [{"correct": r["correct"]} for r in results]
    # every number compared beside its limit, last on the line and last
    # on stderr (of the first window that failed, else of the first)
    shown = next((r for r in results if not r["correct"]), res)
    line["checks"] = {name: {"value": got, "limit": f"{op} {lim}"}
                      for name, got, op, lim, _good in shown["checks"]}
    print(json.dumps(line), flush=True)
    for check in shown["checks"]:
        print(check_line(check), file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
