"""`benchmarks/seams.py` over synthetic slices: known host spans, the
runtime's enqueue / completion events and device programs on a clock
of their own give the known lags, skews and overhead; what does not
pair up gives no number."""

import importlib.util
import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
sys.path.insert(0, BENCH)

import seams  # noqa: E402

CTX = {"trace": {"xplane": "x"}}
US = 1_000


def _reader(name):
    path = os.path.join(BENCH, "layers", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _ev(stage, tick, start_us, dur_us, **stats):
    return ("stage:" + stage, start_us * US, dur_us * US,
            dict(stats, tick=tick))


def _call(tick, t0, seam="unprotect", put=(20, 30), dispatch=100,
          block=400, d2h=150):
    """One device call opening at `t0` us: dispatch (the put inside
    it), block, copy back, back to back."""
    st = seams.SEAMS[seam]
    return [_ev(st["dispatch"], tick, t0, dispatch),
            _ev(st["put"], tick, t0 + put[0], put[1]),
            _ev(st["block"], tick, t0 + dispatch, block),
            _ev(st["d2h"], tick, t0 + dispatch + block, d2h)]


#: the device's clock runs this far behind the host's (and steps)
OFFSET_US = 4_300
#: enqueue returns this long before the chip starts; the host sees the
#: end this long after it
PICKUP_US, NOTICE_US = 0, 400


def _tick(tick, t0, prog_u=(180, 120), prog_f=(260, 500)):
    """A tick at `t0` us: unprotect call (inside `unprotect_wait`), then
    the fan-out call 2 ms later; (start offset, duration) of each
    program from its call's opening, us, on the HOST clock.  None: no
    such program.  Returns (host events, [(run_id, start_us, dur_us)])."""
    host = [_ev("tick", tick, t0, 5000, rx=3),
            _ev("unprotect_wait", tick, t0 + 10, 680)]
    host += _call(tick, t0 + 20)
    host += _call(tick, t0 + 2000, "fanout", dispatch=200, block=700,
                  d2h=300)
    progs = []
    if prog_u:
        progs.append((2 * tick, t0 + 20 + prog_u[0], prog_u[1]))
    if prog_f:
        progs.append((2 * tick + 1, t0 + 2000 + prog_f[0], prog_f[1]))
    return host, progs


def _chip(progs, offset_us=OFFSET_US, pickup_us=PICKUP_US):
    """The runtime's events and the device's for `progs` (host-clock
    starts): the enqueue returns `pickup_us` before the start, the
    host sees the end `NOTICE_US` after it, the device's clock reads
    `offset_us` less (a number, or a function of the run id)."""
    off = offset_us if callable(offset_us) else (lambda _r: offset_us)
    return {
        "modules": sorted(((r, (s - off(r)) * US, d * US)
                           for r, s, d in progs), key=lambda m: m[1]),
        "enqueue": sorted(((s - pickup_us - 40) * US, (s - pickup_us) * US,
                           r) for r, s, _d in progs),
        "seen": {r: (s + d + NOTICE_US) * US for r, s, d in progs},
        # a put's bytes have crossed 50 us before its program starts; a
        # copy back's 500 us after the program ended
        "h2d_done": sorted((s - 50) * US for _r, s, _d in progs),
        "d2h_done": sorted((s + d + 500) * US for _r, s, d in progs)}


def _slice(*ticks, chips=1, **chip_kw):
    host = sorted((e for h, _p in ticks for e in h), key=lambda e: e[1])
    progs = [p for _h, ps in ticks for p in ps]
    return {"host": host, "lo": 0, "hi": 1 << 62,
            "chips": {k: _chip(progs, **chip_kw) for k in range(chips)}
            if progs else {}}


@pytest.fixture
def three_ticks(monkeypatch):
    evs = _slice(_tick(1, 0), _tick(2, 10_000, prog_u=(200, 100)),
                 _tick(3, 20_000, prog_u=(160, 140), prog_f=(300, 400)))
    monkeypatch.setattr(seams, "load", lambda _p: evs)
    return evs


@pytest.mark.parametrize("metric, want_ms", [
    # program start - dispatch start: 180, 200, 160 us
    ("unprotect_device_start_lag_p50_ms", 0.180),
    # block end (t0 + 500) - program end: 200, 200, 200 us
    ("unprotect_device_end_lag_p50_ms", 0.200),
    # 260, 260, 300 us
    ("fanout_device_start_lag_p50_ms", 0.260),
    # wait end (t0 + 900) - program end: 140, 140, 200 us
    ("fanout_device_end_lag_p50_ms", 0.140),
    # 680 + 200 + 700 + 300 us of spans less (120 + 500), (100 + 500),
    # (140 + 400) of programs: 1260, 1280, 1340
    ("device_seam_overhead_p50_ms", 1.280),
])
@pytest.mark.parametrize("kind", ["paced", "sat"])
def test_known_events_give_known_numbers(three_ticks, metric, want_ms,
                                         kind):
    """The device's clock is 4.3 ms behind the host's: the tie through
    the runtime's enqueue puts every program where it ran."""
    assert _reader(f"{metric}.{kind}")(CTX) == pytest.approx(want_ms)


def test_identity_holds_for_every_paired_call(three_ticks):
    for seam, st in seams.SEAMS.items():
        per_tick = seams.pair(three_ticks["host"], three_ticks["chips"][0],
                              st["dispatch"], st["block"])
        assert sorted(per_tick) == [1, 2, 3]
        for (t0, t1, p0, p1), in per_tick.values():
            assert (p0 - t0) + (p1 - p0) + (t1 - p1) == t1 - t0
            assert t0 <= p0 < p1 <= t1


def test_the_copies_own_events_split_the_lags(three_ticks):
    """`TransferToDevice ... Done` splits the start lag, the host's
    seeing the end splits the end lag, `TransferFromDevice ... Done`
    the copy back (tick 1's numbers; the medians are its)."""
    chip, st = three_ticks["chips"][0], seams.SEAMS["unprotect"]
    per_tick = seams.pair(three_ticks["host"], chip, st["dispatch"],
                          st["block"])
    got = seams._milestones(three_ticks["host"], chip, st, {1: per_tick[1]})
    # opens at 20: put 40-70, program 200-320, block ends 520, copy
    # back 520-670; bytes in at 150, end seen at 720 (after the block:
    # no split there), bytes back at 820 (after the span: none either)
    assert got == {"open_to_put": 0.02, "put_end_to_h2d_done": 0.08,
                   "h2d_done_to_program_start": 0.05}
    chip = dict(chip, seen={2: 400 * US}, d2h_done=[600 * US])
    got = seams._milestones(three_ticks["host"], chip, st, {1: per_tick[1]})
    assert got["program_end_to_seen"] == 0.08
    assert got["seen_to_block_end"] == 0.12
    assert got["d2h_start_to_d2h_done"] == 0.08
    assert got["d2h_done_to_d2h_end"] == 0.07


def test_the_tie_takes_the_fastest_pickup_of_the_neighbourhood():
    """Pick-up delays of 0-30 us: the launch with none ties the clock,
    the others start that much after their enqueue returned."""
    progs = [(r, 1000 * r, 100) for r in range(1, 9)]
    chip = _chip(progs)
    for r, (s, e, _run) in zip(range(1, 9), chip["enqueue"]):
        late = 10 * (r % 4)             # 10, 20, 30, 0, 10, ...
        chip["enqueue"][r - 1] = (s - late * US, e - late * US, r)
    got = seams.programs(chip)
    assert got == {r: (s * US, (s + d) * US) for r, s, d in progs}
    assert seams.slack_ms(chip) == pytest.approx(
        (NOTICE_US + 15) / 1000)


def test_a_step_of_the_devices_clock_drops_the_launches_round_it(
        monkeypatch):
    """The chip's clock steps by 2.8 ms between two launches (as a v5e
    slice did): launches whose neighbourhood holds both offsets get no
    instant, the others read as if nothing had happened."""
    monkeypatch.setattr(seams, "WINDOW", 4)
    ticks = [_tick(k, 10_000 * k) for k in range(1, 41)]
    evs = _slice(*ticks, offset_us=lambda r: 4_300 if r < 41 else 1_500)
    chip = evs["chips"][0]
    tied = seams.programs(chip)
    assert set(tied) == set(range(2, 82)) - set(range(37, 45))
    per_tick = seams.pair(evs["host"], chip, "fanout_dispatch",
                          "fanout_wait")
    assert [t for t, c in per_tick.items() if c is None] == \
        [18, 19, 20, 21]
    monkeypatch.setattr(seams, "load", lambda _p: evs)
    assert seams.lag_p50_ms(CTX, "fanout", "start") == pytest.approx(0.26)
    assert seams.lag_p50_ms(CTX, "fanout", "end") == pytest.approx(0.14)


def test_two_size_classes_pair_in_order(monkeypatch):
    """A tick that unprotects two size classes makes two calls: the
    first program goes with the first dispatch / block."""
    host, progs = _tick(7, 0)
    host += _call(7, 800, put=(10, 20), dispatch=50, block=300, d2h=100)
    progs.append((99, 800 + 90, 200))
    evs = _slice((host, progs))
    monkeypatch.setattr(seams, "load", lambda _p: evs)
    calls = seams.pair(evs["host"], evs["chips"][0], "unprotect_dispatch",
                       "unprotect_block")[7]
    assert [(p0 - t0) // US for t0, _t1, p0, _p1 in calls] == [180, 90]
    assert [(t1 - p1) // US for _t0, t1, _p0, p1 in calls] == [200, 60]
    assert seams.lag_p50_ms(CTX, "unprotect", "start") == \
        pytest.approx((0.180 + 0.090) / 2)


@pytest.mark.parametrize("broken", [
    dict(prog_u=None),                  # the program is not in the trace
    dict(prog_u=(600, 100)),            # it starts after the block ended
    dict(prog_u=(300, 400)),            # it ends after the block ended
])
def test_a_tick_that_does_not_pair_gives_no_number(monkeypatch, broken):
    evs = _slice(_tick(1, 0, **broken))
    monkeypatch.setattr(seams, "load", lambda _p: evs)
    assert seams.pair(evs["host"], evs["chips"][0], "unprotect_dispatch",
                      "unprotect_block") == {1: None}
    assert seams.lag_p50_ms(CTX, "unprotect", "start") is None
    assert seams.lag_p50_ms(CTX, "unprotect", "end") is None
    assert seams.overhead_p50_ms(CTX) is None
    # the fan-out of the same tick still pairs
    assert seams.lag_p50_ms(CTX, "fanout", "start") == pytest.approx(0.26)


def test_one_bad_tick_in_twenty_is_left_out_not_counted(monkeypatch):
    ticks = [_tick(k, 10_000 * k) for k in range(1, 20)]
    ticks.append(_tick(20, 200_000, prog_f=None))
    evs = _slice(*ticks)
    monkeypatch.setattr(seams, "load", lambda _p: evs)
    assert seams.lag_p50_ms(CTX, "fanout", "end") == pytest.approx(0.14)
    # three in twenty is a reader that does not fit the program: no
    # number
    evs2 = _slice(*ticks[2:], _tick(1, 10_000, prog_f=None),
                  _tick(2, 20_000, prog_f=None))
    monkeypatch.setattr(seams, "load", lambda _p: evs2)
    assert seams.lag_p50_ms(CTX, "fanout", "end") is None


def test_unequal_dispatch_and_block_counts_do_not_pair():
    host, progs = _tick(1, 0)
    host = [e for e in host if e[0] != "stage:unprotect_block"]
    assert seams.pair(host, _chip(progs), "unprotect_dispatch",
                      "unprotect_block") == {1: None}


def test_parent_spans_read_the_fanout_and_the_overhead(monkeypatch):
    """The parent books `unprotect_wait`, `fanout_dispatch`,
    `fanout_wait` and `fanout_d2h` only: the fan-out's lags and the
    overhead read, the unprotect's lags and the new spans do not."""
    new = {"stage:" + s for s in ("unprotect_dispatch", "unprotect_put",
                                  "unprotect_block", "unprotect_d2h",
                                  "fanout_put")}
    host, progs = _tick(1, 0)
    evs = _slice(([e for e in host if e[0] not in new], progs))
    monkeypatch.setattr(seams, "load", lambda _p: evs)
    assert seams.lag_p50_ms(CTX, "unprotect", "start") is None
    assert seams.lag_p50_ms(CTX, "fanout", "start") == pytest.approx(0.26)
    assert seams.lag_p50_ms(CTX, "fanout", "end") == pytest.approx(0.14)
    assert seams.overhead_p50_ms(CTX) == pytest.approx(1.26)


@pytest.mark.parametrize("ctx", [
    {"trace": None}, {}, {"trace": {"xplane": None}}])
def test_untraced_run_reads_nothing(ctx):
    for seam in seams.SEAMS:
        for which in ("start", "end"):
            assert seams.lag_p50_ms(ctx, seam, which) is None
    assert seams.overhead_p50_ms(ctx) is None
    assert seams.chip_skew_p50_ms(ctx, "start") is None


def test_off_the_chip_reads_nothing(monkeypatch):
    """No device plane and no runtime events: no chip, no number."""
    host, _progs = _tick(1, 0)
    evs = _slice((host, []))
    monkeypatch.setattr(seams, "load", lambda _p: evs)
    assert seams.lag_p50_ms(CTX, "fanout", "start") is None
    assert seams.overhead_p50_ms(CTX) is None
    assert _reader("mesh_chip_end_skew_p50_ms.paced")(CTX) is None


def _four_chips(evs, starts_us, durs_us):
    """`evs` on four chips, each with a clock of its own: chip k's
    fan-out programs (odd run ids) start `starts_us[k]` later than chip
    0's and run `durs_us[k]`."""
    progs = [(r, s // US + OFFSET_US, d // US)
             for r, s, d in evs["chips"][0]["modules"]]
    return dict(evs, chips={k: _chip(
        [(r, s + starts_us[k], durs_us[k]) if r % 2 else (r, s, d)
         for r, s, d in progs], offset_us=OFFSET_US - 700 * k)
        for k in range(4)})


@pytest.mark.parametrize("which, want_ms", [("start", 0.045),
                                            ("end", 0.075)])
def test_four_chips_with_staggered_starts_give_the_skew(
        monkeypatch, three_ticks, which, want_ms):
    # starts 0 / 15 / 30 / 45 us after chip 0's; durations so that
    # ends lie at 400 / 425 / 450 / 475 us; four device clocks
    evs = _four_chips(three_ticks, (0, 15, 30, 45), (400, 410, 420, 430))
    monkeypatch.setattr(seams, "load", lambda _p: evs)
    assert _reader(f"mesh_chip_{which}_skew_p50_ms.paced")(CTX) == \
        pytest.approx(want_ms)
    calls = seams.chip_calls(CTX)
    assert len(calls) == 3 and all(len(c) == 4 for c in calls)
    # the tick thread waits for chip 3 every time
    assert {max(c, key=lambda n: c[n][1]) for c in calls} == {3}


def test_skew_needs_two_chips_and_every_chip_paired(monkeypatch,
                                                    three_ticks):
    evs = _four_chips(three_ticks, (0, 15, 30, 45), (400,) * 4)
    monkeypatch.setattr(seams, "load", lambda _p: dict(
        evs, chips={0: evs["chips"][0]}))
    assert seams.chip_skew_p50_ms(CTX, "start") is None
    # chip 2 lost its fan-out programs: no launch pairs on every chip
    lost = dict(evs["chips"][2], enqueue=[
        e for e in evs["chips"][2]["enqueue"] if e[2] % 2 == 0])
    chips = dict(evs["chips"])
    chips[2] = lost
    monkeypatch.setattr(seams, "load", lambda _p: dict(evs, chips=chips))
    assert seams.chip_skew_p50_ms(CTX, "end") is None


# ------------------------- the overlapped tick: dispatched by N, collected
# ------------------------- by N + 1, its spans carrying N

def _overlapped(n_ticks, period=5000):
    """`n_ticks` ticks as the bridge runs them: tick k collects tick
    k-1's fan-out (its `fanout_wait` / `fanout_d2h` carry k-1), makes
    its unprotect call, then dispatches its own fan-out, whose program
    runs while the tick ends and the next begins."""
    host, progs = [], []
    for k in range(1, n_ticks + 1):
        t0 = period * k
        host.append(_ev("tick", k, t0, period - 200, rx=3))
        if k > 1:
            # the collection: the launch was ready 900 us before
            host += [_ev("fanout_wait", k - 1, t0 + 400, 30, collected=1,
                         ready=1, hidden_us=1300),
                     _ev("fanout_d2h", k - 1, t0 + 430, 120)]
        host.append(_ev("unprotect_wait", k, t0 + 600, 680))
        host += _call(k, t0 + 610)
        st = seams.SEAMS["fanout"]
        host += [_ev(st["dispatch"], k, t0 + 3800, 200),
                 _ev(st["put"], k, t0 + 3820, 30)]
        progs += [(2 * k, t0 + 610 + 180, 120),
                  (2 * k + 1, t0 + 3800 + 260, 500)]
    return host, progs


def test_an_overlapped_slice_pairs_a_dispatch_with_its_own_wait(
        monkeypatch):
    """The fan-out's block closes in the NEXT tick, carrying its
    dispatch's `tick`: every tick but the slice's last (whose
    collection lies outside) pairs, the lags and the overhead are read,
    and the end lag is program end to COLLECTION."""
    evs = _slice(_overlapped(20))
    monkeypatch.setattr(seams, "load", lambda _p: evs)
    got = seams.pair(evs["host"], evs["chips"][0], "fanout_dispatch",
                     "fanout_wait")
    assert sorted(got) == list(range(1, 21))
    assert [t for t, c in got.items() if c is None] == [20]
    assert seams.lag_p50_ms(CTX, "fanout", "start") == pytest.approx(0.26)
    # the next tick opens 1,000 us after the dispatch began; the wait
    # ends 430 us into it; the program ended 760 us after its dispatch
    # opened: 1,000 + 200 + 430 - 760
    assert seams.lag_p50_ms(CTX, "fanout", "end") == pytest.approx(0.87)
    assert seams.lag_p50_ms(CTX, "unprotect", "start") == \
        pytest.approx(0.18)
    # 680 + 200 + 30 + 120 us of spans less 120 + 500 of programs
    assert seams.overhead_p50_ms(CTX) == pytest.approx(0.41)


def test_a_captured_trace_of_the_bridge_pairs(sfu_with_traffic, tmp_path,
                                              monkeypatch):
    """A profiler trace of the real bridge (off the chip: the host
    plane alone): each `stage:fanout_wait` carries its DISPATCH's
    `tick` beside `collected`, `ready` and `hidden_us`, and with a
    program laid between each dispatch and its wait `pair` pairs at
    least nine ticks in ten; the two readers read."""
    import time

    import jax

    import reduce
    import xstats

    sfu, sup, send = sfu_with_traffic
    send.until_forwarded()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(12):
            send()
            time.sleep(0.005)
            with jax.profiler.TraceAnnotation("bench:tick"):
                sup.tick(now=50.0)
    finally:
        jax.profiler.stop_trace()
    path = reduce.find_xplane(str(tmp_path))
    host = xstats.load(path)["host"]
    dispatched = seams.by_tick(host, "fanout_dispatch")
    waits = [(s, st) for n, s, _d, st in host if n == "stage:fanout_wait"]
    assert len(dispatched) == 12 and len(waits) == 12
    for start, st in waits[1:]:         # (the first: dispatched before)
        assert st["collected"] == 1 and st["ready"] in (0, 1)
        ((_d0, d1),) = dispatched[st["tick"]]
        # its own dispatch: the one that ended `hidden_us` before
        assert start - d1 == pytest.approx(st["hidden_us"] * 1e3,
                                           abs=150e3)
    # the device's side, as a chip would have written it: a program
    # 20 us after each call's dispatch ended, 50 us long
    progs = [(k, (e + 20_000) // US, 50) for k, e in enumerate(sorted(
        e for stage in ("fanout_dispatch", "unprotect_dispatch")
        for evs in seams.by_tick(host, stage).values() for _s, e in evs))]
    chip = _chip(progs, pickup_us=5)
    for st in seams.SEAMS.values():
        got = seams.pair(host, chip, st["dispatch"], st["block"])
        assert len(got) == 12
        assert sum(c is not None for c in got.values()) >= 0.9 * 12 - 1
    monkeypatch.setattr(seams, "load", lambda _p: {
        "host": host, "lo": 0, "hi": 1 << 62, "chips": {0: chip}})
    assert seams.lag_p50_ms(CTX, "fanout", "end") > 0.0
    ctx = {"trace": {"xplane": path}}
    assert _reader("fanout_collect_ready_pct.paced")(ctx) is not None
    assert _reader("fanout_hidden_p50_ms.sat")(ctx) > 0.0
    assert _reader("fanout_hidden_p50_ms.paced")({"trace": None}) is None
