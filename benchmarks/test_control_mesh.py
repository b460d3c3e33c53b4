"""`test_control.py`'s runs for the mesh cell, and a check of the readers
the cell brings.

    python3 -m pytest benchmarks/test_control_mesh.py   (about ten minutes)

1. The rehearsal of `audio-sfu-cm-40k-mesh4.talk-paced` at a size a CPU
   holds (`--rows 64 --traffic rehearsal-mesh4`: 64 endpoints over four
   of the host's virtual devices, a bridge on a device mesh admitted
   through `request_join` with its warm ladder) must come out sound
   where nothing is broken, with nothing compiled in the window, and
   `correct: false` under both faults.
2. The readers of `layers/mesh_*.py` return a number on a four-plane
   slice and None on a one-plane one.  The four-plane slice is the
   recorded one-chip v5e slice (`fixtures/`) laid on four device planes
   with its two programs renamed to the mesh's; its numbers are
   re-derived here from the fixture's own.

Not part of the repo's tier-1 tests; the benchmark's own runs never run
it.
"""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

FIXTURE = os.path.join(HERE, "fixtures", "v5e-cm-talk-a9.trace.json.gz")
PLANE = "/device:TPU:0"


def test_mesh_sound_true_faults_false():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "audio-sfu-cm-40k-mesh4.talk-paced", "--rows", "64", "--traffic",
         "rehearsal-mesh4", "--seconds", "8", "--seeds", "7", "--faults",
         "bridge-bitflip,client-key-bit"],
        env=env, capture_output=True, text=True, timeout=3000)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-4000:]
    wins = [json.loads(line.split("window result: ", 1)[1])
            for line in p.stdout.splitlines() if "window result: " in line]
    assert [w["fault"] for w in wins] == ["", "bridge-bitflip",
                                          "client-key-bit"]
    assert wins[0]["correct"] is True, p.stdout[-6000:]
    assert wins[0]["compiles"] == 0 and wins[0]["lost"] == 0
    assert wins[1]["correct"] is False
    assert wins[2]["correct"] is False
    last = json.loads(p.stdout.strip().splitlines()[-1])
    # a rehearsal can never pass for a chip run
    assert last["correct"] is False
    assert last["device"]["platform"] == "cpu"


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        "layer_" + name, os.path.join(HERE, "layers", name + ".paced.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _ctx(path: str) -> dict:
    return {"trace": {"xplane": path,
                      "slice": {"rx": np.array([0, 30, 40, 0, 50]),
                                "rx_packets": 120, "forwarded": 840}},
            "system": {"fanout": 7, "suite": "AES_CM_128_HMAC_SHA1_80",
                       "mean_length": 112.0},
            "peaks": json.load(open(os.path.join(HERE, "peaks.json")))[
                "TPU v5 lite"]}


def test_plane_readers_on_four_planes_and_on_one(tmp_path):
    import opcount
    import planes
    import reduce

    one = reduce.load_fixture(FIXTURE)
    expect = json.load(open(FIXTURE.replace(".trace.json.gz",
                                            ".expect.json")))
    names = {"jit__fanout_protect": "jit_mesh_fanout_protect",
             "jit__unprotect_rtp_impl": "jit_mesh_unprotect_rtp"}
    lines = one["device"][PLANE]
    four = {"host": one["host"], "device": {}}
    for d in range(4):
        # plane d runs d / 4 of the recorded ops less: planes differ
        keep = {ln: evs[:len(evs) - d * len(evs) // 4]
                for ln, evs in lines.items()}
        keep[reduce.MODULES_LINE] = [
            [names.get(reduce.program_name(n), n) + "(1)", s, t]
            for n, s, t in lines[reduce.MODULES_LINE]]
        four["device"][f"/device:TPU:{d}"] = keep
    path4 = str(tmp_path / "four.trace.json.gz")
    reduce.save_fixture(four, path4)

    ctx4, ctx1 = _ctx(path4), _ctx(FIXTURE)
    got = planes.of(ctx4)
    assert sorted(got["planes"]) == [f"/device:TPU:{d}" for d in range(4)]
    assert abs(got["window_s"] - expect["window_s"]) < 1e-9
    busy = [p["busy_s"] for p in got["planes"].values()]
    assert abs(busy[0] - expect["busy_s"]) < 1e-9
    assert busy[0] > busy[1] > busy[2] > busy[3] > 0

    lo = _reader("mesh_device_busy_min_pct")(ctx4)
    hi = _reader("mesh_device_busy_max_pct")(ctx4)
    assert abs(hi - 100.0 * expect["busy_s"] / expect["window_s"]) < 1e-6
    assert 0 < lo < hi

    # the mesh programs' time summed over the planes; least time a chip
    dev_s = 4 * (expect["program_s"]["jit__fanout_protect"]
                 + expect["program_s"]["jit__unprotect_rtp_impl"])
    least = sum(opcount.least_time_s(opcount.call_cost(
        "AES_CM_128_HMAC_SHA1_80", rows, 112.0), ctx4["peaks"])
        for n in (30, 40, 50) for rows in (n, 7 * n))
    us = _reader("mesh_crypto_device_us_per_pkt")(ctx4)
    roof = _reader("mesh_crypto_roofline_pct")(ctx4)
    assert abs(us - 1e6 * dev_s / 960) < 1e-6 * us
    assert abs(roof - 100.0 * least / dev_s) < 1e-6 * roof
    assert 0 < roof < 105
    # the same work on one plane's worth of time reads four times the
    # share: what `crypto_roofline_pct`'s reader would say of a mesh
    assert abs(4 * roof - 100.0 * least / (dev_s / 4)) < 1e-6

    for name in ("mesh_device_busy_min_pct", "mesh_device_busy_max_pct",
                 "mesh_crypto_device_us_per_pkt", "mesh_crypto_roofline_pct"):
        assert _reader(name)(ctx1) is None, name
        assert _reader(name)({"trace": None}) is None, name


def test_span_readers_on_a_mesh_tick_and_on_a_one_chip_tick(monkeypatch):
    import xstats

    def tick(t, rows, lanes=None, hot=None):
        mesh = {} if lanes is None else {
            "shards": 4, "lanes": lanes, "rows_hottest_shard": hot,
            "affine": 0}
        return [("stage:expand", t, 10, {"tick": t, "rows": rows,
                                         "rows_padded": rows}),
                ("stage:fanout_dispatch", t + 10, 10,
                 dict(mesh, tick=t, h2d_arrays=6))]

    mesh_evs = {"host": tick(1, 400, 256, 140) + tick(2, 100, 64, 25)
                + tick(3, 900, 1024, 300), "modules": [], "lo": 0,
                "hi": 100}
    one_evs = {"host": tick(1, 400) + tick(2, 100), "modules": [],
               "lo": 0, "hi": 100}
    ctx = {"trace": {"xplane": "x"}}
    monkeypatch.setattr(xstats, "load", lambda _p: mesh_evs)
    assert _reader("mesh_lanes_useful_pct")(ctx) == float(np.median(
        [100 * 400 / 1024, 100 * 100 / 256, 100 * 900 / 4096]))
    assert _reader("mesh_hot_shard_share_pct")(ctx) == float(np.median(
        [35.0, 25.0, 100 * 300 / 900]))
    # a tick of two width classes launches the fan-out twice: each
    # launch's lanes are its own (64 + 256 a chip, not 320 x 8 shards)
    two = tick(4, 300, 64, 60)
    two = two + [two[0][:3] + ({"tick": 4, "rows": 500},),
                 two[1][:3] + (dict(two[1][3], lanes=256,
                                    rows_hottest_shard=200),)]
    monkeypatch.setattr(xstats, "load",
                        lambda _p: dict(mesh_evs, host=two))
    assert _reader("mesh_lanes_useful_pct")(ctx) == \
        100 * 800 / (4 * 64 + 4 * 256)
    assert _reader("mesh_hot_shard_share_pct")(ctx) == 100 * 260 / 800
    monkeypatch.setattr(xstats, "load", lambda _p: one_evs)
    assert _reader("mesh_lanes_useful_pct")(ctx) is None
    assert _reader("mesh_hot_shard_share_pct")(ctx) is None
    assert _reader("mesh_lanes_useful_pct")({"trace": None}) is None
