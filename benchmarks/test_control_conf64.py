"""`test_control.py`'s runs for the cell of 64-member conferences: the
rehearsal of `audio-sfu-cm-10k-conf64.meeting-paced` at a size a CPU
holds must come out sound where nothing is broken, `correct: false`
under every fault, and must compile nothing when a tick's fan-out rows
outgrow the largest row class its ladder warmed.

    python3 -m pytest benchmarks/test_control_conf64.py   (ten minutes)

`--rows 128` is two conferences of 64; `traffic/rehearsal-conf64.json`
has 3 of the active conference's 64 members speak at a slow period (an
XLA:CPU fan-out row takes about 2 ms and a packet is 63 of them).  One
process, one set-up, four windows: a sound one, `bridge-bitflip` (the
seeded sample catches the flipped payload bit), `no-latch` (the 61
listeners never reach the bridge: `unlatched_members` 61 and 61 of a
packet's 63 deliveries lost), `client-key-bit` (nothing opens; last,
because the quarantine outlasts the window).  A second process under
`traffic/rehearsal-conf64-burst.json` has 40 members hold their packets
back and release them inside the same 20 ms every 8 s: a tick finds a
burst of over 16 packets (1,024 rows, the 128-endpoint ladder's largest
class; the chip's 65 packets and 4,096 rows), which goes out in several
launches with 0 compile events and nothing lost.

Not part of the repo's tier-1 tests (`tests/test_sfu_conf64.py` holds
the split to the oracle there); the benchmark's own runs never run it.
"""

import json
import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "audio-sfu-cm-10k-conf64.meeting-paced"


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--rows", "128", *args],
        env=env, capture_output=True, text=True, timeout=3000)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-4000:]
    wins = [json.loads(line.split("window result: ", 1)[1])
            for line in p.stdout.splitlines() if "window result: " in line]
    return p, wins


def test_conf64_sound_true_faults_false():
    p, wins = _run("--traffic", "rehearsal-conf64", "--seconds", "12",
                   "--seeds", "7", "--faults",
                   "bridge-bitflip,no-latch,client-key-bit")
    assert [w["fault"] for w in wins] == [
        "", "bridge-bitflip", "no-latch", "client-key-bit"]
    sound, flipped, unlatched, badkey = wins
    assert sound["correct"] is True and sound["lost"] == 0, \
        p.stdout[-6000:]
    assert sound["compiles"] == 0
    assert flipped["correct"] is False
    assert unlatched["correct"] is False
    assert badkey["correct"] is False
    latched = [line.split("check ", 1)[1] for line in p.stdout.splitlines()
               if "check unlatched_members" in line]
    assert latched[0] == "unlatched_members: 0 (limit == 0)"
    assert latched[2] == "unlatched_members: 61 (limit == 0)  <-- FAILS"
    # 61 of a packet's 63 deliveries have no address to go to
    assert unlatched["lost"] * 63 == \
        unlatched["offered_pps"] * 12 * 61
    last = json.loads(p.stdout.strip().splitlines()[-1])
    # a rehearsal can never pass for a chip run
    assert last["correct"] is False
    assert last["device"]["platform"] == "cpu"


def test_conf64_backlog_tick_splits_and_compiles_nothing():
    p, wins = _run("--traffic", "rehearsal-conf64-burst", "--seconds",
                   "16", "--seed", "7", "--trace", "1")
    (win,) = wins
    assert win["correct"] is True and win["lost"] == 0, p.stdout[-6000:]
    assert win["compiles"] == 0
    # the window's largest tick, in packets: over the 16 that fill the
    # ladder's largest class at 63 receivers a packet
    top = max(int(m.group(1)) for m in re.finditer(
        r"packets a tick \(ticks with packets\):.* p100 (\d+);", p.stdout))
    assert top * 63 > 1024
    last = json.loads(p.stdout.strip().splitlines()[-1])
    m = last["metrics"]
    # the window's readers always read; the traced slice is the window's
    # last 3 s and holds one of the 8 s bursts or none (then the slice's
    # readers find no `stage:expand` and leave their metric out)
    for name in ("egress_send_us_per_row.paced",
                 "egress_worker_busy_pct.paced"):
        assert m[name]["value"] > 0
    for name in ("fanout_launches_per_tick_max.paced",
                 "expand_us_per_row.paced"):
        assert name not in m or m[name]["value"] > 0
    assert last["correct"] is False
