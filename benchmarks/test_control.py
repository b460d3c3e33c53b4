"""The runs that must come out `correct: false`, at a size a CPU holds.

    python3 -m pytest benchmarks/test_control.py        (a few minutes)

One rehearsal process (`--rows 64`: the device gate lifted, everything
else as on the chip), one set-up, three windows:

* a sound window — every check of `judge` passes;
* the timed path broken underneath (`bridge-bitflip`: one payload bit
  flipped in a share of the fan-out rows where the bridge produces
  them) — the seeded sample catches it;
* the control (`client-key-bit`: the clients' keys one bit off, which
  breaks the authentication guarantee the configuration states) — the
  bridge rejects what the clients send and nothing opens.

A second rehearsal has listeners (`traffic/rehearsal-listeners.json`: 2
of the conference's 8 speak): its sound window loses nothing and finds
every member latched, and with the generator withholding the listeners'
first packets (`no-latch`) `unlatched_members` reads 6 and six sevenths
of the deliveries are lost.

Not part of the repo's tier-1 tests (`tests/`); the benchmark's own
runs never run it.
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def _windows(stdout: str):
    out = []
    for line in stdout.splitlines():
        if "window result: " in line:
            out.append(json.loads(line.split("window result: ", 1)[1]))
    return out


def test_sound_true_faults_false():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "audio-sfu-cm-10k.talk-paced", "--rows", "64", "--traffic", "rehearsal",
         "--seconds", "8", "--seeds", "7", "--faults",
         "bridge-bitflip,client-key-bit"],
        env=env, capture_output=True, text=True, timeout=3000)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-4000:]
    wins = _windows(p.stdout)
    assert [w["fault"] for w in wins] == ["", "bridge-bitflip",
                                          "client-key-bit"]
    assert wins[0]["correct"] is True, p.stdout[-6000:]
    assert wins[1]["correct"] is False
    assert wins[2]["correct"] is False
    last = json.loads(p.stdout.strip().splitlines()[-1])
    # a rehearsal can never pass for a chip run
    assert last["correct"] is False
    assert last["device"]["platform"] == "cpu"


def test_listeners_latched_and_no_latch_false():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "audio-sfu-cm-10k.talk-burst", "--rows", "64", "--traffic",
         "rehearsal-listeners", "--seconds", "8", "--seeds", "7",
         "--faults", "no-latch"],
        env=env, capture_output=True, text=True, timeout=3000)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-4000:]
    sound, broken = _windows(p.stdout)
    assert (sound["fault"], broken["fault"]) == ("", "no-latch")
    assert sound["correct"] is True and sound["lost"] == 0, \
        p.stdout[-6000:]
    latched = [line.split("check ", 1)[1] for line in p.stdout.splitlines()
               if "check unlatched_members" in line]
    assert latched == ["unlatched_members: 0 (limit == 0)",
                       "unlatched_members: 6 (limit == 0)  <-- FAILS"]
    assert broken["correct"] is False
    # six of a packet's seven deliveries have no address to go to
    assert broken["lost"] * 7 == broken["offered_pps"] * 8 * 6
    # the numbers compared, of the window that failed: last on the
    # result line and last on stderr
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(last)[-1] == "checks"
    assert last["checks"]["unlatched_members"] == {"value": 6,
                                                   "limit": "== 0"}
    assert "check unlatched_members: 6 (limit == 0)  <-- FAILS" in \
        p.stderr.splitlines()[-len(last["checks"]):]


def test_off_the_chip_no_result():
    """Without --rows the run refuses a non-TPU platform: non-zero
    exit, no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "audio-sfu-cm-10k.talk-paced", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
