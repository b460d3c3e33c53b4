"""`test_control.py`'s runs for the GCM cell: the rehearsal of
`audio-sfu-gcm-10k.talk-paced` at a size a CPU holds must come out sound
where nothing is broken and `correct: false` under both faults.

    python3 -m pytest benchmarks/test_control_gcm.py     (a few minutes)

One rehearsal process (`--rows 64 --traffic rehearsal`), one set-up,
three windows: a sound one, `bridge-bitflip` (the seeded sample, opened
under the scalar RFC 7714 oracle with each client's own key, catches the
flipped payload bit: the AEAD tag covers it), `client-key-bit` (the
bridge rejects what the clients send and nothing opens).  Not part of
the repo's tier-1 tests; the benchmark's own runs never run it.
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def test_gcm_sound_true_faults_false():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "audio-sfu-gcm-10k.talk-paced", "--rows", "64", "--traffic",
         "rehearsal", "--seconds", "8", "--seeds", "7", "--faults",
         "bridge-bitflip,client-key-bit"],
        env=env, capture_output=True, text=True, timeout=3000)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-4000:]
    wins = [json.loads(line.split("window result: ", 1)[1])
            for line in p.stdout.splitlines() if "window result: " in line]
    assert [w["fault"] for w in wins] == ["", "bridge-bitflip",
                                          "client-key-bit"]
    assert wins[0]["correct"] is True, p.stdout[-6000:]
    assert wins[0]["compiles"] == 0
    assert wins[1]["correct"] is False
    assert wins[2]["correct"] is False
    last = json.loads(p.stdout.strip().splitlines()[-1])
    # a rehearsal can never pass for a chip run
    assert last["correct"] is False
    assert last["device"]["platform"] == "cpu"
