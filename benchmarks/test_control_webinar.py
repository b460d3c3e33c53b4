"""`test_control.py`'s runs for the webinar room: the rehearsal of
`audio-sfu-cm-10k-webinar1k.presenter-paced` at a size a CPU holds must
come out sound where nothing is broken and `correct: false` under a
fault, and what it delivers must be what the plain reference says.

    python3 -m pytest benchmarks/test_control_webinar.py

`--rows 128` is out (a room is 512), so the rehearsal is ONE room:
`--rows 512`, a panel of 8 and 504 visitors, admitted by the
configuration's own rule.  `traffic/rehearsal-webinar.json` has the
presenter send a packet every 2 s (an XLA:CPU fan-out row takes about
2 ms and a packet is 511 of them).  One process, one set-up (minutes:
the ladder of 512 endpoints warms the fan-out up to the 1,024-row
class, a quarter of a minute a program on XLA:CPU), four windows: a
sound one, `bridge-bitflip` (the seeded sample catches the flipped
payload bit), `no-latch` (the 511 who listen never reach the bridge:
`unlatched_members` 511 and every delivery lost), `client-key-bit`
(nothing opens; last, because the quarantine outlasts the window).
(At ISSUE 47's first size, `--rows 1024` and a packet every 4 s, the
same four windows took a quarter of an hour, seven minutes of it the
ladder up to 4,096 rows: my CPU run, PR 47.)

The routing's reference is `who_hears`, the copy of
`tests/test_sfu_webinar.py`'s: a participant's packet reaches every
other member of its room exactly once, a visitor's reaches nobody,
nothing leaves the room.  The sound window's client records are held to
it delivery by delivery.

Not part of the repo's tier-1 tests (`tests/test_sfu_webinar.py` holds
the comparison there, under both suites); the benchmark's own runs
never run it.
"""

import json
import os
import subprocess
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
CELL = "audio-sfu-cm-10k-webinar1k.presenter-paced"
ROOM, PANEL = 512, 8


def who_hears(members, participants, sender):
    """The members a packet of `sender` reaches.  `members`: member ->
    room; `participants`: the members who take part.  A participant's
    packet reaches every other member of its room exactly once; a
    visitor's reaches nobody; nothing leaves the room."""
    if sender not in participants:
        return []
    return sorted(m for m, room in members.items()
                  if room == members[sender] and m != sender)


def test_who_hears_is_the_three_rules():
    members = {0: "a", 1: "a", 2: "a", 3: "b", 4: "b"}
    assert who_hears(members, {0, 3}, 0) == [1, 2]
    assert who_hears(members, {0, 3}, 3) == [4]
    assert who_hears(members, {0, 3}, 1) == []
    assert who_hears({7: "c"}, {7}, 7) == []


def test_webinar_sound_true_bitflip_false_and_deliveries_are_who_hears():
    import loadgen

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--rows", str(ROOM), "--traffic", "rehearsal-webinar",
         "--seconds", "24", "--seed", "7", "--seeds", "7", "--faults",
         "bridge-bitflip,no-latch,client-key-bit"],
        env=env, capture_output=True, text=True, timeout=6000)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-4000:]
    wins = [json.loads(line.split("window result: ", 1)[1])
            for line in p.stdout.splitlines() if "window result: " in line]
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
    assert latched[2] == \
        f"unlatched_members: {ROOM - 1} (limit == 0)  <-- FAILS"
    # nobody who listens has an address: every delivery is lost
    assert unlatched["lost"] == unlatched["offered_pps"] * 24 > 0
    last = json.loads(p.stdout.strip().splitlines()[-1])
    # a rehearsal can never pass for a chip run
    assert last["correct"] is False
    assert last["device"]["platform"] == "cpu"
    # the sound window's records (gen0) against the reference: every media
    # datagram a client received is a (receiver, sender) pair the
    # reference allows, each (receiver, sender, seq) once, and every
    # packet the presenter sent in the window reached all of them
    out = os.path.join(HERE, "out", f"{CELL}.7.t0", "gen0")
    recs = np.concatenate([np.load(os.path.join(out, f))["recs"]
                           for f in sorted(os.listdir(out))
                           if f.startswith("rx")])
    recs = recs[(recs["b1"] & 0x7F) == loadgen.RTP_PT]
    members = {m: 0 for m in range(ROOM)}
    panel = set(range(PANEL))
    allowed = {s: set(who_hears(members, panel, s)) for s in range(ROOM)}
    seen = set()
    for rx, ssrc, seq in zip(recs["rx"].tolist(), recs["ssrc"].tolist(),
                             recs["seq"].tolist()):
        sender = ssrc - loadgen.SSRC_BASE
        assert rx in allowed[sender], (rx, sender)
        assert (rx, sender, seq) not in seen
        seen.add((rx, sender, seq))
    by_packet = {}
    for rx, sender, seq in seen:
        by_packet.setdefault((sender, seq), set()).add(rx)
    assert {s for s, _q in by_packet} <= panel
    whole = [k for k, v in by_packet.items() if v == allowed[k[0]]]
    assert len(whole) >= sound["offered_pps"] * 24 / (ROOM - 1)
