"""Chained, fetch-verified AES-core re-measurement.

Why this exists: bench.py's `_time_fn` times ONE launch per sample and
subtracts the scalar-fetch floor, so any core whose net device time is
smaller than the floor's own jitter emits junk — a core once read 20x
faster than its peers that way.

The fix: run the core k times inside ONE jitted program with a data
dependence (each iteration's ciphertext becomes the next iteration's
plaintext), so XLA cannot elide any round and the measured span grows
with k.  k is doubled until the net span is >= FLOOR_MULT x the
measured fetch-floor jitter; per-block time is then
(elapsed - floor) / (k * batch).  A core that cannot reach the jitter
bar inside the budget reports "below_floor", never a number.

The measurement library itself lives in `kernels/registry.py`
(aes_floor_stats / aes_chained / measure_aes_core[s]) so
`aes.py:get_core()` can consume a cached record instead of a hardcoded
default; this script is the CLI wrapper.

Usage:  python scripts/bench_aes_cores.py [--batch 4096] [--budget 60]
                                          [--write-record]
Prints one JSON object; `--write-record` additionally merges the
result into the `_meta`-stamped AES_CORES.json at the repo root (the
record `kernels/aes.py:get_core()` picks the core from).  Exit 0 on
success, 2 on harness error.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--budget", type=float, default=60.0,
                    help="seconds per core")
    ap.add_argument("--write-record", action="store_true",
                    help="merge the result into AES_CORES.json (the "
                         "measured-pick record get_core() reads)")
    args = ap.parse_args()

    import jax

    from libjitsi_tpu.kernels import registry

    if args.write_record:
        rec = registry.write_aes_record(batch=args.batch,
                                        budget=args.budget)
        picked = registry.measured_aes_core()
    else:
        rec = registry.measure_aes_cores(batch=args.batch,
                                         budget=args.budget)
        picked = None
    out = dict(rec)
    out["backend"] = jax.default_backend()
    if args.write_record:
        out["record"] = registry.aes_record_path()
        out["picked_core"] = picked
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    try:
        main()
    except Exception as e:
        print(json.dumps({"error": f"{type(e).__name__}: {e}"}))
        sys.exit(2)
