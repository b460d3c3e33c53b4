#!/usr/bin/env python3
"""Where a cell's admission goes: the warm ladder, the waves, the rest.

    chiprun -- python3 scripts/setup_split.py <configuration> [rows]

Builds the configuration's bridge as `benchmarks/sut.py` does, admits
every endpoint through `request_join` (`sut.admit_all`) and prints one
JSON line: `admit_s` and, inside it, `ladder_s`
(`StreamLifecycleManager._warm_class`: tracing, lowering, compiling or
loading every program of every rung; it does not grow with the table),
`stage_s` / `commit_s` (`SfuBridge.stage_endpoints` /
`commit_endpoints`: the waves' host work, which does) and `rest_s` (the
admission ticks themselves and the joins' queueing).  Run it twice in
one call for a cold and a warm split: the second finds the first's
compile cache (`benchmarks/.cache`, as `run.py` keeps it).
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
sys.path[:0] = [BENCH, ROOT]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(BENCH, ".cache")
os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def timed(obj, name: str, into: dict) -> None:
    inner = getattr(obj, name)

    def call(*a, **k):
        t0 = time.perf_counter()
        try:
            return inner(*a, **k)
        finally:
            into[name] = into.get(name, 0.0) + time.perf_counter() - t0

    setattr(obj, name, call)


def main() -> int:
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    conf = next(c for c in bench["configs"] if c["name"] == sys.argv[1])
    config = json.load(open(os.path.join(ROOT, conf["file"])))
    if len(sys.argv) > 2:
        config["capacity"] = int(sys.argv[2])
    import jax

    from libjitsi_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import sut

    system = sut.System(config, print)
    spent: dict = {}
    timed(system.bridge, "stage_endpoints", spent)
    timed(system.bridge, "commit_endpoints", spent)
    timed(system.lc, "_warm_class", spent)
    adm = system.admit_all(1)
    ladder = spent["_warm_class"]
    stage = spent["stage_endpoints"]
    commit = spent["commit_endpoints"]
    dev = jax.devices()[0]
    print(json.dumps({
        "config": sys.argv[1], "rows": int(config["capacity"]),
        "device": f"{dev.platform} {dev.device_kind} x{len(jax.devices())}",
        "admit_s": adm["admit_s"], "admit_ticks": adm["admit_ticks"],
        "ladder_s": ladder, "stage_s": stage, "commit_s": commit,
        "rest_s": adm["admit_s"] - ladder - stage - commit,
        "cache_hits": adm["cache_hits"],
        "cache_misses": adm["cache_misses"]}), flush=True)
    system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
