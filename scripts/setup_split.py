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
admission ticks themselves and the joins' queueing).  Beside `ladder_s`
the ladder's split a program, `ladder_programs`: one
`[name, rung, rows, width, trace_lower_s, load_or_compile_s]` a program
the ladder compiled or loaded, in order of finishing (`rung` the row
class `_warm_class` was warming; `rows` x `width` the program's packet
plane, tail and all; JAX's own `jaxpr_trace` + `jaxpr_to_mlir_module`
durations, and its `backend_compile` duration, which is the persistent
cache's read and deserialisation on a hit), `ladder_big` how many of
them took a second or more in all, and their two sums: the programs of
a rung run in one pool, so the sums may exceed `ladder_s`.  Run it
twice in one call for a cold and a warm split: the second finds the
first's compile cache (`benchmarks/.cache`, as `run.py` keeps it).
"""

import json
import logging
import os
import sys
import threading
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


class ProgramSplit(logging.Handler):
    """What each program of the ladder cost, from JAX itself: its
    `Compiling <name> with global shapes ...` record (the shapes) and
    the `/jax/core/compile/*_duration` events, which arrive on the
    thread that compiles, so the pool's programs do not mix."""

    def __init__(self) -> None:
        super().__init__(logging.DEBUG)
        self.rung = None                  # set round `_warm_class`
        self.programs: list = []
        self._open: dict = {}             # thread -> the program in hand
        self._traced: dict = {}           # thread -> (fun_name, seconds)
        from jax import monitoring

        log = logging.getLogger("jax._src.interpreters.pxla")
        log.setLevel(logging.DEBUG)
        log.propagate = False
        log.addHandler(self)
        monitoring.register_event_duration_secs_listener(self.on_duration)

    def emit(self, record: logging.LogRecord) -> None:
        if self.rung is None or not str(record.msg).startswith(
                "Compiling %s with global shapes"):
            return
        name, avals = record.args[0], record.args[1]
        # the packet plane: the uint8 argument wider than a key or an IV
        plane = next((a.shape for a in avals if a.dtype == "uint8"
                      and len(a.shape) >= 2 and a.shape[-1] > 16), None)
        tid = threading.get_ident()
        fun, traced = self._traced.pop(tid, ("", 0.0))
        self._open[tid] = [
            name[4:-1] if name.startswith("jit(") else name, self.rung,
            plane[-2] if plane else None, plane[-1] if plane else None,
            traced if name == f"jit({fun})" else 0.0, 0.0]

    def on_duration(self, event: str, duration_secs: float,
                    fun_name: str = "", **_kw) -> None:
        if self.rung is None:
            return
        tid = threading.get_ident()
        if event.endswith("jaxpr_trace_duration"):
            # nested jits finish first: the last one is the outermost
            self._traced[tid] = (fun_name, duration_secs)
        elif tid in self._open:
            if event.endswith("jaxpr_to_mlir_module_duration"):
                self._open[tid][4] += duration_secs
            elif event.endswith("backend_compile_duration"):
                rec = self._open.pop(tid)
                rec[5] = duration_secs
                self.programs.append(
                    rec[:4] + [round(rec[4], 3), round(rec[5], 3)])


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
    split = ProgramSplit()
    warm = system.lc._warm_class

    def warm_class(rc, rtp):
        split.rung = rc
        try:
            return warm(rc, rtp)
        finally:
            split.rung = None

    system.lc._warm_class = warm_class
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
        "ladder_s": ladder,
        "ladder_big": sum(p[4] + p[5] >= 1.0 for p in split.programs),
        "ladder_trace_lower_s": round(sum(p[4] for p in split.programs), 1),
        "ladder_load_or_compile_s": round(
            sum(p[5] for p in split.programs), 1),
        "stage_s": stage, "commit_s": commit,
        "rest_s": adm["admit_s"] - ladder - stage - commit,
        "cache_hits": adm["cache_hits"],
        "cache_misses": adm["cache_misses"],
        "ladder_programs": split.programs}), flush=True)
    system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
