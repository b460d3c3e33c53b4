"""Kernel provider registry: benchmark the candidates, keep the fastest.

Rebuilds the reference's provider-selection pattern
(`org.jitsi.impl.neomedia.transform.srtp.crypto.Aes` micro-benchmarks the
SunJCE / BouncyCastle / OpenSSL-JNI AES providers at startup and installs
the winner) for TPU kernel backends: each op registers one or more
providers ("xla" fused jnp, "pallas" VMEM kernel, ...), and the first hot
call times each on the real shapes and pins the winner for that shape
signature.

The choice is per (op, shape-signature) because the winner genuinely
flips with shape (XLA's fusion wins small fused elementwise programs;
Pallas wins when staying resident in VMEM avoids HBM round trips).
`force(op, provider)` — or the config key `kernels.provider.<op>` once
`libjitsi_tpu.init()` has run — overrides the measurement for tests and
deployments that want determinism.

Benchmarking compiles and times every provider, so it must stay off the
media path: latency-sensitive callers (the mixer tick) call `warmup()`
with their real shapes at setup time, exactly when the reference runs
its startup crypto benchmark.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax


class _Op:
    def __init__(self, name: str):
        self.name = name
        self.providers: Dict[str, Callable] = {}
        self.forced: Optional[str] = None
        self.choice: Dict[Tuple, str] = {}      # shape signature -> provider
        self.timings: Dict[Tuple, Dict[str, float]] = {}
        self.errors: Dict[Tuple, Dict[str, str]] = {}


_OPS: Dict[str, _Op] = {}
_BENCH_ITERS = 5


def register(op: str, provider: str, fn: Callable) -> None:
    _OPS.setdefault(op, _Op(op)).providers[provider] = fn


def force(op: str, provider: Optional[str]) -> None:
    """Pin a provider (None returns to measured selection)."""
    o = _OPS[op]
    if provider is not None and provider not in o.providers:
        raise KeyError(f"{op}: unknown provider {provider!r} "
                       f"(have {sorted(o.providers)})")
    o.forced = provider
    o.choice.clear()


def providers(op: str) -> List[str]:
    return sorted(_OPS[op].providers)


def report() -> Dict[str, Dict[str, Any]]:
    """Selection state for observability/debugging."""
    return {
        name: {
            "providers": sorted(o.providers),
            "forced": o.forced,
            "choices": {str(k): v for k, v in o.choice.items()},
            "timings_ms": {
                str(k): {p: round(t * 1e3, 4) for p, t in d.items()}
                for k, d in o.timings.items()},
            "errors": {str(k): dict(d) for k, d in o.errors.items()},
        }
        for name, o in _OPS.items()
    }


def _signature(args) -> Tuple:
    sig = []
    for a in args:
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        sig.append((tuple(shape), str(dtype)) if shape is not None else a)
    return tuple(sig)


def _force(out) -> None:
    """Completion by fetching bytes: provider timing ends on one leaf's
    host copy rather than `block_until_ready`, so mesh `_LazyArray`
    leaves (which defer their wire-order scatter) materialize through
    the same call.  One leaf suffices — competing providers return
    identical shapes, so the (equal) transfer cost cancels in the
    comparison."""
    import numpy as _np

    for leaf in jax.tree_util.tree_leaves(out):
        if hasattr(leaf, "__array__"):
            _np.asarray(leaf)
            return
    jax.block_until_ready(out)


def _time_once(fn: Callable, args) -> Tuple[float, Any]:
    out = fn(*args)
    _force(out)                         # compile + warm
    t0 = time.perf_counter()
    for _ in range(_BENCH_ITERS):
        out = fn(*args)
    # one fetch at the end: the device queue executes in order, so the
    # last result's bytes prove all iterations completed — 5 executions
    # amortize the single forced transfer
    _force(out)
    return (time.perf_counter() - t0) / _BENCH_ITERS, out


def _forced_provider(o: _Op) -> Optional[str]:
    if o.forced is not None:
        return o.forced
    # config override (reference: named tunables via ConfigurationService)
    try:
        import libjitsi_tpu
        if libjitsi_tpu._started:
            prov = libjitsi_tpu.configuration_service().get_string(
                f"kernels.provider.{o.name}")
            if prov in o.providers:
                return prov
    except Exception:
        pass
    return None


def _select(o: _Op, sig: Tuple, args) -> Tuple[str, Any]:
    """Benchmark every provider on these args; pin and return the winner
    (and its result).  A provider's failure is recorded in `errors`
    (report() exposes it) and, off the CPU, re-raised: on the chip a
    kernel the compiler refuses must stop the program, not quietly hand
    the op to the survivor.  The CPU tier keeps record-and-continue —
    there a provider can fail for lack of the hardware it targets."""
    timings: Dict[str, float] = {}
    results: Dict[str, Any] = {}
    for name, fn in o.providers.items():
        try:
            timings[name], results[name] = _time_once(fn, args)
        except Exception as e:
            o.errors.setdefault(sig, {})[name] = repr(e)
            if jax.default_backend() != "cpu":
                raise
    if not timings:
        raise RuntimeError(
            f"{o.name}: no provider succeeded for {sig}: "
            f"{o.errors.get(sig)}")
    chosen = min(timings, key=timings.get)
    o.choice[sig] = chosen
    o.timings[sig] = timings
    return chosen, results[chosen]


def warmup(op: str, *args) -> str:
    """Compile + benchmark all providers for these argument shapes, off
    the hot path (the reference benches its crypto providers at startup;
    latency-sensitive callers do this at setup time).  Returns the
    pinned provider name."""
    o = _OPS[op]
    forced = _forced_provider(o)
    if forced is not None:
        _force(o.providers[forced](*args))
        return forced
    sig = _signature(args)
    chosen = o.choice.get(sig)
    if chosen is None:
        chosen, _ = _select(o, sig, args)
    return chosen


def call(op: str, *args):
    """Dispatch to the selected provider, measuring on first sight of a
    shape signature (use `warmup()` beforehand to keep the measurement
    off latency-sensitive paths)."""
    o = _OPS[op]
    forced = _forced_provider(o)
    if forced is not None:
        return o.providers[forced](*args)
    if len(o.providers) == 1:
        return next(iter(o.providers.values()))(*args)
    sig = _signature(args)
    chosen = o.choice.get(sig)
    if chosen is None:
        _, result = _select(o, sig, args)
        return result
    return o.providers[chosen](*args)


# ------------------------------------------------- measured AES core
#
# The per-shape provider race above picks between whole-kernel
# implementations; the AES *core* (table / bitsliced variants inside
# kernels/aes.py) is chosen once per backend instead, because the core
# is read at trace time and switching it invalidates every compiled
# crypto kernel.  The measurement is the chained above-floor protocol
# from scripts/bench_aes_cores.py (k data-dependent encrypts inside one
# jitted program, k doubled until the net span clears the scalar-fetch
# floor's jitter — a single launch can sit inside that jitter and read
# as noise).  Results are cached to a `_meta`-stamped
# AES_CORES.json at the repo root so startup reads a record instead of
# re-paying the ~minutes-long sweep; set LIBJITSI_TPU_AES_MEASURE to a
# per-core second budget to (re)measure the current backend and update
# the record.

AES_FLOOR_MULT = 10.0       # net span must exceed this x floor jitter
AES_SAMPLES = 5

_AES_CORE_CACHE: Dict[str, Optional[str]] = {}


def aes_record_path() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "AES_CORES.json")


def aes_floor_stats() -> Tuple[float, float]:
    """Median + spread (max-min) of the 4-byte verification fetch on a
    trivial program — the spread is the jitter bar every measurement
    must clear."""
    import jax.numpy as jnp
    import numpy as np

    g = jax.jit(lambda x: jnp.sum(x))
    x = jnp.arange(8, dtype=jnp.uint32)
    np.asarray(g(x))                        # compile + prime
    samples = []
    for _ in range(9):
        t0 = time.perf_counter()
        np.asarray(g(x))
        samples.append(time.perf_counter() - t0)
    arr = np.asarray(samples)
    return float(np.median(arr)), float(arr.max() - arr.min())


def aes_chained(fn: Callable, rks, k: int) -> Callable:
    """jit( blocks -> checksum(fn applied k times, chained) ).

    The loop-carried value is the block batch itself: round i's output
    is round i+1's input, so dead-code elimination cannot drop work and
    the program's span scales with k."""
    import jax.numpy as jnp
    from jax import lax

    def body(_i, blk):
        return fn(rks, blk)

    def prog(blk):
        out = lax.fori_loop(0, k, body, blk)
        return jnp.sum(out.astype(jnp.uint32))

    return jax.jit(prog)


def measure_aes_core(fn: Callable, rks, blocks, floor: float,
                     jitter: float, deadline: float) -> Dict[str, Any]:
    """Blocks/s for one core, or a refusal record.  Doubles the chain
    length until the net span clears the jitter bar; a core that cannot
    reach the bar inside the budget reports "below_floor"/"skipped:
    budget", never a number."""
    import numpy as np

    b = blocks.shape[0]
    k = 4
    while True:
        if time.monotonic() > deadline:
            return {"status": "skipped: budget", "chain_k": k}
        try:
            g = aes_chained(fn, rks, k)
            np.asarray(g(blocks))           # compile + prime
            spans = []
            for _ in range(AES_SAMPLES):
                t0 = time.perf_counter()
                np.asarray(g(blocks))
                spans.append(time.perf_counter() - t0)
                if time.monotonic() > deadline:
                    break
        except Exception as e:              # lowering refusal, recorded
            return {"status": f"error: {type(e).__name__}"}
        net = float(np.median(spans)) - floor
        if net >= AES_FLOOR_MULT * jitter:
            return {
                "status": "ok",
                "blocks_per_sec": round(b * k / net, 1),
                "chain_k": k,
                "net_span_ms": round(net * 1e3, 3),
                "floor_jitter_ms": round(jitter * 1e3, 3),
            }
        if k >= 1 << 16:
            # even 65k chained rounds sit inside the floor jitter:
            # the honest answer is a bound, not a rate
            return {"status": "below_floor", "chain_k": k,
                    "net_span_ms": round(net * 1e3, 3)}
        k *= 2


def measure_aes_cores(batch: int = 4096,
                      budget: float = 60.0) -> Dict[str, Any]:
    """Run the chained sweep over every AES core on the current backend
    and return one backend record (the value stored under
    `backends.<name>` in AES_CORES.json)."""
    import jax.numpy as jnp
    import numpy as np

    from libjitsi_tpu.kernels.aes import (aes_encrypt_table,
                                          expand_keys_batch)
    from libjitsi_tpu.kernels.aes_bitsliced import (
        aes_encrypt_bitsliced, aes_encrypt_bitsliced32,
        aes_encrypt_bitsliced_tower, aes_encrypt_pallas_bitsliced)

    rng = np.random.default_rng(21)
    rks = jnp.asarray(expand_keys_batch(
        rng.integers(0, 256, (batch, 16), dtype=np.uint8)))
    blocks = jnp.asarray(
        rng.integers(0, 256, (batch, 16), dtype=np.uint8))

    floor, jitter = aes_floor_stats()
    rec = {
        "batch": batch,
        "fetch_floor_ms": round(floor * 1e3, 3),
        "floor_jitter_ms": round(jitter * 1e3, 3),
        "method": ("k chained (data-dependent) encrypts per program; "
                   f"k doubled until net span >= {AES_FLOOR_MULT}x "
                   "floor jitter"),
        "cores": {},
    }
    for name, fn in (("xla_table", aes_encrypt_table),
                     ("xla_bitsliced", aes_encrypt_bitsliced),
                     ("xla_bitsliced_tower", aes_encrypt_bitsliced_tower),
                     ("xla_bitsliced32", aes_encrypt_bitsliced32),
                     ("pallas_bitsliced", aes_encrypt_pallas_bitsliced)):
        deadline = time.monotonic() + budget
        rec["cores"][name] = measure_aes_core(
            fn, rks, blocks, floor, jitter, deadline)
    return rec


def write_aes_record(batch: int = 4096, budget: float = 60.0,
                     path: Optional[str] = None) -> Dict[str, Any]:
    """Measure the current backend and merge it into AES_CORES.json
    (other backends' entries are preserved; `_meta` is re-stamped)."""
    import datetime
    import subprocess

    path = path or aes_record_path()
    doc: Dict[str, Any] = {}
    if os.path.exists(path):
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except Exception:
            doc = {}
    backend = jax.default_backend()
    doc.setdefault("backends", {})[backend] = measure_aes_cores(
        batch=batch, budget=budget)
    try:
        git = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(path)).stdout.strip() or "unknown"
    except Exception:
        git = "unknown"
    doc["_meta"] = {
        "written": datetime.datetime.now().isoformat(timespec="seconds"),
        "git": git,
        "note": ("measured AES-core record consumed by "
                 "kernels/aes.py:get_core(); regenerate with "
                 "scripts/bench_aes_cores.py --write-record or "
                 "LIBJITSI_TPU_AES_MEASURE=<budget-seconds>"),
    }
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True)
        fh.write("\n")
    _AES_CORE_CACHE.pop(backend, None)
    return doc["backends"][backend]


def measured_aes_core(backend: Optional[str] = None) -> Optional[str]:
    """The fastest *measured* AES core for `backend` (default: the
    current one), or None when no credible number exists — the caller
    (kernels/aes.py:get_core) falls back to its heuristic default then.

    Only `status == "ok"` entries count (below_floor / budget-skipped /
    errored cores are refusals, not slow results), and only the xla_*
    core names map onto aes.py's `_CORES` (the pallas entry is a
    whole-kernel provider raced by the registry above, not a core
    get_core can select)."""
    backend = backend or jax.default_backend()
    if backend in _AES_CORE_CACHE:
        return _AES_CORE_CACHE[backend]

    path = aes_record_path()
    budget = os.environ.get("LIBJITSI_TPU_AES_MEASURE")
    have = False
    if os.path.exists(path):
        try:
            with open(path) as fh:
                have = backend in json.load(fh).get("backends", {})
        except Exception:
            have = False
    if budget and not have and backend == jax.default_backend():
        try:
            write_aes_record(budget=max(float(budget), 1.0))
        except Exception:
            pass

    choice: Optional[str] = None
    try:
        with open(path) as fh:
            cores = (json.load(fh).get("backends", {})
                     .get(backend, {}).get("cores", {}))
        from libjitsi_tpu.kernels.aes import _CORES
        best = -1.0
        for name, rec in cores.items():
            if not name.startswith("xla_") or rec.get("status") != "ok":
                continue
            core = name[len("xla_"):]
            if core in _CORES and rec["blocks_per_sec"] > best:
                best, choice = rec["blocks_per_sec"], core
    except Exception:
        choice = None
    _AES_CORE_CACHE[backend] = choice
    return choice
