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

Users: `mix_minus` (`conference/mixer.py`) and the mesh table's
`mesh_gcm_rtp_*` (`mesh/table.py`).  `_select` hands every provider the
SAME arguments, so a provider may not donate a buffer, and a signature
first seen in a tick is timed in that tick: a caller whose shapes vary
from tick to tick does not belong here.  The single-chip table's GCM
forms left for those two reasons and are picked by a rule of the shape
(`transform/srtp/context.py:_gcm_form_grid`).
"""

from __future__ import annotations

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
