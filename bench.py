"""Headline benchmark: SRTP protect throughput at 10k streams on one chip.

Mirrors BASELINE.json's metric ("SRTP packets/sec/chip @ 10k streams") and
config #1's CPU reference: the vs_baseline denominator is a single-thread
OpenSSL SRTP protect (AES-128-CTR + HMAC-SHA1-80 via the `cryptography`
package — the same libcrypto the reference's fastest JNI provider binds).

Survivability contract (round-3 postmortem: the driver's timeout killed the
whole run and recorded nothing):

- a WALL-CLOCK BUDGET (``LIBJITSI_TPU_BENCH_BUDGET_S``, default 440 s) is
  enforced by per-section time boxes; sections that would not fit are
  skipped and *recorded* as skipped;
- the result dict is built incrementally — the headline section runs
  first, every completed section lands in the dict immediately;
- the one JSON line is emitted from a ``finally`` block, from the SIGTERM
  handler (the driver's ``timeout`` sends TERM first) and from a daemon
  watchdog thread that fires even if the main thread is stuck in a native
  call — whichever comes first, exactly once;
- there are NO fatal asserts: integrity failures (auth miss, lost echo
  packets) are recorded as degradation fields, not raised.

Section order is headline-first: device microbenches, then crypto
sweeps, then the production/loop paths.  Every section that uses the
accelerator runs in THIS process — a chip belongs to one process at a
time, so a child that wanted it would fail or hang; the only child is
the one pinned to the CPU (`mesh_cpu8`).

Output protocol:
- FULL results: BENCH_DETAIL.json on disk (git-ignored; written by the
  run) + one big stdout line;
- FINAL stdout line: a COMPACT headline (value, vs_baseline, p99,
  roofline verdict, section tally) sized for the driver's tail window;
- every throughput figure carries an HBM-roofline annotation and is
  CAPPED at the physically possible rate (median-of-passes banking)
  — see _roofline.
"""

from __future__ import annotations

import json
import os
import signal
import threading
import time

import numpy as np

from libjitsi_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()

N_STREAMS = 10_240
# Launch size: 65536 amortizes per-launch dispatch overhead.  Timings
# below are fetch-verified (see _checksum); no rate from the v5e is on
# record for this code — PERF.md says "not measured" until a run is.
BATCH = 65536
WIDTH = 192          # capacity; 20 ms Opus packet ≈ 12B header + 160B payload
PKT_LEN = 172
TAG_LEN = 10

BUDGET_S = float(os.environ.get("LIBJITSI_TPU_BENCH_BUDGET_S", "440"))
_T0 = time.monotonic()

# Physics self-check: every pps figure is recorded next to the implied
# HBM traffic, and any estimator above the roofline is CAPPED to it and
# flagged — a number the bench itself marks impossible must not become
# the headline.  Published peaks, keyed by `device_kind` as JAX reports
# it (Google Cloud documentation, "TPU v5e": 16 GB of HBM at 819 GB/s).
# A device that is not in the table is an error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_gbps": 819.0},
}


def _device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def _hbm_gbps() -> float:
    kind = _device_kind()
    if kind not in DEVICE_PEAKS:
        raise RuntimeError(
            f"no published peaks for device kind {kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}): bench.py rates are "
            "judged against the chip's roofline, and there is none "
            "for this device")
    EXTRA["device_kind"] = kind
    EXTRA["hbm_gbps"] = DEVICE_PEAKS[kind]["hbm_gbps"]
    return EXTRA["hbm_gbps"]


_FLOOR = [None, None]           # [median, jitter (max - min)]


def _checksum(fn):
    """Wrap `fn` into a jitted twin returning ONE uint32 checksum scalar.

    Fetching bytes is the one completion signal that cannot return
    early; reducing the outputs to a scalar keeps that forced transfer
    at 4 bytes, so timing `np.asarray(g(*args))` measures dispatch +
    real execution + one scalar round trip (subtract `_fetch_floor()`).
    """
    import jax
    import jax.numpy as jnp

    def _sum_tree(out):
        tot = jnp.uint32(0)
        for leaf in jax.tree_util.tree_leaves(out):
            if hasattr(leaf, "dtype"):
                tot = tot + jnp.sum(leaf.astype(jnp.uint32))
        return tot

    return jax.jit(lambda *a: _sum_tree(fn(*a)))


def _fetch_floor() -> float:
    """Per-iteration cost of the 4-byte verification fetch itself
    (dispatch RTT + scalar transfer), measured once on a trivial
    program and subtracted from every fetch-verified timing."""
    if _FLOOR[0] is None:
        import jax
        import jax.numpy as jnp

        g = jax.jit(lambda x: jnp.sum(x))
        x = jnp.arange(8, dtype=jnp.uint32)
        _ = np.asarray(g(x))
        samples = []
        for _i in range(7):
            t0 = time.perf_counter()
            _ = np.asarray(g(x))
            samples.append(time.perf_counter() - t0)
        arr = np.asarray(samples)
        _FLOOR[0] = float(np.median(arr))
        _FLOOR[1] = float(arr.max() - arr.min())
        EXTRA["scalar_fetch_floor_ms"] = round(_FLOOR[0] * 1e3, 2)
        EXTRA["scalar_fetch_floor_jitter_ms"] = round(_FLOOR[1] * 1e3, 3)
    return _FLOOR[0]


def _roofline(key: str, pps: float, bytes_per_item: float,
              traffic: str) -> float:
    """Record `pps` under EXTRA[key] with its implied GB/s and the HBM
    ceiling for this traffic model; return the roofline-capped value.
    `traffic` documents the per-item byte model (auditable in the
    detail record)."""
    ceiling = _hbm_gbps() * 1e9 / bytes_per_item
    implied = pps * bytes_per_item / 1e9
    rec = {"pps": round(pps, 1), "implied_gbps": round(implied, 1),
           "bytes_per_item": round(bytes_per_item, 1),
           "ceiling_pps": round(ceiling, 1), "traffic": traffic}
    if pps > ceiling:
        rec["roofline_capped"] = True
    EXTRA.setdefault("roofline", {})[key] = rec
    return min(pps, ceiling)


def _elapsed() -> float:
    return time.monotonic() - _T0


def _remaining() -> float:
    return BUDGET_S - _elapsed()


# ---------------------------------------------------------------- result --

RESULT: dict = {
    "metric": "srtp_protect_pps_at_10k_streams",
    "value": 0.0,
    "unit": "packets/sec/chip",
    "vs_baseline": 0.0,
    "extra": {"batch": BATCH, "pkt_len": PKT_LEN, "budget_s": BUDGET_S,
              "sections": {}},
}
EXTRA = RESULT["extra"]
SECTIONS = EXTRA["sections"]

_emit_lock = threading.Lock()
_emitted = False


def emit() -> None:
    """Emit results exactly once (thread/signal safe).

    Protocol (a full dict can overflow a driver's tail window mid-line,
    and then nothing machine-parses it):
    - the FULL result dict is written to BENCH_DETAIL.json on disk and
      printed as a non-final stdout line (best effort);
    - the LAST stdout line is a COMPACT headline — value, vs_baseline,
      p99, roofline verdict, section tally, detail pointer — small
      enough that any sane tail window holds it whole.

    The emitted flag latches only after a successful serialization: the
    watchdog thread can race the main thread mutating EXTRA/SECTIONS
    (json.dumps then raises "dictionary changed size"), and a latched
    flag with no output would defeat the whole survivability contract —
    so serialization retries, then degrades to the compact line alone.
    """
    global _emitted
    import copy

    with _emit_lock:
        if _emitted:
            return
        base = EXTRA.get("cpu_openssl_pps")
        if base and RESULT["value"]:
            RESULT["vs_baseline"] = round(RESULT["value"] / base, 3)
        EXTRA["elapsed_s"] = round(_elapsed(), 1)
        full = None
        for _ in range(3):
            try:
                full = json.dumps(copy.deepcopy(RESULT))
                break
            except Exception:
                time.sleep(0.05)
        if full is not None:
            try:
                detail_path = os.environ.get(
                    "LIBJITSI_TPU_BENCH_DETAIL") or os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "BENCH_DETAIL.json")
                with open(detail_path, "w") as f:
                    f.write(full)
            except Exception:
                pass
            print(full, flush=True)     # non-final: tail may clip it
        try:
            # build the compact line from the SERIALIZED snapshot (an
            # immutable copy) — referencing the live EXTRA dicts here
            # would reopen the mutation race the retry loop handles
            ex = json.loads(full)["extra"] if full is not None else {}
            sect = list(ex.get("sections", {}).values())
            ok_n = sum(1 for v in sect if isinstance(v, dict)
                       and v.get("status") == "ok")
            compact = json.dumps({
                "metric": RESULT["metric"], "value": RESULT["value"],
                "unit": RESULT["unit"],
                "vs_baseline": RESULT["vs_baseline"],
                "extra": {
                    "p99_batch_ms": ex.get("p99_batch_ms"),
                    "estimators_pps": ex.get("estimators_pps"),
                    "device_kind": ex.get("device_kind"),
                    "hbm_gbps": ex.get("hbm_gbps"),
                    "headline_roofline": ex.get("roofline", {}).get(
                        "headline", {}),
                    "sections_ok": ok_n, "sections_total": len(sect),
                    "elapsed_s": ex.get("elapsed_s"),
                    "detail": ("BENCH_DETAIL.json + penultimate stdout "
                               "line"),
                }})
        except Exception:   # scalar-only degrade: ONE line out, always
            compact = json.dumps({
                "metric": RESULT["metric"],
                "value": float(RESULT["value"]),
                "unit": RESULT["unit"],
                "vs_baseline": float(RESULT["vs_baseline"]),
                "extra": {"degraded": "emit serialization raced"}})
        print(compact, flush=True)   # the FINAL line
        _emitted = True


def _kill_child() -> None:
    """os._exit only kills THIS process: a live section subprocess
    (the CPU-pinned mesh_cpu8 child) would otherwise linger as an
    orphan."""
    p = _CHILD
    if p is not None:
        try:
            p.kill()
        except Exception:
            pass


def _on_term(signum, frame):
    _kill_child()
    SECTIONS["_terminated"] = f"signal {signum} at {_elapsed():.1f}s"
    # Signal handlers run ON the main thread: if the signal lands while
    # this very thread is inside emit() holding the (non-reentrant)
    # lock, a blocking acquire would self-deadlock and nothing would
    # print.  Try-acquire instead — on failure the interrupted emit()
    # completes its own print when the handler returns.
    if not _emit_lock.acquire(blocking=False):
        return
    _emit_lock.release()
    emit()
    os._exit(0)


def _watchdog():
    _kill_child()
    SECTIONS["_terminated"] = f"watchdog at {_elapsed():.1f}s"
    emit()
    os._exit(0)


class SkipSection(Exception):
    """Raised by a section to record a clean skip (e.g. an optional
    dependency is absent) instead of an error entry — str(exc) is the
    reason recorded as ``status: "skipped: <reason>"``."""


def section(name: str, min_cost_s: float, box_s: float, fn):
    """Run one bench section inside a time box.

    Skips (and records the skip) when the remaining budget cannot cover
    ``min_cost_s``; passes the section a hard deadline of
    ``now + min(box_s, remaining)``; converts exceptions into recorded
    degradation entries instead of killing the run.
    """
    if _remaining() < min_cost_s:
        SECTIONS[name] = {"status": "skipped: budget",
                          "at_s": round(_elapsed(), 1)}
        return None
    t0 = time.monotonic()
    deadline = t0 + min(box_s, _remaining())
    # visible in the terminated record if this section never returns
    SECTIONS[name] = {"status": "running", "at_s": round(_elapsed(), 1)}
    try:
        out = fn(deadline)
        SECTIONS[name] = {"status": "ok",
                          "elapsed_s": round(time.monotonic() - t0, 1)}
        return out
    except SkipSection as e:  # clean refusal, not a degradation
        SECTIONS[name] = {"status": f"skipped: {e}",
                          "elapsed_s": round(time.monotonic() - t0, 1)}
        return None
    except Exception as e:  # recorded, never fatal
        SECTIONS[name] = {
            "status": f"error: {type(e).__name__}: {e}"[:300],
            "elapsed_s": round(time.monotonic() - t0, 1)}
        return None


# -------------------------------------------------------------- sections --

def _aes_core_name() -> str:
    from libjitsi_tpu.kernels.aes import get_core

    return get_core()


def tpu_pps(deadline: float) -> None:
    import jax
    import jax.numpy as jnp

    from libjitsi_tpu.transform.srtp import kernel

    rng = np.random.default_rng(3)
    tab_rk = rng.integers(0, 256, (N_STREAMS, 11, 16), dtype=np.uint8)
    tab_mid = rng.integers(0, 2**32, (N_STREAMS, 2, 5), dtype=np.uint64
                           ).astype(np.uint32)
    stream = rng.integers(0, N_STREAMS, BATCH).astype(np.int32)
    data = rng.integers(0, 256, (BATCH, WIDTH), dtype=np.uint8)
    length = np.full(BATCH, PKT_LEN, dtype=np.int32)
    payload_off = np.full(BATCH, 12, dtype=np.int32)
    iv = rng.integers(0, 256, (BATCH, 16), dtype=np.uint8)
    roc = np.zeros(BATCH, dtype=np.uint32)

    import functools

    @functools.partial(jax.jit, donate_argnums=())
    def step(tab_rk, tab_mid, stream, data, length, payload_off, iv, roc):
        return kernel.srtp_protect(
            data, length, payload_off, tab_rk[stream], iv, tab_mid[stream],
            roc, TAG_LEN, True, payload_off_const=12)

    args = [jnp.asarray(a) for a in
            (tab_rk, tab_mid, stream, data, length, payload_off, iv, roc)]
    # FETCH-VERIFIED timing (see _checksum): every sample below
    # includes a forced 4-byte result fetch; the scalar-fetch floor is
    # measured and subtracted.
    g = _checksum(step)
    _ = np.asarray(g(*args))            # compile + prime
    floor = _fetch_floor()
    lat = []
    for _ in range(6):
        t0 = time.perf_counter()
        _ = np.asarray(g(*args))
        lat.append(time.perf_counter() - t0)
        if time.monotonic() > deadline and len(lat) >= 3:
            break
    per_launch = max(float(np.median(lat)) - floor, 1e-9)
    # sustained: enqueue k launches, fetch only the LAST checksum —
    # the device executes in order, so the final scalar proves all k
    # completed; this is the deployment overlap shape, now honest
    k = 3 if per_launch > 0.3 else 25
    sustained = []
    for _ in range(2):
        t0 = time.perf_counter()
        s = None
        for _i in range(k):
            s = g(*args)
        _ = np.asarray(s)
        sustained.append(k * BATCH / max(
            time.perf_counter() - t0 - floor, 1e-9))
        if time.monotonic() > deadline:
            break
    # Per-packet HBM traffic model for one protect launch: data in+out
    # (2W) + round-key gather (11*16) + midstates (2*5*4) + iv (16) +
    # roc/len/off/stream (4 each).  With honest timing the measured
    # rate sits far BELOW this ceiling; the cap is a sanity backstop.
    bytes_per_pkt = 2 * WIDTH + 11 * 16 + 2 * 5 * 4 + 16 + 4 * 4
    traffic = (f"2*{WIDTH} data + 176 rk + 40 mid + 16 iv + 16 scalars"
               f" per packet")
    med_sustained = float(np.median(sustained)) if sustained else \
        BATCH / per_launch
    RESULT["value"] = round(
        _roofline("headline", med_sustained, bytes_per_pkt, traffic), 1)
    _roofline("sync_per_launch", BATCH / per_launch, bytes_per_pkt,
              traffic)
    EXTRA["p99_batch_ms"] = round(
        (float(np.percentile(np.asarray(lat), 99)) - floor) * 1e3, 3)
    EXTRA["on_device_launch_ms"] = round(per_launch * 1e3, 3)
    EXTRA["estimators_pps"] = {
        "sync_fetch_verified": round(BATCH / per_launch, 1),
        "sustained_median": round(med_sustained, 1),
        "sustained_passes": [round(v, 1) for v in sustained],
        "aes_core_in_use": _aes_core_name()}


def cpu_pps(deadline: float) -> None:
    """Single-thread OpenSSL SRTP protect (keystream XOR + HMAC-SHA1-80)."""
    import hmac as pyhmac
    import hashlib

    # lazy + gated like control/dtls.py's _openssl(): the container may
    # not ship `cryptography`, and an absent optional baseline is a
    # skip, not a degradation record
    try:
        from cryptography.hazmat.primitives.ciphers import (
            Cipher, algorithms, modes)
    except ImportError:
        raise SkipSection("missing-dep")

    rng = np.random.default_rng(4)
    n = 2000
    pkts = [rng.integers(0, 256, PKT_LEN, dtype=np.uint8).tobytes()
            for _ in range(n)]
    keys = [rng.integers(0, 256, 16, dtype=np.uint8).tobytes()
            for _ in range(64)]
    akeys = [rng.integers(0, 256, 20, dtype=np.uint8).tobytes()
             for _ in range(64)]
    iv = rng.integers(0, 256, 16, dtype=np.uint8).tobytes()
    t0 = time.perf_counter()
    done = 0
    for i, p in enumerate(pkts):
        enc = Cipher(algorithms.AES(keys[i % 64]), modes.CTR(iv)).encryptor()
        ct = p[:12] + enc.update(p[12:]) + enc.finalize()
        tag = pyhmac.new(akeys[i % 64], ct + b"\x00\x00\x00\x00",
                         hashlib.sha1).digest()[:TAG_LEN]
        _ = ct + tag
        done += 1
        if done % 500 == 0 and time.monotonic() > deadline:
            break
    EXTRA["cpu_openssl_pps"] = round(done / (time.perf_counter() - t0), 1)


def _time_fn(fn, args, deadline: float, iters: int = 4) -> float:
    """Median FETCH-VERIFIED per-launch time, scalar-fetch floor
    subtracted (see _checksum).  Deadline-aware: stops sampling
    once the box is spent (the first sample already yields a number)."""
    g = _checksum(fn)
    _ = np.asarray(g(*args))            # compile + prime
    floor = _fetch_floor()
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        _ = np.asarray(g(*args))
        samples.append(time.perf_counter() - t0)
        if time.monotonic() > deadline and samples:
            break
    return max(float(np.median(samples)) - floor, 1e-9)


def gcm_sweep(deadline: float) -> None:
    """BASELINE config #2's AEAD_AES_128_GCM leg, both table paths at
    three batch sizes (pin the grouped/per-row crossover from data, not
    a constant).

    `grouped` is the production table path: rows grouped by stream, one
    GHASH matrix read per stream per launch.  `per_row` gathers a 16 KiB
    matrix per row (capped at 32768 rows by HBM).  The crossover batch
    recorded here is what `transform/srtp/context.py` consumes via
    `kernels.registry` measurement at table setup.
    """
    import functools as _ft

    import jax.numpy as jnp

    from libjitsi_tpu.kernels import gcm as G
    from libjitsi_tpu.transform.srtp.context import _gcm_grid

    rng = np.random.default_rng(5)
    grouped: dict = {}
    per_row: dict = {}
    EXTRA["gcm_pps_grouped_by_batch"] = grouped
    EXTRA["gcm_pps_per_row_by_batch"] = per_row

    for b in (16384, 65536):
        if time.monotonic() > deadline:
            grouped[str(b)] = "skipped: budget"
            continue
        n_streams = max(b // 64, 64)
        rks = rng.integers(0, 256, (b, 11, 16), dtype=np.uint8)
        data = rng.integers(0, 256, (b, WIDTH), dtype=np.uint8)
        length = np.full(b, PKT_LEN, np.int32)
        aad = np.full(b, 12, np.int32)
        iv = rng.integers(0, 256, (b, 12), dtype=np.uint8)
        stream = np.repeat(np.arange(n_streams), b // n_streams)
        rng.shuffle(stream)
        grid, _us, inv = _gcm_grid(stream)
        gms_g = rng.integers(0, 2, (grid.shape[0], 128, 128), dtype=np.int8)
        args = [jnp.asarray(x) for x in (data, length, aad, rks, gms_g, iv,
                                         grid, inv)]
        dt = _time_fn(_ft.partial(G.gcm_protect_grouped, aad_const=12),
                      args, deadline, iters=2)
        # per pkt: 2W data + 176 rk + 12 iv + one 16KiB GHASH matrix
        # per GROUP amortized over its rows
        bpp = 2 * WIDTH + 176 + 12 + 16384 * grid.shape[0] / b
        grouped[str(b)] = round(
            _roofline(f"gcm_grouped_{b}", b / dt, bpp,
                      "2W+rk+iv+gmat/group"), 1)

    for b in (16384,):
        # one point: the 16384 rung plus the grouped sweep pins the
        # crossover shape inside the section's budget
        if time.monotonic() > deadline:
            per_row[str(b)] = "skipped: budget"
            continue
        rks = rng.integers(0, 256, (b, 11, 16), dtype=np.uint8)
        gms = rng.integers(0, 2, (b, 128, 128), dtype=np.int8)
        data = rng.integers(0, 256, (b, WIDTH), dtype=np.uint8)
        length = np.full(b, PKT_LEN, np.int32)
        aad = np.full(b, 12, np.int32)
        iv = rng.integers(0, 256, (b, 12), dtype=np.uint8)
        args = [jnp.asarray(x) for x in (data, length, aad, rks, gms, iv)]
        dt = _time_fn(G.gcm_protect, args, deadline, iters=2)
        per_row[str(b)] = round(
            _roofline(f"gcm_per_row_{b}", b / dt,
                      2 * WIDTH + 176 + 12 + 16384,
                      "2W+rk+iv+16KiB gmat/row"), 1)

    # continuity keys
    if isinstance(grouped.get("65536"), (int, float)):
        EXTRA["gcm_pps"] = grouped["65536"]
    if isinstance(per_row.get("32768"), (int, float)):
        EXTRA["gcm_pps_per_row"] = per_row["32768"]
    elif isinstance(per_row.get("16384"), (int, float)):
        EXTRA["gcm_pps_per_row"] = per_row["16384"]


def gcm_fanout(deadline: float, packets: int = 128, receivers: int = 512
               ) -> None:
    """AEAD leg of BASELINE config #5: full-mesh GCM fan-out via the
    grouped kernel (per-LEG GHASH matrices — 16 KiB x receivers, not
    x rows, of key-material traffic)."""
    import jax.numpy as jnp

    from libjitsi_tpu.kernels import gcm as G

    rng = np.random.default_rng(12)
    rks = rng.integers(0, 256, (receivers, 11, 16), dtype=np.uint8)
    gms = rng.integers(0, 2, (receivers, 128, 128), dtype=np.int8)
    data = rng.integers(0, 256, (packets, WIDTH), dtype=np.uint8)
    length = np.full(packets, PKT_LEN, np.int32)
    iv = rng.integers(0, 256, (receivers, packets, 12), dtype=np.uint8)
    args = [jnp.asarray(x) for x in (data, length, rks, gms, iv)]
    dt = _time_fn(G.gcm_protect_fanout, args, deadline, iters=2)
    rows = packets * receivers
    # per out row: W write + W/G read + gmat/packets + rk/packets + iv
    bpp = WIDTH + WIDTH / receivers + (16384 + 176) / packets + 12
    EXTRA["gcm_fanout_rows_per_sec"] = round(
        _roofline("gcm_fanout", rows / dt, bpp,
                  "W out + amortized in/gmat/rk + iv"), 1)


def mixer(deadline: float, n_participants: int = 256) -> None:
    """BASELINE config #3: N-participant 48 kHz mono 20 ms mix-minus."""
    import jax.numpy as jnp

    from libjitsi_tpu.conference.mixer import _mix_jit

    rng = np.random.default_rng(6)
    pcm = jnp.asarray(rng.integers(-8000, 8000, (n_participants, 960))
                      .astype(np.int16))
    active = jnp.ones(n_participants, dtype=bool)
    dt = _time_fn(_mix_jit, (pcm, active), deadline)
    EXTRA["mix_256p_per_sec"] = round(1.0 / dt, 1)


def bridge_mixes(deadline: float, conferences: int = 64,
                 participants: int = 64) -> None:
    """Whole-bridge mixing: C conferences of N participants per launch
    (a single conference launch is dispatch-bound; see MixerBridge)."""
    import jax.numpy as jnp

    from libjitsi_tpu.conference.mixer import _mix_many_jit

    rng = np.random.default_rng(8)
    pcm = jnp.asarray(rng.integers(
        -8000, 8000, (conferences, participants, 960)).astype(np.int16))
    active = jnp.ones((conferences, participants), dtype=bool)
    dt = _time_fn(_mix_many_jit, (pcm, active), deadline)
    EXTRA["bridge_64conf_64p_mixes_per_sec"] = round(conferences / dt, 1)


def fanout(deadline: float, packets: int = 128, receivers: int = 512
           ) -> None:
    """BASELINE config #5 core: per-receiver re-encrypt of a fan-out
    matrix (rows = packets x receivers) in one launch."""
    import jax
    import jax.numpy as jnp

    from libjitsi_tpu.transform.srtp import kernel

    rng = np.random.default_rng(7)
    rows = packets * receivers
    tab_rk = rng.integers(0, 256, (receivers, 11, 16), dtype=np.uint8)
    tab_mid = rng.integers(0, 2**32, (receivers, 2, 5), dtype=np.uint64
                           ).astype(np.uint32)
    recv = np.repeat(np.arange(receivers, dtype=np.int32), packets)
    data = rng.integers(0, 256, (rows, WIDTH), dtype=np.uint8)
    length = np.full(rows, PKT_LEN, np.int32)
    off = np.full(rows, 12, np.int32)
    iv = rng.integers(0, 256, (rows, 16), dtype=np.uint8)
    roc = np.zeros(rows, np.uint32)

    # same math as translator._fanout_protect (the payload offset is
    # an operand), without buffer donation (donation would invalidate
    # the timed args)
    @jax.jit
    def step(tab_rk, tab_mid, recv, data, length, off, iv, roc):
        return kernel.srtp_protect_rows(data, length, off, tab_rk[recv],
                                        iv, tab_mid[recv], roc, TAG_LEN,
                                        True)

    args = [jnp.asarray(x) for x in
            (tab_rk, tab_mid, recv, data, length, off, iv, roc)]
    dt = _time_fn(step, args, deadline, iters=2)
    EXTRA["sfu_fanout_rows_per_sec"] = round(
        _roofline("sfu_fanout", rows / dt,
                  2 * WIDTH + 176 + 40 + 16 + 16,
                  "2W data + rk + mid + iv + scalars"), 1)


_TABLES: dict = {}


def _production_tables(n_streams: int):
    """Build (and cache, keyed by stream count) the tx/rx tables +
    batch maker shared by the probe and bulk production-path
    sections.  The measured bulk-install rate lands in
    _TABLES["install_rate"] for the caller to report."""
    if _TABLES.get("n_streams") == n_streams:
        return _TABLES["tx"], _TABLES["rx"], _TABLES["make_batches"]
    from libjitsi_tpu.rtp import header as rtp_header
    from libjitsi_tpu.transform.srtp import SrtpStreamTable

    rng = np.random.default_rng(9)
    mks = rng.integers(0, 256, (n_streams, 16), dtype=np.uint8)
    mss = rng.integers(0, 256, (n_streams, 14), dtype=np.uint8)
    t0 = time.perf_counter()
    tx = SrtpStreamTable(capacity=n_streams)
    tx.add_streams(np.arange(n_streams), mks, mss)
    _TABLES["install_rate"] = round(
        n_streams / (time.perf_counter() - t0), 1)
    rx = SrtpStreamTable(capacity=n_streams)
    rx.add_streams(np.arange(n_streams), mks, mss)

    # distinct batches (distinct seqs: replay must accept all), mixed
    # sizes hitting all three width classes: 60% small voice, 30% mid
    # video, 10% near-MTU
    sizes = np.array([100, 400, 950])

    def make_batches(count: int, seq_base: int, bsz: int):
        out = []
        for k in range(count):
            streams = rng.permutation(n_streams)[:bsz]
            ln = sizes[rng.choice(3, bsz, p=[0.6, 0.3, 0.1])]
            payloads = [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
                        for n in ln]
            out.append(rtp_header.build(
                payloads, [seq_base + k] * bsz, [k * 960] * bsz,
                (0x10000 + streams).tolist(), [96] * bsz,
                stream=streams.tolist()))
        return out

    _TABLES.update(tx=tx, rx=rx, make_batches=make_batches,
                   n_streams=n_streams)
    return tx, rx, make_batches


def _probe_body(n_streams: int = N_STREAMS) -> None:
    """Body of table_roundtrip_probe: builds its tables via the shared
    helper and reports cumulative partial results."""
    from libjitsi_tpu.rtp import header as rtp_header

    # self-bound: past it, stop measuring and keep what exists
    deadline = time.monotonic() + 70
    tx, rx, _ = _production_tables(n_streams)
    # single packet size on purpose: ONE size class = one compile pair
    rng = np.random.default_rng(77)
    rt = []
    auth_fail = 0
    for k in range(12):
        streams = rng.permutation(n_streams)[:512]
        payloads = [rng.integers(0, 256, 160, dtype=np.uint8).tobytes()
                    for _ in range(512)]
        b = rtp_header.build(
            payloads, [1000 + k] * 512, [k * 960] * 512,
            (0x10000 + streams).tolist(), [96] * 512,
            stream=streams.tolist())
        t1 = time.perf_counter()
        w = tx.protect_rtp(b)
        _, ok = rx.unprotect_rtp(w)
        rt.append(time.perf_counter() - t1)
        auth_fail += int(len(ok) - int(np.sum(ok)))
        if len(rt) in (4, 8, 12):
            # cumulative partial print: the parent parses the LAST
            # line, so even a hard kill mid-stall keeps these samples
            tail = rt[max(len(rt) // 4, 1):] or rt
            out = {"table_roundtrip_512_p99_ms": round(
                       float(np.percentile(tail, 99) * 1e3), 3),
                   "table_roundtrip_512_p50_ms": round(
                       float(np.percentile(tail, 50) * 1e3), 3),
                   "table_roundtrip_samples": len(rt),
                   "install_streams_per_sec": _TABLES["install_rate"]}
            if auth_fail:
                out["table_roundtrip_auth_failures"] = auth_fail
            _report(out)
        if time.monotonic() > deadline and len(rt) >= 4:
            break


_CHILD = None     # live section subprocess; killed by _on_term/_watchdog
_AS_CHILD = False  # set only inside the CPU-pinned mesh_cpu8 child


def _report(out: dict) -> None:
    """A section's cumulative partial results.  In this process they
    merge straight into EXTRA (a section cut at its deadline keeps
    what it measured); the CPU-pinned child prints them as one JSON
    line for `_run_in_cpu_child` to merge."""
    if _AS_CHILD:
        print(json.dumps(out), flush=True)
    else:
        EXTRA.update(out)


def _run_in_cpu_child(fn_name: str, deadline: float, cap_s: float,
                      env: dict) -> None:
    """Run a CPU-PINNED bench section in a subprocess with its own
    timeout and merge its one-line JSON stdout into EXTRA.  `env` must
    hold the child to the CPU: the parent holds the chip, and a child
    that reached for it would fail or hang.

    Salvage rule: whatever valid JSON the child managed to print is
    kept even if it then hung in teardown or died non-zero.
    """
    global _CHILD
    import subprocess
    import sys

    if env.get("JAX_PLATFORMS") != "cpu":
        raise ValueError("a bench child must be pinned to the CPU")
    budget = max(min(deadline - time.monotonic(), cap_s), 30)
    child_env = dict(os.environ)
    child_env.update(env)
    p = subprocess.Popen(
        [sys.executable, "-c",
         f"import bench; bench._AS_CHILD = True; bench.{fn_name}()"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=os.path.dirname(os.path.abspath(__file__)), env=child_env)
    _CHILD = p
    timed_out = False
    try:
        out, err = p.communicate(timeout=budget)
    except subprocess.TimeoutExpired:
        timed_out = True
        p.kill()
        out, err = p.communicate()
    finally:
        _CHILD = None
    lines = [l for l in (out or "").splitlines() if l.strip()]
    payload = None
    # newest parseable line wins: a timeout-kill can clip the child's
    # FINAL print mid-line, and discarding the earlier complete partial
    # would lose already-measured numbers
    for line in reversed(lines):
        try:
            cand = json.loads(line)
        except ValueError:
            continue
        if isinstance(cand, dict):   # stray scalar/list prints are not
            payload = cand           # results; keep scanning upward
            break
    if payload is not None:
        EXTRA.update(payload)
        if timed_out or p.returncode != 0:
            SECTIONS[f"_{fn_name}_note"] = (
                f"results salvaged (timed_out={timed_out}, "
                f"rc={p.returncode})")
        return
    raise RuntimeError(
        f"{fn_name} child {'timed out' if timed_out else ''} "
        f"rc={p.returncode}: {(err or '')[-200:]}")


def table_roundtrip_probe(deadline: float) -> None:
    """The ASSEMBLED production path's latency on the device —
    `SrtpStreamTable.protect_rtp` → `unprotect_rtp` round trip p99 at
    batch 512 over 10k installed streams, full host control plane per
    call."""
    _probe_body()


def table_path(deadline: float) -> None:
    """PRODUCTION-path SRTP: `SrtpStreamTable.protect_rtp/unprotect_rtp`
    with the full host control plane — header parse, chain-index /
    index-estimation, replay window update, size-class bucketing — at
    10k installed streams and mixed packet sizes (the kernel-only bench
    above deliberately excludes all of that).  Its three size-class
    compile pairs are the bench's heaviest fresh compiles.  The
    host-plane ceiling and an H2D probe are reported alongside to keep
    the decomposition visible.
    """
    _table_body()


def _table_body(n_streams: int = N_STREAMS, batch: int = 4096,
                 n_batches: int = 6) -> None:
    """Body of table_path.  Self-bounded with early breaks, so a slow
    section still reports everything measured so far."""
    from libjitsi_tpu.core.packet import bucket_by_size
    from libjitsi_tpu.core.rtp_math import chain_packet_indices
    from libjitsi_tpu.rtp import header as rtp_header

    deadline = time.monotonic() + 55
    out: dict = {}
    tx, rx, make_batches = _production_tables(n_streams)
    batches = make_batches(n_batches, 2000, batch)

    warm = n_batches // 3                     # first passes pay compiles
    lat_p, lat_u = [], []
    protected = []
    t_all = 0.0
    for k, b in enumerate(batches):
        t1 = time.perf_counter()
        w = tx.protect_rtp(b)
        dt = time.perf_counter() - t1
        protected.append(w)
        if k >= warm:
            lat_p.append(dt)
            t_all += dt
        if time.monotonic() > deadline and lat_p:
            break
    out["table_protect_pps"] = round(batch * len(lat_p) / t_all, 1)
    out["table_protect_p99_batch_ms"] = round(
        float(np.percentile(lat_p, 99) * 1e3), 3)
    _report(out)   # cumulative partial (see probe)
    t_all = 0.0
    auth_fail = 0
    for k, b in enumerate(protected):
        t1 = time.perf_counter()
        _, ok = rx.unprotect_rtp(b)
        dt = time.perf_counter() - t1
        auth_fail += int(len(ok) - int(np.sum(ok)))
        if k >= warm:
            lat_u.append(dt)
            t_all += dt
        if time.monotonic() > deadline and lat_u:
            break
    if lat_u:
        out["table_unprotect_pps"] = round(
            batch * len(lat_u) / t_all, 1)
        out["table_unprotect_p99_batch_ms"] = round(
            float(np.percentile(lat_u, 99) * 1e3), 3)
    if auth_fail:        # degradation field, not a fatal assert
        out["table_auth_failures"] = auth_fail
    _report(out)   # cumulative partial

    # double-buffered production path: protect_rtp_async keeps DEPTH
    # batches in flight (host state commits at dispatch; bytes
    # materialize later), overlapping H2D/compute/D2H across batches —
    # the naive path above drains every batch before the next dispatch
    if time.monotonic() < deadline:
        depth = 3
        more = make_batches(n_batches, 3000, batch)
        t1 = time.perf_counter()
        inflight = []
        for b in more:
            inflight.append(tx.protect_rtp_async(b))
            if len(inflight) >= depth:
                inflight.pop(0).result()
        for p in inflight:
            p.result()
        out["table_protect_pps_pipelined"] = round(
            batch * n_batches / (time.perf_counter() - t1), 1)

    # host control plane alone (parse, chain index, IV build, bucketing,
    # replay max update) — the part this bench adds over the kernel bench
    b = batches[-1]
    t1 = time.perf_counter()
    reps = 10
    for _ in range(reps):
        hdr = rtp_header.parse(b)
        stream = np.asarray(b.stream, dtype=np.int64)
        idx = chain_packet_indices(stream, hdr.seq, tx.tx_ext)
        _ = bucket_by_size(b)
        _ = tx._cm_iv(tx._salt_rtp[stream], hdr.ssrc, idx)
        np.maximum.at(tx.tx_ext, stream, idx)
    out["table_host_plane_pps"] = round(
        batch * reps / (time.perf_counter() - t1), 1)

    # H2D probe: one synchronous transfer of the batch-sized buffer
    import jax
    import jax.numpy as jnp
    probe = np.zeros_like(batches[0].data)
    d = jnp.asarray(probe)
    jax.block_until_ready(d)
    t1 = time.perf_counter()
    for _ in range(3):
        d = jnp.asarray(probe)
        jax.block_until_ready(d)
    out["h2d_transfer_probe_ms"] = round(
        (time.perf_counter() - t1) / 3 * 1e3, 3)
    _report(out)


def mesh_plan(deadline: float, b: int = BATCH, n_dev: int = 8) -> None:
    """Host routing plane of the sharded table: one vectorized
    `_OwnerPlan` + chip-local row map + grouped-GCM grid build at the
    headline batch size over 8 devices.  Pure host cost — the per-batch
    overhead mesh mode adds BEFORE any device work."""
    from libjitsi_tpu.mesh.table import (_OwnerPlan, local_rows,
                                         mesh_gcm_grid)

    rng = np.random.default_rng(31)
    ids = rng.integers(0, N_STREAMS, b).astype(np.int64)
    rows_per = N_STREAMS // n_dev
    t_plan = t_grid = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        plan = _OwnerPlan(ids, N_STREAMS, rows_per, n_dev)
        local = local_rows(plan, ids, N_STREAMS, rows_per, n_dev)
        t1 = time.perf_counter()
        mesh_gcm_grid(local)
        t2 = time.perf_counter()
        t_plan = min(t_plan, t1 - t0)
        t_grid = min(t_grid, t2 - t1)
        if time.monotonic() > deadline:
            break
    EXTRA["mesh_plan_ms"] = {
        "batch": b, "n_dev": n_dev,
        "owner_plan_ms": round(t_plan * 1e3, 3),
        "gcm_grid_ms": round(t_grid * 1e3, 3),
        "plan_pps_ceiling": round(b / t_plan, 1)}


def mesh_seam(deadline: float) -> None:
    """Sharded-table seam overhead on the device: `ShardedSrtpTable`
    on a ONE-device mesh vs the plain table — same host control plane,
    same chip; the delta is the owner-plan / shard_map /
    deferred-scatter seam."""
    _mesh_seam_body()


def _mesh_seam_body(n_streams: int = N_STREAMS, batch: int = 4096,
                     iters: int = 3) -> None:
    deadline = time.monotonic() + 45
    import jax
    from jax.sharding import Mesh

    from libjitsi_tpu.mesh import ShardedSrtpTable
    from libjitsi_tpu.rtp import header as rtp_header
    from libjitsi_tpu.transform.srtp import SrtpStreamTable

    rng = np.random.default_rng(9)
    mks = rng.integers(0, 256, (n_streams, 16), dtype=np.uint8)
    mss = rng.integers(0, 256, (n_streams, 14), dtype=np.uint8)
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("streams",))
    out: dict = {}

    def drive(table, key):
        lat = []
        for k in range(iters):
            streams = rng.permutation(n_streams)[:batch]
            b = rtp_header.build(
                [b"\xcd" * 160] * batch,
                [4000 + iters * int(key == "mesh1") + k] * batch,
                [k * 960] * batch, (0x30000 + streams).tolist(),
                [96] * batch, stream=streams.tolist())
            t0 = time.perf_counter()
            w = table.protect_rtp(b)
            lat.append(time.perf_counter() - t0)
            if time.monotonic() > deadline and len(lat) >= 2:
                break
        warm = lat[max(len(lat) // 3, 1):] or lat
        out[f"mesh_seam_{key}_ms"] = round(
            float(np.median(warm)) * 1e3, 3)

    plain = SrtpStreamTable(capacity=n_streams)
    plain.add_streams(np.arange(n_streams), mks, mss)
    drive(plain, "plain")
    _report(out)      # cumulative partial
    sh = ShardedSrtpTable(n_streams, mesh1)
    sh.add_streams(np.arange(n_streams), mks, mss)
    drive(sh, "mesh1")
    if out.get("mesh_seam_plain_ms"):
        out["mesh_seam_overhead_ratio"] = round(
            out["mesh_seam_mesh1_ms"] / out["mesh_seam_plain_ms"], 3)
    _report(out)


def mesh_cpu8(deadline: float) -> None:
    """The sharded product path END-TO-END on the virtual 8-device CPU
    mesh (the same geometry the driver's dryrun validates): sharded vs
    plain `protect_rtp` per-batch time.  CPU numbers — the point is the
    host-plane share and the seam scaling at 8 devices, not chip
    throughput."""
    _run_in_cpu_child("_mesh_cpu8_child", deadline, 55, env={
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                      " --xla_force_host_platform_device_count=8"
                      ).strip()})


def _mesh_cpu8_child(n_streams: int = N_STREAMS, batch: int = 1024,
                     iters: int = 2) -> None:
    # batch sized for the CPU backend's exec floor (~7 ms/packet-KB on
    # this box): the section's value is the 8-device seam RATIO, not
    # absolute CPU throughput — and batch must stay <= n_streams for
    # the permutation below.  Self-bound sits UNDER the parent's 55s
    # kill cap so the final print always happens.
    deadline = time.monotonic() + 40
    import jax

    jax.config.update("jax_platforms", "cpu")
    from libjitsi_tpu.mesh import ShardedSrtpTable, make_media_mesh
    from libjitsi_tpu.rtp import header as rtp_header
    from libjitsi_tpu.transform.srtp import SrtpStreamTable

    rng = np.random.default_rng(10)
    mks = rng.integers(0, 256, (n_streams, 16), dtype=np.uint8)
    mss = rng.integers(0, 256, (n_streams, 14), dtype=np.uint8)
    mesh = make_media_mesh()
    out: dict = {"mesh_cpu8_batch": batch,
                 "mesh_cpu8_n_dev": int(mesh.devices.size)}

    def drive(table, key):
        lat = []
        for k in range(iters):
            streams = rng.permutation(n_streams)[:batch]
            b = rtp_header.build(
                [b"\xef" * 160] * batch,
                [6000 + iters * int(key == "mesh8") + k] * batch,
                [k * 960] * batch, (0x40000 + streams).tolist(),
                [96] * batch, stream=streams.tolist())
            t0 = time.perf_counter()
            table.protect_rtp(b)
            lat.append(time.perf_counter() - t0)
            if time.monotonic() > deadline and len(lat) >= 2:
                break
        warm = lat[max(len(lat) // 3, 1):] or lat
        out[f"mesh_cpu8_{key}_ms"] = round(
            float(np.median(warm)) * 1e3, 3)

    plain = SrtpStreamTable(capacity=n_streams)
    plain.add_streams(np.arange(n_streams), mks, mss)
    drive(plain, "plain")
    _report(out)      # cumulative partial
    sh = ShardedSrtpTable(n_streams, mesh)
    sh.add_streams(np.arange(n_streams), mks, mss)
    drive(sh, "mesh8")
    if out.get("mesh_cpu8_plain_ms") and out.get("mesh_cpu8_mesh8_ms"):
        out["mesh_cpu8_ratio_vs_plain"] = round(
            out["mesh_cpu8_mesh8_ms"] / out["mesh_cpu8_plain_ms"], 3)
    _report(out)


def dense_tick(deadline: float, n_streams: int = 10_240) -> None:
    """Host cost of one decode-path tick at 10k streams: dense jitter
    insert+pop plus the batched GCC feed — the plane that used to be
    per-stream Python objects.  Pure host time (no device)."""
    from libjitsi_tpu.bwe.batched import BatchedRemoteBitrateEstimator
    from libjitsi_tpu.rtp.dense_jitter import DenseJitterBank

    jb = DenseJitterBank(capacity=n_streams, depth=16, payload_cap=64)
    bwe = BatchedRemoteBitrateEstimator(capacity=64)
    rng = np.random.default_rng(13)
    sids = np.arange(n_streams)
    tids = sids % 64
    pay = rng.integers(0, 256, (n_streams, 64), dtype=np.uint8)
    best = float("inf")
    for k in range(12):
        now = 5.0 + 0.02 * k
        t0 = time.perf_counter()
        jb.insert_batch(sids, np.full(n_streams, 100 + k),
                        np.full(n_streams, 160 * k), pay,
                        np.full(n_streams, 64), now)
        jb.pop_all(now + 0.001)
        bwe.incoming_batch(tids, np.full(n_streams, now * 1000),
                           np.full(n_streams,
                                   (int(now * (1 << 18)) & 0xFFFFFF)),
                           np.full(n_streams, 172))
        if k >= 2:
            best = min(best, time.perf_counter() - t0)
        if time.monotonic() > deadline and k >= 3:
            break
    bwe.update_estimate(6.0 * 1000)
    EXTRA["dense_receive_tick_ms_10k"] = round(best * 1e3, 3)


def _loop_fixture():
    """Fresh registry/SRTP-tables/chain for one echo-loop run (tables
    are stateful: each run needs its own).  Callers libjitsi_tpu.init()
    once themselves."""
    import libjitsi_tpu
    from libjitsi_tpu.core.packet import PacketBatch
    from libjitsi_tpu.service.media_stream import StreamRegistry
    from libjitsi_tpu.transform import (SrtpTransformEngine,
                                        TransformEngineChain)
    from libjitsi_tpu.transform.srtp import SrtpStreamTable

    mk, ms = bytes(range(16)), bytes(range(30, 44))
    mk2, ms2 = bytes(range(60, 76)), bytes(range(80, 94))
    reg = StreamRegistry(libjitsi_tpu.configuration_service(), capacity=16)
    rx_tab = SrtpStreamTable(capacity=16)
    rx_tab.add_stream(3, mk, ms)
    tx_tab = SrtpStreamTable(capacity=16)
    tx_tab.add_stream(3, mk2, ms2)
    chain = TransformEngineChain([SrtpTransformEngine(tx_tab, rx_tab)])

    def on_media(batch, ok):
        rows = np.nonzero(ok)[0]
        if len(rows) == 0:
            return None
        return PacketBatch(batch.data[rows],
                           np.asarray(batch.length)[rows],
                           batch.stream[rows])

    return reg, chain, on_media, (mk, ms), (mk2, ms2)


def loop_rtt(deadline: float) -> None:
    """End-to-end MediaLoop tick over REAL loopback UDP (SURVEY
    §3.2/§3.4's socket→chain→socket hot loop)."""
    _loop_rtt_body()


def loop_pipelined_gain(deadline: float) -> None:
    """SURVEY §7 step 4's dispatch/flush overlap seam, sync vs
    pipelined MediaLoop on the same echo workload."""
    _loop_gain_body()


def _loop_rtt_body(n_pkts: int = 256, cycles: int = 12) -> None:
    """Body of loop_rtt: client protect → send → bridge
    recv_batch → SSRC demux → unprotect → echo → re-protect → send →
    client recv, the path the 2 ms p99 budget governs.  The cycle
    time includes four device launches (client protect/unprotect +
    bridge unprotect/protect).
    """
    # self-bound inside the section's box
    deadline = time.monotonic() + 45
    import libjitsi_tpu
    from libjitsi_tpu.io import UdpEngine
    from libjitsi_tpu.io.loop import MediaLoop
    from libjitsi_tpu.rtp import header as rtp_header
    from libjitsi_tpu.transform.srtp import SrtpStreamTable

    libjitsi_tpu.stop()
    libjitsi_tpu.init()
    reg, chain, on_media, (mk, ms), (mk2, ms2) = _loop_fixture()
    bridge = MediaLoop(UdpEngine(port=0, max_batch=n_pkts + 8), reg,
                       on_media=on_media, chain=chain, recv_window_ms=0)
    reg.map_ssrc(0xBEEF01, 3)
    c_tx = SrtpStreamTable(capacity=1)
    c_tx.add_stream(0, mk, ms)
    c_rx = SrtpStreamTable(capacity=1)
    c_rx.add_stream(0, mk2, ms2)
    client = UdpEngine(port=0, max_batch=n_pkts + 8)

    lat = []
    done_pkts = 0
    sent_pkts = 0
    t_all = time.perf_counter()
    try:
        for cyc in range(cycles):
            payloads = [b"\xab" * 160] * n_pkts
            b = rtp_header.build(payloads, list(range(cyc * n_pkts,
                                                      (cyc + 1) * n_pkts)),
                                 [cyc * 960] * n_pkts, [0xBEEF01] * n_pkts,
                                 [96] * n_pkts, stream=[0] * n_pkts)
            t1 = time.perf_counter()
            wire = c_tx.protect_rtp(b)
            client.send_batch(wire, "127.0.0.1", bridge.engine.port)
            sent_pkts += n_pkts
            got = 0
            back_parts = []
            cyc_deadline = time.perf_counter() + 5.0
            while got < n_pkts and time.perf_counter() < cyc_deadline:
                bridge.tick()
                back, _, _ = client.recv_batch(timeout_ms=1)
                if back.batch_size:
                    back_parts.append(back)
                    got += back.batch_size
            for back in back_parts:
                back.stream[:] = 0
                _, ok = c_rx.unprotect_rtp(back)
                done_pkts += int(ok.sum())
            lat.append(time.perf_counter() - t1)
            if time.monotonic() > deadline and cyc >= 3:
                break
        total = time.perf_counter() - t_all
    finally:
        bridge.engine.close()
        client.close()
    warm = len(lat) // 3
    tail = np.asarray(lat[warm:])
    out = {"loop_udp_echo_pps": round(done_pkts / total, 1),
           "loop_udp_cycle_p99_ms": round(
               float(np.percentile(tail, 99) * 1e3), 3),
           "loop_udp_cycle_p50_ms": round(
               float(np.percentile(tail, 50) * 1e3), 3)}
    if done_pkts != sent_pkts:      # degradation field, not a fatal assert
        out["loop_udp_lost_pkts"] = sent_pkts - done_pkts
    _report(out)


def _loop_gain_body(n_pkts: int = 512, cycles: int = 12) -> None:
    """Body of loop_pipelined_gain: the pipelined MediaLoop
    dispatches the reply protect and flushes it at the top of the next
    tick, so the device launch overlaps the next recv window instead of
    serializing with it.  Same echo workload both ways."""
    # self-bound inside the section's box (see _loop_rtt_body); one
    # sync+pipelined pair is the minimum result
    deadline = time.monotonic() + 55
    import libjitsi_tpu
    from libjitsi_tpu.io import UdpEngine
    from libjitsi_tpu.io.loop import MediaLoop
    from libjitsi_tpu.rtp import header as rtp_header
    from libjitsi_tpu.transform.srtp import SrtpStreamTable

    libjitsi_tpu.stop()
    libjitsi_tpu.init()

    def run_mode(pipelined: bool) -> float:
        # fresh fixture per run: SRTP tables are stateful
        reg, chain, on_media, (mk, ms), _ = _loop_fixture()
        loop = MediaLoop(UdpEngine(port=0, max_batch=n_pkts + 8), reg,
                         on_media=on_media, chain=chain,
                         recv_window_ms=0, pipelined=pipelined)
        reg.map_ssrc(0xBEEF01, 3)
        c_tx = SrtpStreamTable(capacity=1)
        c_tx.add_stream(0, mk, ms)
        client = UdpEngine(port=0, max_batch=n_pkts + 8)
        # streaming shape: bursts keep flowing without waiting for
        # their echoes, so the pipelined loop holds a dispatched batch
        # in flight across each next tick (the sync loop materializes
        # per tick); echoes drain opportunistically
        echoed = 0
        t0 = time.perf_counter()
        for cyc in range(cycles):
            b = rtp_header.build([b"\xab" * 160] * n_pkts,
                                 list(range(cyc * n_pkts,
                                            (cyc + 1) * n_pkts)),
                                 [cyc * 960] * n_pkts,
                                 [0xBEEF01] * n_pkts, [96] * n_pkts,
                                 stream=[0] * n_pkts)
            client.send_batch(c_tx.protect_rtp(b), "127.0.0.1",
                              loop.engine.port)
            loop.tick()
            back, _, _ = client.recv_batch(timeout_ms=0)
            echoed += back.batch_size
        for _ in range(8 * cycles):
            loop.tick()
            back, _, _ = client.recv_batch(timeout_ms=1)
            echoed += back.batch_size
            if echoed >= cycles * n_pkts:
                break
        loop.flush_sends()
        back, _, _ = client.recv_batch(timeout_ms=5)
        echoed += back.batch_size
        dt = time.perf_counter() - t0
        loop.engine.close()
        client.close()
        return echoed / dt

    # dispatch noise can bury the overlap effect in a single pair;
    # interleave runs per mode while the box allows and keep each
    # mode's best (the least-stalled sample)
    sync_pps = pipe_pps = 0.0
    for _ in range(3):
        sync_pps = max(sync_pps, run_mode(False))
        pipe_pps = max(pipe_pps, run_mode(True))
        # cumulative partial print per pair (parent keeps the last line)
        _report({"loop_echo_sync_pps": round(sync_pps, 1),
                 "loop_echo_pipelined_pps": round(pipe_pps, 1)})
        if time.monotonic() > deadline:
            break


def main():
    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)
    # The watchdog thread fires even when the main thread sits in a
    # native call (a long compile) where a SIGALRM-style handler would
    # be deferred until the call returns.
    wd = threading.Timer(BUDGET_S + 50, _watchdog)
    wd.daemon = True
    wd.start()
    try:
        # Headline-first: a run cut at its budget still holds the
        # device microbenches; the host/production-path sections run
        # last.
        section("tpu_pps", 20, 200, tpu_pps)
        section("cpu_pps", 3, 20, cpu_pps)
        section("dense_tick", 3, 25, dense_tick)
        section("mesh_plan", 2, 15, mesh_plan)
        # quick device sections before the compile-heavy sweeps so a
        # cold-cache run still records them (fetch-verified sampling
        # made every section ~10x pricier; warm cache covers the rest)
        section("mixer", 6, 20, mixer)
        section("bridge_mixes", 6, 20, bridge_mixes)
        section("fanout", 8, 30, fanout)
        section("gcm_fanout", 8, 30, gcm_fanout)
        section("table_roundtrip_probe", 25, 90, table_roundtrip_probe)
        section("gcm_sweep", 25, 90, gcm_sweep)
        section("table_path", 25, 75, table_path)
        section("mesh_seam", 20, 65, mesh_seam)
        section("mesh_cpu8", 20, 60, mesh_cpu8)
        # boxes exceed the bodies' self-bounds
        section("loop_rtt", 20, 65, loop_rtt)
        section("loop_pipelined_gain", 25, 75, loop_pipelined_gain)
    finally:
        emit()


if __name__ == "__main__":
    main()
