"""jitlint: the four checkers against seeded true/false positives, the
pragma + baseline machinery, and the real CLI against the real tree
(the gate itself is tier-1-tested).

Every TP fixture is drawn from a failure class this repo actually hit:
seq-wrap (PR 2: jitter buffer / lookup_nack / build_nack), host-sync
(the ~100 ms scalar-fetch floor in bench.py), secret-dependent lookup
(the reason kernels/aes_bitsliced.py exists), counter drift (the
recovery-ladder counters of PR 2).
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from libjitsi_tpu.analysis import baseline as baseline_mod
from libjitsi_tpu.analysis.checkers.drift import (check_metrics_drift,
                                                  check_snapshot_drift)
from libjitsi_tpu.analysis.checkers.hotalloc import check_hotpath_alloc
from libjitsi_tpu.analysis.checkers.hotpath import check_hotpath_purity
from libjitsi_tpu.analysis.checkers.rtpmod16 import check_rtp_mod16
from libjitsi_tpu.analysis.checkers.secrets import check_secret_taint
from libjitsi_tpu.analysis.core import FileContext
from libjitsi_tpu.analysis.driver import run_lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "libjitsi_tpu")


def ctx_of(src: str, relpath: str = "libjitsi_tpu/somefile.py"):
    return FileContext(relpath, relpath, textwrap.dedent(src))


def rules_of(findings):
    return [f.rule for f in findings]


# ------------------------------------------------------- hotpath-purity

def test_hotpath_item_and_int_fire():
    """Seeded from the host-sync class: one .item() in a jitted path
    re-introduces the ~100 ms scalar-fetch floor."""
    src = """
    import jax

    @jax.jit
    def f(x):
        n = x.sum().item()
        m = int(x[0])
        return n + m
    """
    found = check_hotpath_purity(ctx_of(src))
    assert len(found) == 2
    assert all(f.rule == "hotpath-purity" for f in found)
    assert "host sync" in found[0].message


def test_hotpath_python_branch_on_tracer_fires():
    src = """
    import jax

    @jax.jit
    def f(x):
        if x > 0:
            return x
        while x < 3:
            x = x + 1
        return -x
    """
    found = check_hotpath_purity(ctx_of(src))
    assert len(found) == 2
    assert "tracer-derived" in found[0].message


def test_hotpath_partial_jit_and_static_argnames():
    """static_argnames are Python values at trace time: int() on them
    must NOT fire; the traced arg still must."""
    src = """
    import functools, jax

    @functools.partial(jax.jit, static_argnames=("n",))
    def f(x, n):
        k = int(n)          # static: fine
        j = int(x)          # traced: host sync
        return k + j
    """
    found = check_hotpath_purity(ctx_of(src))
    assert len(found) == 1
    assert "`int()`" in found[0].message


def test_hotpath_lax_cond_and_none_checks_do_not_fire():
    """lax.cond on tracers is THE sanctioned branch; `is None` tests
    are pytree-structure checks; shape reads are static."""
    src = """
    import jax
    from jax import lax
    import jax.numpy as jnp

    @jax.jit
    def f(x, aux=None):
        y = lax.cond(x[0] > 0, lambda v: v, lambda v: -v, x)
        if aux is None:
            y = y + 1
        if x.shape[0] > 4:
            y = y * 2
        if len(x) > 2:
            y = y - 1
        return jnp.where(x > 0, y, -y)
    """
    assert check_hotpath_purity(ctx_of(src)) == []


def test_hotpath_np_asarray_and_nonzero_fire():
    src = """
    import jax
    import numpy as np
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        h = np.asarray(x)
        r = jnp.nonzero(x)
        ok = jnp.nonzero(x, size=4)      # static size: fine
        return h, r, ok
    """
    found = check_hotpath_purity(ctx_of(src))
    assert len(found) == 2


def test_hotpath_call_wrapped_jit_detected():
    """mesh-style `jax.jit(shard_map(fn, ...))` wrapping."""
    src = """
    import jax

    def inner(x):
        return x.item()

    wrapped = jax.jit(jax.shard_map(inner, mesh=None))
    """
    found = check_hotpath_purity(ctx_of(src))
    assert len(found) == 1


def test_hotpath_unjitted_host_code_is_free():
    src = """
    def host(x):
        if x > 0:
            return int(x)
        return x.item()
    """
    assert check_hotpath_purity(ctx_of(src)) == []


# -------------------------------------------------------- secret-taint

def test_secret_branch_and_table_lookup_fire():
    """Seeded from the secret-dependent-lookup class the bitsliced AES
    core eliminates."""
    src = """
    SBOX = list(range(256))

    def leak(key, data):
        if key[0] == 0x80:            # secret-dependent branch
            return data
        return SBOX[key[1]]           # secret-indexed lookup
    """
    found = check_secret_taint(ctx_of(src, "libjitsi_tpu/kernels/fx.py"))
    rules = rules_of(found)
    assert rules.count("secret-taint") == len(found)
    msgs = " | ".join(f.message for f in found)
    assert "secret-dependent branch" in msgs
    assert "secret-indexed lookup" in msgs


def test_secret_taint_propagates_through_assignment():
    src = """
    def leak(master_key):
        derived = master_key[:16]
        t = derived
        if t == b"16-byte-constant":
            return 1
        return 0
    """
    found = check_secret_taint(ctx_of(src, "libjitsi_tpu/kernels/fx.py"))
    assert len(found) == 1


def test_secret_structure_checks_do_not_fire():
    """len()/shape/dtype/`is None` are about structure, not contents —
    kdf.py validates key lengths everywhere and must stay clean."""
    src = """
    def derive(master_key, salt=None):
        if len(master_key) != 16:
            raise ValueError("bad key size")
        if salt is None:
            salt = b"\\x00" * 14
        if master_key is None:
            return None
        return master_key + salt
    """
    assert check_secret_taint(
        ctx_of(src, "libjitsi_tpu/transform/srtp/fx.py")) == []


def test_secret_vectorized_compare_does_not_fire():
    """`ok = tags == expected` is the constant-time idiom — a verdict
    array, not a branch."""
    src = """
    import numpy as np

    def verify(tags, expected_tags):
        ok = tags == expected_tags
        return np.where(ok, 1, 0)
    """
    assert check_secret_taint(
        ctx_of(src, "libjitsi_tpu/kernels/fx.py")) == []


def test_secret_scope_is_kernels_and_srtp_only():
    src = """
    def host(key):
        if key[0]:
            return 1
        return 0
    """
    assert check_secret_taint(ctx_of(src, "libjitsi_tpu/service/fx.py")) == []
    assert len(check_secret_taint(
        ctx_of(src, "libjitsi_tpu/kernels/fx.py"))) == 1


# ----------------------------------------------------------- rtp-mod16

def test_mod16_raw_compare_fires():
    """Seeded from the PR 2 seq-wrap class: raw `<` on seqs misorders
    across 65535->0 (the jitter-buffer / lookup_nack bug)."""
    src = """
    def newest(a_seq, b_seq):
        if a_seq < b_seq:
            return b_seq
        return a_seq
    """
    found = check_rtp_mod16(ctx_of(src))
    assert len(found) == 1
    assert "wrap" in found[0].message


def test_mod16_unmasked_arith_and_augassign_fire():
    src = """
    class Tx:
        def bump(self, n):
            self._tx_seq += n
            nxt = self.base_seq + 1
            return nxt
    """
    found = check_rtp_mod16(ctx_of(src))
    assert len(found) == 2


def test_mod16_masked_and_helper_forms_do_not_fire():
    src = """
    from libjitsi_tpu.core.rtp_math import seq_delta, is_newer_seq

    def ok(seq, last_seq, roc):
        a = (seq + 1) & 0xFFFF
        b = (seq - last_seq) % 65536
        d = seq_delta(seq + 1, last_seq)
        n = is_newer_seq(seq, last_seq)
        hi = seq >> 8
        lo = seq & 0xFF
        if seq >= 0:                      # sentinel compare
            pass
        new_roc = (roc + 1) & 0xFFFFFFFF
        return a, b, d, n, hi, lo, new_roc
    """
    assert check_rtp_mod16(ctx_of(src)) == []


def test_mod16_seq_delta_internals_do_not_fire():
    """The helper module itself subtracts raw seqs by design."""
    path = os.path.join(PKG, "core", "rtp_math.py")
    with open(path) as fh:
        ctx = FileContext(path, "libjitsi_tpu/core/rtp_math.py", fh.read())
    assert check_rtp_mod16(ctx) == []


def test_mod16_ext_counters_exempt():
    """`*_ext` names are 64-bit extended counters — raw math is the
    point (SeqNumUnwrapper output, RFC 3711 indices)."""
    src = """
    def unwrapped(next_seq_ext, k):
        top = next_seq_ext + k
        next_seq_ext += 1
        return top
    """
    assert check_rtp_mod16(ctx_of(src)) == []


def test_mod16_slice_and_range_and_max_fire():
    src = """
    import numpy as np

    def walk(buf, start_seq, end_seq, seqs):
        a = buf[start_seq:end_seq]
        for s in range(start_seq, end_seq):
            pass
        hi = max(start_seq, end_seq)
        return a, hi
    """
    found = check_rtp_mod16(ctx_of(src))
    assert len(found) == 3


# --------------------------------------------------------------- drift

def test_drift_snapshot_missing_field_fires():
    """Seeded from the crash-recover class: a field outside
    _SNAP_FIELDS restores as stale zeros."""
    src = """
    import numpy as np
    from libjitsi_tpu.utils.checkpoint import ArraySnapshotMixin

    class Bank(ArraySnapshotMixin):
        _SNAP_FIELDS = ("a",)

        def __init__(self):
            self.a = np.zeros(4)
            self.forgotten = np.zeros(4)
    """
    found = check_snapshot_drift(ctx_of(src))
    assert len(found) == 1
    assert "forgotten" in found[0].message


def test_drift_snapshot_covered_and_stale_entry():
    src = """
    import numpy as np
    from libjitsi_tpu.utils.checkpoint import ArraySnapshotMixin

    class Bank(ArraySnapshotMixin):
        _SNAP_FIELDS = ("a", "ghost")

        def __init__(self):
            self.a = np.zeros(4)
    """
    found = check_snapshot_drift(ctx_of(src))
    assert len(found) == 1
    assert "ghost" in found[0].message


def test_drift_metrics_partial_coverage_fires():
    """Seeded from the recovery-ladder counters: a class exporting SOME
    counters that silently grew another one."""
    src = """
    class Recovery:
        def __init__(self):
            self.nacks_sent = 0
            self.rtx_cache_miss = 0

        def work(self):
            self.nacks_sent += 1
            self.rtx_cache_miss += 1

        def register_metrics(self, registry):
            registry.register_counters(self, (
                ("nacks_sent", "lost seqs NACKed"),
            ), prefix="r")
    """
    ctx = ctx_of(src)
    found = check_metrics_drift({ctx.relpath: ctx})
    assert len(found) == 1
    assert "rtx_cache_miss" in found[0].message


def test_drift_metrics_full_coverage_and_unregistered_class_clean():
    src = """
    class Covered:
        def __init__(self):
            self.frames_sent = 0

        def work(self):
            self.frames_sent += 1

        def register_metrics(self, registry):
            registry.register_counters(self, ("frames_sent",))

    class Internal:
        def __init__(self):
            self.cache_miss = 0

        def work(self):
            self.cache_miss += 1
    """
    ctx = ctx_of(src)
    assert check_metrics_drift({ctx.relpath: ctx}) == []


def test_drift_metrics_dangling_registration_fires():
    src = """
    class R:
        def __init__(self):
            self.hits_count = 0

        def work(self):
            self.hits_count += 1

        def register_metrics(self, registry):
            registry.register_counters(self, (
                ("hits_count", "ok"),
                ("hits_cuont", "typo"),
            ))
    """
    ctx = ctx_of(src)
    found = check_metrics_drift({ctx.relpath: ctx})
    assert any("hits_cuont" in f.message for f in found)


def test_drift_trunk_counters_partial_coverage_fires():
    """Seeded from the cascade trunk (mesh/cascade.py): a relay class
    that grows a recovery counter without exporting it — the failover
    dashboard would silently under-report trunk RTX."""
    src = """
    class Relay:
        def __init__(self):
            self.relay_frames_total = 0
            self.rtx_served_total = 0
            self.plc_fallthrough_total = 0

        def relay(self):
            self.relay_frames_total += 1

        def serve_nack(self):
            self.rtx_served_total += 1

        def expire(self):
            self.plc_fallthrough_total += 1

        def register_metrics(self, registry):
            registry.register_counters(self, (
                ("relay_frames_total", "frames relayed"),
                ("plc_fallthrough_total", "losses conceded to PLC"),
            ), prefix="trunk")
    """
    ctx = ctx_of(src)
    found = check_metrics_drift({ctx.relpath: ctx})
    assert len(found) == 1
    assert "rtx_served_total" in found[0].message


def test_drift_trunk_counters_full_coverage_clean():
    """The same relay with every counter registered (the shape
    mesh/cascade.py actually ships) must not fire."""
    src = """
    class Relay:
        def __init__(self):
            self.relay_frames_total = 0
            self.rtx_served_total = 0

        def relay(self):
            self.relay_frames_total += 1

        def serve_nack(self):
            self.rtx_served_total += 1

        def register_metrics(self, registry):
            registry.register_counters(self, (
                ("relay_frames_total", "frames relayed"),
                ("rtx_served_total", "RTX served from cache"),
            ), prefix="trunk")
    """
    ctx = ctx_of(src)
    assert check_metrics_drift({ctx.relpath: ctx}) == []


def test_drift_slospec_unregistered_metric_fires():
    """An SloSpec naming a family no registration defines burns
    against a permanently-absent signal — the SLO can never fire."""
    src = """
    from libjitsi_tpu.utils.slo import SloSpec

    SPECS = [
        SloSpec("ghost", objective=0.99,
                bad_metric="never_registered_bad",
                total_metric="bridge_forwarded"),
    ]

    def register(registry):
        registry.register_scalar("bridge_forwarded", lambda: 0,
                                 kind="counter")
    """
    ctx = ctx_of(src)
    found = check_metrics_drift({ctx.relpath: ctx})
    assert len(found) == 1
    assert "never_registered_bad" in found[0].message
    assert "ghost" in found[0].message


def test_drift_slospec_exact_and_suffix_matched_refs_clean():
    """Refs resolved by an exact constant registration AND by a
    register_counters suffix under a call-site prefix are both clean
    (prefix-parameterized names must not false-positive)."""
    src = """
    from libjitsi_tpu.utils.slo import SloSpec

    SPECS = [
        SloSpec("loss", objective=0.999,
                bad_metric="recovery_nacks_abandoned",
                total_metric="bridge_forwarded"),
    ]

    class Recovery:
        def __init__(self):
            self.nacks_abandoned = 0

        def work(self):
            self.nacks_abandoned += 1

        def register_metrics(self, registry):
            registry.register_counters(self, (
                ("nacks_abandoned", "deadline passed"),
            ), prefix="recovery")

    def register(registry):
        registry.register_scalar("bridge_forwarded", lambda: 0,
                                 kind="counter")
    """
    ctx = ctx_of(src)
    assert check_metrics_drift({ctx.relpath: ctx}) == []


def test_drift_exemplar_histogram_never_fed_fires():
    """exemplars=True reserves exemplar slots; if no observe call ever
    passes exemplar=, every OpenMetrics scrape ships them empty."""
    src = """
    class Loop:
        def __init__(self, registry):
            self.journey = registry.histogram(
                "packet_journey_seconds", (0.001, 0.01),
                exemplars=True)

        def on_egress(self, dt):
            self.journey.observe(dt)
    """
    ctx = ctx_of(src)
    found = check_metrics_drift({ctx.relpath: ctx})
    assert len(found) == 1
    assert "exemplar" in found[0].message
    assert "journey" in found[0].message


def test_drift_exemplar_histogram_fed_anywhere_clean():
    """The exemplar feed may live in another file — the check is over
    the whole-tree index, not per file."""
    src_def = """
    class Loop:
        def __init__(self, registry):
            self.journey = registry.histogram(
                "packet_journey_seconds", (0.001, 0.01),
                exemplars=True)
    """
    src_use = """
    class Egress:
        def flush(self, loop, dt, trace):
            loop.journey.observe(
                dt, exemplar={"trace_id": str(trace)})
    """
    a = ctx_of(src_def, relpath="libjitsi_tpu/io/loop.py")
    b = ctx_of(src_use, relpath="libjitsi_tpu/service/x.py")
    assert check_metrics_drift({a.relpath: a, b.relpath: b}) == []


def test_drift_exemplar_histogram_vec_never_fed_fires():
    """A hop-labeled HistogramVec created with exemplars=True whose
    children only ever observe WITHOUT exemplar= ships empty exemplar
    slots on every label — same bug as the plain-histogram case, one
    label axis over."""
    src = """
    class Sup:
        def __init__(self, registry):
            self.journey_vec = registry.histogram_vec(
                "packet_journey_seconds", (0.001, 0.01), "hop",
                exemplars=True)

        def note_hop(self, hop, dt):
            self.journey_vec.labels(hop).observe(dt)
    """
    ctx = ctx_of(src)
    found = check_metrics_drift({ctx.relpath: ctx})
    assert len(found) == 1
    assert "exemplar" in found[0].message
    assert "journey_vec" in found[0].message


def test_drift_exemplar_histogram_vec_chained_labels_feed_clean():
    """The chained `vec.labels(hop).observe(..., exemplar=...)` idiom
    feeds the vec's exemplar slots — must not false-positive; the same
    for a bound child (`h = vec.labels("local")`) fed through its
    local name."""
    src = """
    class Sup:
        def __init__(self, registry):
            self.journey_vec = registry.histogram_vec(
                "packet_journey_seconds", (0.001, 0.01), "hop",
                exemplars=True)
            self.local_hist = self.journey_vec.labels("local")

        def note_hop(self, hop, dt, trace):
            self.journey_vec.labels(hop).observe(
                dt, exemplar={"trace_id": str(trace)})
    """
    ctx = ctx_of(src)
    assert check_metrics_drift({ctx.relpath: ctx}) == []


def test_drift_exemplar_vec_fed_via_bound_child_alias_clean():
    """A vec fed ONLY through a bound child histogram
    (`h = vec.labels(x)` then `h.observe(..., exemplar=...)`) is fed —
    the labels() alias edge credits the parent vec."""
    src = """
    class Loop:
        def __init__(self, registry):
            self.journey_vec = registry.histogram_vec(
                "packet_journey_seconds", (0.001, 0.01), "hop",
                exemplars=True)
            self.journey_hist = self.journey_vec.labels("local")

        def on_egress(self, dt, trace):
            self.journey_hist.observe(
                dt, exemplar={"trace_id": str(trace)})
    """
    ctx = ctx_of(src)
    assert check_metrics_drift({ctx.relpath: ctx}) == []


def test_drift_histogram_observed_but_never_registered_fires():
    """A Histogram constructed and fed but never handed to the
    registry records distributions nobody can scrape."""
    src = """
    from libjitsi_tpu.utils.metrics import Histogram

    class Bank:
        def __init__(self):
            self.jitter_hist = Histogram((0.01, 0.1))

        def tick(self, vals):
            self.jitter_hist.observe_array(vals)
    """
    ctx = ctx_of(src)
    found = check_metrics_drift({ctx.relpath: ctx})
    assert len(found) == 1
    assert "jitter_hist" in found[0].message
    assert "never registered" in found[0].message


def test_drift_histogram_registered_forms_are_clean():
    """Both registration idioms clear the check — an explicit
    register_histogram (even in ANOTHER file) and the
    registry.histogram factory, which registers on creation.  An
    `.observe()` on a non-histogram attr (Watchdog-style) is out of
    scope entirely."""
    src = """
    from libjitsi_tpu.utils.metrics import Histogram

    class Bank:
        def __init__(self):
            self.jitter_hist = Histogram((0.01, 0.1))

        def tick(self, vals):
            self.jitter_hist.observe_array(vals)
    """
    reg = """
    def wire(bank, registry):
        registry.register_histogram("jitter", bank.jitter_hist)
    """
    factory = """
    class Loop:
        def __init__(self, registry):
            self.size_hist = registry.histogram("sizes", (64, 1500))
            self.watchdog = object()

        def tick(self, lens):
            self.size_hist.observe_array(lens)
            self.watchdog.observe(0.1)
    """
    c1, c2 = ctx_of(src), ctx_of(reg, "libjitsi_tpu/other.py")
    assert check_metrics_drift({c1.relpath: c1, c2.relpath: c2}) == []
    c3 = ctx_of(factory, "libjitsi_tpu/loop.py")
    assert check_metrics_drift({c3.relpath: c3}) == []


def test_drift_undeclared_admit_reason_fires():
    """A refusal literal outside the ADMIT_REASONS tuple is an untyped
    reason — the soak gates' `refused <= ADMIT_REASONS` assertions and
    the admit_rejected{reason=...} label set never heard of it.  The
    cross-file shape mirrors the real tree: the tuple lives in
    lifecycle, the refusal site in the supervisor."""
    decl = """
    ADMIT_REASONS = ("capacity", "fast_burn", "trunk_down")
    """
    refuse = """
    class Supervisor:
        def admission_decision(self):
            if self.burning:
                return False, "fast_burn"
            if self.haunted:
                return False, "mystery"
            return True, "ok"
    """
    c1 = ctx_of(decl, "libjitsi_tpu/service/lifecycle.py")
    c2 = ctx_of(refuse, "libjitsi_tpu/service/supervisor.py")
    found = check_metrics_drift({c1.relpath: c1, c2.relpath: c2})
    assert len(found) == 1
    assert "mystery" in found[0].message
    assert "ADMIT_REASONS" in found[0].message
    assert found[0].path == "libjitsi_tpu/service/supervisor.py"


def test_drift_declared_admit_reasons_are_clean():
    """Declared refusal literals clear the check in both shapes — the
    `(False, "reason")` pair and the bare-string `admit_reason` form —
    and the `"ok"` accept token is never read as a reason.  A tree
    with no ADMIT_REASONS declaration at all is out of scope (fixture
    trees without an admission plane)."""
    decl = """
    ADMIT_REASONS = ("capacity", "fast_burn", "trunk_down",
                     "trunk_backlog")
    """
    refuse = """
    class Supervisor:
        def admission_decision(self):
            if self.burning:
                return False, "fast_burn"
            return True, "ok"

    class Trunk:
        def admit_reason(self):
            if self.state != "up":
                return "trunk_down"
            if self.backlog:
                return "trunk_backlog"
            return None
    """
    c1 = ctx_of(decl, "libjitsi_tpu/service/lifecycle.py")
    c2 = ctx_of(refuse, "libjitsi_tpu/service/supervisor.py")
    assert check_metrics_drift({c1.relpath: c1, c2.relpath: c2}) == []
    # no declaration anywhere -> the refusal site alone is out of scope
    assert check_metrics_drift({c2.relpath: c2}) == []


def test_drift_capacity_forecast_without_families_fires():
    """Declaring the `capacity_forecast` reason contracts the tree to
    export the capacity_* families — a forecast that refuses joins
    with no scrapeable headroom explanation is exactly the silent
    wiring bug the drift rule exists for."""
    decl = """
    ADMIT_REASONS = ("capacity", "capacity_forecast")
    """
    ctx = ctx_of(decl, "libjitsi_tpu/service/lifecycle.py")
    found = check_metrics_drift({ctx.relpath: ctx})
    fams = {f.message.split("`")[3] for f in found}
    assert fams == {"capacity_headroom_users", "capacity_bottleneck",
                    "capacity_estimate_confidence",
                    "capacity_forecast_refusals"}


def test_drift_capacity_forecast_with_families_clean():
    """The real wiring — CapacityModel registering all four families
    (in another file, like utils/capacity.py does) — clears the
    contract."""
    decl = """
    ADMIT_REASONS = ("capacity", "capacity_forecast")
    """
    model = """
    class CapacityModel:
        def register_metrics(self, registry):
            registry.register_scalar(
                "capacity_headroom_users", lambda: self.headroom)
            registry.register_multi(
                "capacity_bottleneck", self._bottleneck_samples)
            registry.register_scalar(
                "capacity_estimate_confidence", self.confidence)
            registry.register_scalar(
                "capacity_forecast_refusals",
                lambda: self.forecast_refusals)
    """
    c1 = ctx_of(decl, "libjitsi_tpu/service/lifecycle.py")
    c2 = ctx_of(model, "libjitsi_tpu/utils/capacity.py")
    assert check_metrics_drift({c1.relpath: c1, c2.relpath: c2}) == []


def _perf_tree(tmp_path, baseline_keys, scenario_ids):
    """Fake repo: PERF_BASELINE.json + scripts/perf_gate.py + one
    indexed file whose path anchors the disk walk-up."""
    tmp_path.joinpath("PERF_BASELINE.json").write_text(json.dumps(
        {"_meta": {"git": "0123abc"}, **{k: {"value": 1.0}
                                         for k in baseline_keys}}))
    sdir = tmp_path / "scripts"
    sdir.mkdir()
    body = "\n".join(f'def _s{i}():\n    return 1.0'
                     for i in range(len(scenario_ids)))
    entries = ", ".join(f'"{sid}": _s{i}'
                        for i, sid in enumerate(scenario_ids))
    sdir.joinpath("perf_gate.py").write_text(
        body + "\nSCENARIOS = {" + entries + "}\n")
    pkg = tmp_path / "libjitsi_tpu"
    pkg.mkdir()
    mod = pkg / "mod.py"
    mod.write_text("x = 1\n")
    ctx = FileContext(str(mod), "libjitsi_tpu/mod.py", "x = 1\n")
    return {ctx.relpath: ctx}


def test_drift_perf_baseline_stale_and_ungated_fire(tmp_path):
    """Both directions in one tree: a baseline key no scenario backs
    (the gate never compares it) AND a scenario with no baseline entry
    (free to regress forever)."""
    index = _perf_tree(tmp_path, baseline_keys={"old_pps", "loop_x"},
                       scenario_ids={"loop_x", "new_y"})
    found = [f for f in check_metrics_drift(index)
             if f.path == "PERF_BASELINE.json"]
    msgs = "\n".join(f.message for f in found)
    assert len(found) == 2
    assert "`old_pps` matches no perf_gate scenario" in msgs
    assert "`new_y` has no PERF_BASELINE.json entry" in msgs
    assert all(f.rule == "drift" for f in found)


def test_drift_perf_baseline_in_sync_is_clean(tmp_path):
    """Matching key sets (plus the ignored _meta) produce nothing; a
    corrupt baseline is a single loud finding, not a crash."""
    index = _perf_tree(tmp_path, baseline_keys={"loop_x", "prot_y"},
                       scenario_ids={"loop_x", "prot_y"})
    assert [f for f in check_metrics_drift(index)
            if f.path == "PERF_BASELINE.json"] == []
    tmp_path.joinpath("PERF_BASELINE.json").write_text("{nope")
    found = [f for f in check_metrics_drift(index)
             if f.path == "PERF_BASELINE.json"]
    assert len(found) == 1 and "not valid JSON" in found[0].message


def test_drift_perf_baseline_pure_helper_and_real_files_agree():
    """check_perf_baseline is a set comparison; and the REAL checked-in
    baseline must match the REAL gate script right now."""
    from libjitsi_tpu.analysis.checkers.drift import (
        _perf_gate_scenario_ids, check_perf_baseline)

    assert check_perf_baseline({"a"}, {"a"}) == []
    msgs = check_perf_baseline({"a", "stale"}, {"a", "ungated"})
    assert len(msgs) == 2
    real_ids = _perf_gate_scenario_ids(
        os.path.join(REPO, "scripts", "perf_gate.py"))
    with open(os.path.join(REPO, "PERF_BASELINE.json")) as fh:
        real_keys = {k for k in json.load(fh) if not k.startswith("_")}
    assert real_ids, "SCENARIOS literal not found in perf_gate.py"
    assert check_perf_baseline(real_keys, real_ids) == []


def test_drift_baseline_meta_git_must_be_a_hash(tmp_path):
    """A baseline whose _meta.git is not a commit hash (the PR 11
    failure class: `--write-baseline` once left a stale hand-edited
    stamp) fires exactly one finding; a real hash is clean."""
    from libjitsi_tpu.analysis.checkers.drift import check_baseline_meta

    assert check_baseline_meta({"git": "0123abc"}) == []
    assert check_baseline_meta({"git": "c041577" + "0" * 33}) == []
    for bad in ({"git": "unknown"}, {"git": ""}, {}, None,
                {"git": "v1.2.3"}, {"git": "0123ABC"}):
        msgs = check_baseline_meta(bad)
        assert len(msgs) == 1 and "_meta.git" in msgs[0]
    # end to end through the walk-up: the fixture tree with a mangled
    # stamp yields the finding on PERF_BASELINE.json
    index = _perf_tree(tmp_path, baseline_keys={"loop_x"},
                       scenario_ids={"loop_x"})
    doc = json.loads(tmp_path.joinpath("PERF_BASELINE.json").read_text())
    doc["_meta"]["git"] = "unknown"
    tmp_path.joinpath("PERF_BASELINE.json").write_text(json.dumps(doc))
    found = [f for f in check_metrics_drift(index)
             if f.path == "PERF_BASELINE.json"]
    assert len(found) == 1 and "_meta.git" in found[0].message


def test_drift_baseline_meta_dirty_tree_fires():
    """A baseline stamped on a dirty working tree points _meta.git at
    a commit that is NOT the measured code (the PR 11 failure class);
    `tree: "clean"` and absent-key (pre-rule) stamps are clean."""
    from libjitsi_tpu.analysis.checkers.drift import check_baseline_meta

    ok = {"git": "0123abc"}
    assert check_baseline_meta(dict(ok, tree="clean")) == []
    assert check_baseline_meta(ok) == []        # pre-rule baseline
    msgs = check_baseline_meta(dict(ok, tree="dirty"))
    assert len(msgs) == 1 and "_meta.tree" in msgs[0]
    # the git-hash rule still wins when both are wrong
    msgs = check_baseline_meta({"git": "unknown", "tree": "dirty"})
    assert len(msgs) == 1 and "_meta.git" in msgs[0]


def test_drift_syscall_and_reap_counters_in_scope():
    """ISSUE 12's ingest telemetry suffixes (`_syscalls`, `_reaps`)
    are counter-shaped: a class growing an unregistered one next to a
    registered sibling fires; registering both via the reading-lambda
    form is clean."""
    src = """
    class Loop:
        def __init__(self):
            self.ingest_syscalls = 0
            self.ingest_ring_reaps = 0

        def sync(self):
            self.ingest_syscalls += 1
            self.ingest_ring_reaps += 1

        def register_metrics(self, registry):
            registry.register_scalar(
                "loop_ingest_syscalls",
                lambda: self.ingest_syscalls, kind="counter")
    """
    ctx = ctx_of(src)
    found = check_metrics_drift({ctx.relpath: ctx})
    assert len(found) == 1
    assert "ingest_ring_reaps" in found[0].message

    covered = src.replace(
        'kind="counter")',
        'kind="counter")\n'
        '            registry.register_scalar(\n'
        '                "loop_ingest_ring_reaps",\n'
        '                lambda: self.ingest_ring_reaps,'
        ' kind="counter")')
    ctx = ctx_of(covered)
    assert check_metrics_drift({ctx.relpath: ctx}) == []


def test_drift_handshake_plane_counters_in_scope():
    """The reconnect-storm plane's counters (`retransmits_total`,
    `inbox_dropped` — `_total`/`_dropped` suffixes) are counter-shaped:
    a deferred-table class growing an unregistered one next to a
    registered sibling fires; registering both via the reading-lambda
    form is clean."""
    src = """
    class AssocTable:
        def __init__(self):
            self.retransmits_total = 0
            self.inbox_dropped = 0

        def tick(self):
            self.retransmits_total += 1

        def on_dtls(self):
            self.inbox_dropped += 1

        def register_metrics(self, registry):
            registry.register_scalar(
                "dtls_retransmits_total",
                lambda: self.retransmits_total, kind="counter")
    """
    ctx = ctx_of(src)
    found = check_metrics_drift({ctx.relpath: ctx})
    assert len(found) == 1
    assert "inbox_dropped" in found[0].message

    covered = src.replace(
        'kind="counter")',
        'kind="counter")\n'
        '            registry.register_scalar(\n'
        '                "dtls_inbox_dropped",\n'
        '                lambda: self.inbox_dropped,'
        ' kind="counter")')
    ctx = ctx_of(covered)
    assert check_metrics_drift({ctx.relpath: ctx}) == []


def test_drift_real_baseline_meta_is_a_fresh_hash():
    """The checked-in baseline's stamp must be a real hash — the
    --write-baseline path stamps HEAD automatically now."""
    from libjitsi_tpu.analysis.checkers.drift import check_baseline_meta

    with open(os.path.join(REPO, "PERF_BASELINE.json")) as fh:
        meta = json.load(fh).get("_meta", {})
    assert check_baseline_meta(meta) == []


# ------------------------------------------------- pragmas and baseline

def test_line_pragma_suppresses():
    src = """
    def newest(a_seq, b_seq):
        if a_seq < b_seq:  # jitlint: disable=rtp-mod16
            return b_seq
        return a_seq
    """
    assert check_rtp_mod16(ctx_of(src)) == []


def test_def_level_pragma_suppresses_whole_function():
    src = """
    def newest(a_seq, b_seq):  # jitlint: disable=rtp-mod16
        c = a_seq + 1
        if a_seq < b_seq:
            return b_seq
        return a_seq
    """
    assert check_rtp_mod16(ctx_of(src)) == []


def test_file_pragma_suppresses_everything():
    src = """
    # jitlint: disable-file=all

    def newest(a_seq, b_seq):
        return a_seq < b_seq
    """
    assert check_rtp_mod16(ctx_of(src)) == []


def test_pragma_for_other_rule_does_not_suppress():
    src = """
    def newest(a_seq, b_seq):
        if a_seq < b_seq:  # jitlint: disable=secret-taint
            return b_seq
        return a_seq
    """
    assert len(check_rtp_mod16(ctx_of(src))) == 1


def test_baseline_roundtrip(tmp_path):
    bad = tmp_path / "pkg" / "bad_seq.py"
    bad.parent.mkdir()
    bad.write_text(textwrap.dedent("""
        def newest(a_seq, b_seq):
            if a_seq < b_seq:
                return b_seq
            return a_seq
    """))
    bpath = str(tmp_path / "baseline.json")

    r1 = run_lint([str(bad.parent)], baseline_path=bpath)
    assert r1.exit_code == 1 and len(r1.findings) == 1
    baseline_mod.save_baseline(r1.findings, bpath, why="fixture")

    r2 = run_lint([str(bad.parent)], baseline_path=bpath)
    assert r2.exit_code == 0
    assert len(r2.grandfathered) == 1 and r2.findings == []

    # unrelated edits (line drift) keep the baseline key stable
    bad.write_text("x = 1\n\n\n" + bad.read_text())
    r3 = run_lint([str(bad.parent)], baseline_path=bpath)
    assert r3.exit_code == 0

    # fixing the line retires the entry: stale, not matched
    bad.write_text(textwrap.dedent("""
        from libjitsi_tpu.core.rtp_math import is_newer_seq

        def newest(a_seq, b_seq):
            if is_newer_seq(b_seq, a_seq):
                return b_seq
            return a_seq
    """))
    r4 = run_lint([str(bad.parent)], baseline_path=bpath)
    assert r4.exit_code == 0 and len(r4.stale_baseline) == 1


# ------------------------------------------- regression: the fixed TPs

def test_fixed_zrtp_is_lint_clean():
    """Production fix: ZRTP's 16-bit wire seq wraps at the increment
    (AST check only — runs even without the `cryptography` package)."""
    path = os.path.join(PKG, "control", "zrtp.py")
    with open(path) as fh:
        ctx = FileContext(path, "libjitsi_tpu/control/zrtp.py", fh.read())
    assert check_rtp_mod16(ctx) == []


def test_fixed_zrtp_seq_wraps_mod16():
    """Production fix, runtime half: _send at seq 0xFFFF lands on 0."""
    pytest.importorskip("cryptography")
    from libjitsi_tpu.control import zrtp as zrtp_mod

    ep = zrtp_mod.ZrtpEndpoint(ssrc=7)
    ep._seq = 0xFFFF
    pkt = ep._send(b"\x00" * 12)
    assert ep._seq == 0          # wrapped, not 65536
    assert pkt[2:4] == b"\x00\x00"


def test_fixed_header_ext_is_lint_clean_and_lookup_survives_wrap():
    """Production fix: TransportCC's extended counter is `_ext`-named
    and lookup unwraps via rtp_math.seq_delta."""
    from libjitsi_tpu.transform.header_ext import TransportCCEngine

    path = os.path.join(PKG, "transform", "header_ext.py")
    with open(path) as fh:
        ctx = FileContext(
            path, "libjitsi_tpu/transform/header_ext.py", fh.read())
    assert check_rtp_mod16(ctx) == []

    eng = TransportCCEngine(ext_id=5, clock=lambda: 42.0)
    eng.next_seq_ext = 0x10000 + 3       # past one 16-bit wrap
    eng.sent_seq[(0x10000 + 2) % eng.HISTORY] = 0x10000 + 2
    eng.sent_time[(0x10000 + 2) % eng.HISTORY] = 42.0
    assert eng.lookup_send_time((0x10000 + 2) & 0xFFFF) == 42.0
    assert eng.lookup_send_time(500) is None


def test_fixed_receive_pump_counters_registered():
    """Production fix: the scalar pump's counters export through
    MetricsRegistry (drift rule)."""
    import numpy as np

    from libjitsi_tpu.service.pump import ReceivePump, g711_codec
    from libjitsi_tpu.utils.metrics import MetricsRegistry

    class _NullStream:
        def receive(self, datagrams, arrival=None):
            raise NotImplementedError

    pump = ReceivePump(_NullStream(), g711_codec(), plc=False)
    reg = MetricsRegistry()
    pump.register_metrics(reg)
    pump.tick(now=1.0)                       # one underrun
    text = reg.render()
    assert "rx_pump_lost_frames 1" in text
    assert "rx_pump_decoded_frames 0" in text
    assert "rx_pump_decode_errors 0" in text


# ------------------------------------------------------- the real gate

def test_cli_clean_on_real_tree_under_20s():
    """The merged tree lints clean, fast, through the real CLI — the
    exact command scripts/tier1.sh gates on.  The 20 s budget holds
    even for a COLD index (~19 s for 137 files); a warm index runs in
    ~2 s, and the gate line reports which one this was."""
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, os.path.join("scripts", "lint.py"),
         "libjitsi_tpu"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    elapsed = time.perf_counter() - t0
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert elapsed < 20.0, f"lint gate took {elapsed:.1f}s (>20s budget)"
    assert "index cache" in proc.stdout     # hit/miss stats on the gate line


def test_cli_json_contract(tmp_path):
    bad = tmp_path / "pkg" / "f.py"
    bad.parent.mkdir()
    bad.write_text("def f(a_seq, b_seq):\n    return a_seq + 1\n")
    empty_base = tmp_path / "b.json"
    proc = subprocess.run(
        [sys.executable, os.path.join("scripts", "lint.py"), "--json",
         "--baseline", str(empty_base), str(bad.parent)],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1
    data = json.loads(proc.stdout)
    assert data["exit_code"] == 1
    assert data["findings"][0]["rule"] == "rtp-mod16"
    assert data["findings"][0]["path"].endswith("f.py")


def test_cli_internal_error_is_exit_2(tmp_path):
    broken = tmp_path / "pkg" / "broken.py"
    broken.parent.mkdir()
    broken.write_text("def f(:\n")
    proc = subprocess.run(
        [sys.executable, os.path.join("scripts", "lint.py"),
         str(broken.parent)],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 2


def test_checkers_have_seeded_true_positive_coverage():
    """Acceptance guard: each of the four rules has at least one TP
    fixture test in this file (greps itself)."""
    with open(os.path.abspath(__file__)) as fh:
        me = fh.read()
    for rule in ("hotpath", "hotalloc", "secret", "mod16", "drift"):
        assert me.count(f"def test_{rule}") >= 2


# -------------------------------------------------------- hotpath-alloc

def test_hotalloc_copy_and_ascontiguousarray_fire_in_io():
    """Seeded from the zero-copy arena work: buf[:n].copy() per recv
    window was the dominant host cost in the phase ledger."""
    src = """
    import numpy as np

    def recv_window(self, buf, n):
        batch = buf[:n].copy()
        return batch

    def egress(self, data):
        return np.ascontiguousarray(data)
    """
    found = check_hotpath_alloc(
        ctx_of(src, "libjitsi_tpu/io/fake.py"))
    assert len(found) == 2
    assert all(f.rule == "hotpath-alloc" for f in found)
    assert "per" in found[0].message  # says it allocates per tick


def test_hotalloc_pragma_suppresses():
    src = """
    import numpy as np

    def recv_window(self, buf, n):
        batch = buf[:n].copy()  # jitlint: disable=hotpath-alloc
        return batch
    """
    assert check_hotpath_alloc(
        ctx_of(src, "libjitsi_tpu/io/fake.py")) == []


def test_hotalloc_scope_is_io_only():
    """The same allocation outside io/ is not a tick-path concern."""
    src = """
    import numpy as np

    def anywhere(self, buf, n):
        return buf[:n].copy()
    """
    assert check_hotpath_alloc(
        ctx_of(src, "libjitsi_tpu/transform/fake.py")) == []
    assert check_hotpath_alloc(
        ctx_of(src, "libjitsi_tpu/service/fake.py")) == []


def test_hotalloc_cold_functions_do_not_fire():
    """Constructors and teardown allocate by design; dict.copy-style
    non-numpy receivers still fire (conservative) but np.copy via the
    module alias is caught by the function arm, not the method arm."""
    src = """
    import numpy as np

    class Engine:
        def __init__(self):
            self.buf = np.zeros((4, 1504), np.uint8).copy()

        def close(self):
            self.last = self.buf.copy()

        def register_metrics(self, reg):
            snap = self.buf.copy()
            return snap
    """
    assert check_hotpath_alloc(
        ctx_of(src, "libjitsi_tpu/io/fake.py")) == []


def test_hotalloc_module_level_and_views_do_not_fire():
    src = """
    import numpy as np

    _SCRATCH = np.zeros(16, np.uint8).copy()

    def recv_view(self, buf, n):
        return buf[:n]          # a view, not an allocation
    """
    assert check_hotpath_alloc(
        ctx_of(src, "libjitsi_tpu/io/fake.py")) == []


def test_hotalloc_repo_io_modules_are_clean():
    """The shipped host-I/O modules carry no unpragma'd tick-path
    allocations (every deliberate one states its rationale)."""
    for mod in ("udp.py", "loop.py", "tcp.py"):
        path = os.path.join(PKG, "io", mod)
        with open(path) as fh:
            ctx = FileContext(path, f"libjitsi_tpu/io/{mod}", fh.read())
        assert check_hotpath_alloc(ctx) == [], mod


# ------------------------------------------------------ mesh-collective

from libjitsi_tpu.analysis.checkers.meshcollective import (  # noqa: E402
    check_mesh_collectives)

_PLACEMENT_STUB = """
SANCTIONED_COLLECTIVE_SITES = (
    ("libjitsi_tpu/mesh/sharded.py", "sharded_mix_minus"),
)
"""


def _mesh_index(src, relpath="libjitsi_tpu/mesh/sharded.py"):
    return {
        "libjitsi_tpu/mesh/placement.py": ctx_of(
            _PLACEMENT_STUB, "libjitsi_tpu/mesh/placement.py"),
        relpath: ctx_of(src, relpath),
    }


def test_mesh_collective_unsanctioned_psum_fires():
    """Seeded from the PR 10 failure class: a psum creeping back into
    a steady-state mesh tick silently re-couples every chip and voids
    the mesh_agg_pps_ratio extrapolation."""
    src = """
    import jax

    def my_new_mixer(mesh):
        def _mix(pcm):
            return jax.lax.psum(pcm, "streams")
        return _mix
    """
    found = check_mesh_collectives(_mesh_index(src))
    assert rules_of(found) == ["mesh-collective"]
    assert "psum" in found[0].message


def test_mesh_collective_sanctioned_site_clean():
    """The giant-conference escape hatch named in
    SANCTIONED_COLLECTIVE_SITES keeps its psum (nested defs count:
    the collective lives in the shard_map body closure)."""
    src = """
    import jax

    def sharded_mix_minus(mesh):
        def _mix(pcm):
            return jax.lax.psum(pcm, "streams")
        return _mix
    """
    assert check_mesh_collectives(_mesh_index(src)) == []


def test_mesh_collective_bare_names_and_kin_fire():
    src = """
    from jax.lax import all_gather, ppermute

    def fan_in(x):
        y = all_gather(x, "streams")
        return ppermute(y, "streams", [(0, 1)])
    """
    found = check_mesh_collectives(_mesh_index(src))
    assert len(found) == 2
    assert all(f.rule == "mesh-collective" for f in found)


def test_mesh_collective_scope_is_mesh_only():
    """FP guard: collectives outside mesh/ are someone else's policy."""
    src = """
    import jax

    def f(x):
        return jax.lax.psum(x, "d")
    """
    idx = {"libjitsi_tpu/conference/mixer.py":
           ctx_of(src, "libjitsi_tpu/conference/mixer.py")}
    assert check_mesh_collectives(idx) == []


def test_mesh_collective_segment_sum_clean():
    """FP guard: the shard-local segment_sum mixer is the POINT of the
    affinity layout; it must never be confused with a collective."""
    src = """
    import jax

    def shard_local(pcm, conf):
        return jax.ops.segment_sum(pcm, conf, num_segments=8)
    """
    assert check_mesh_collectives(
        _mesh_index(src, "libjitsi_tpu/mesh/local.py")) == []


def test_mesh_collective_placement_itself_never_sanctioned():
    """A collective in placement.py fires even inside a function whose
    name appears in the sanction list — the list sanctions sites in
    OTHER files, and the placement tick regressing is exactly the bug."""
    src = """
    import jax

    SANCTIONED_COLLECTIVE_SITES = (
        ("libjitsi_tpu/mesh/sharded.py", "sharded_mix_minus"),
    )

    def sharded_mix_minus(x):
        return jax.lax.psum(x, "streams")
    """
    idx = {"libjitsi_tpu/mesh/placement.py":
           ctx_of(src, "libjitsi_tpu/mesh/placement.py")}
    found = check_mesh_collectives(idx)
    assert rules_of(found) == ["mesh-collective"]


_HIERARCHY_STUB = """
SANCTIONED_COLLECTIVE_SITES = (
    ("libjitsi_tpu/mesh/sharded.py", "sharded_mix_minus"),
    ("libjitsi_tpu/mesh/hierarchy.py", "broadcast_bus_fanout"),
)
"""


def _hierarchy_index(src):
    rel = "libjitsi_tpu/mesh/hierarchy.py"
    return {
        "libjitsi_tpu/mesh/placement.py": ctx_of(
            _HIERARCHY_STUB, "libjitsi_tpu/mesh/placement.py"),
        rel: ctx_of(src, rel),
    }


def test_mesh_collective_second_psum_in_hierarchy_fires():
    """TP, seeded from the PR 11 temptation: a helper in hierarchy.py
    adding its OWN collective (say, gathering listener levels) breaks
    the one-collective-per-tick contract even though the file already
    hosts a sanctioned psum."""
    src = """
    import jax

    def broadcast_bus_fanout(mesh, n_conf):
        def _total(seg):
            return jax.lax.psum(seg, "streams")
        return _total

    def listener_level_rollup(mesh):
        def _roll(lvl):
            return jax.lax.all_gather(lvl, "streams")
        return _roll
    """
    found = check_mesh_collectives(_hierarchy_index(src))
    assert rules_of(found) == ["mesh-collective"]
    assert "all_gather" in found[0].message


def test_mesh_collective_sanctioned_bus_fanout_clean():
    """FP guard: the registered broadcast fan-out site keeps its one
    psum (nested closure depth included)."""
    src = """
    import jax

    def broadcast_bus_fanout(mesh, n_conf):
        def _total(seg):
            return jax.lax.psum(seg, "streams")
        return _total
    """
    assert check_mesh_collectives(_hierarchy_index(src)) == []


def test_mesh_collective_real_tree_clean():
    """The shipped mesh/ package holds the zero-collective invariant:
    only the sanctioned participant-sharded escape hatches remain."""
    idx = {}
    mesh_dir = os.path.join(PKG, "mesh")
    for fn in sorted(os.listdir(mesh_dir)):
        if fn.endswith(".py"):
            rel = f"libjitsi_tpu/mesh/{fn}"
            with open(os.path.join(mesh_dir, fn)) as fh:
                idx[rel] = FileContext(rel, rel, fh.read())
    assert check_mesh_collectives(idx) == []


# ===================================================== interprocedural
# secret-flow + plane-affinity run over the whole-tree facts index, so
# these fixtures are real on-disk trees linted through run_lint with a
# tmp baseline (which also pins the facts cache into the tmp dir).

def _tree(tmp_path, files):
    """Write {relpath: source} under tmp_path and lint the tree root;
    returns the LintResult."""
    root = None
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
        root = root or rel.split("/")[0]
    return run_lint([str(tmp_path / root)],
                    baseline_path=str(tmp_path / "baseline.json"))


def _flow(findings, rule="secret-flow"):
    return [f for f in findings if f.rule == rule]


def test_secretflow_cross_module_helper_leak(tmp_path):
    """TP: DTLS-exported key material crosses a module boundary through
    a helper's return value and lands in a flight-recorder payload; the
    finding carries the whole source-to-sink path."""
    r = _tree(tmp_path, {
        "pkg/keysrc.py": """
            def fetch_rx_key(ep):
                profile, tk, tsalt, rk, rsalt = ep.srtp_keys()
                return rk
        """,
        "pkg/svc.py": """
            from pkg.keysrc import fetch_rx_key

            class Mgr:
                def install(self, flight, ep):
                    k = fetch_rx_key(ep)
                    flight.record("install", key=k)
        """,
    })
    flows = _flow(r.findings)
    assert len(flows) == 1
    f = flows[0]
    assert f.path == "pkg/svc.py"
    assert "srtp_keys" in f.message
    assert f.trace[0]["path"] == "pkg/keysrc.py"      # source module
    assert f.trace[-1]["path"] == "pkg/svc.py"        # sink module
    assert "flight-payload" in f.trace[-1]["note"]
    # --format=json carries the same path
    d = f.to_dict()
    assert [h["path"] for h in d["trace"]] == \
        ["pkg/keysrc.py", "pkg/svc.py"]


def test_secretflow_sink_side_hop_recorded(tmp_path):
    """TP: key passed INTO a helper that logs it — the trace records
    the call hop into the sink function."""
    r = _tree(tmp_path, {
        "pkg/a.py": """
            from pkg.b import audit

            def go(log, ep):
                key = ep.export_keying_material()
                audit(log, key)
        """,
        "pkg/b.py": """
            def audit(log, material):
                _log = log
                _log.info("audit", material=material)
        """,
    })
    flows = _flow(r.findings)
    assert len(flows) == 1
    notes = [h["note"] for h in flows[0].trace]
    assert any("passed to" in n for n in notes)
    assert flows[0].path == "pkg/b.py"


def test_secretflow_structure_only_access_clean(tmp_path):
    """FP guard: shape/len/dtype reads of key material are structure,
    not secrets."""
    r = _tree(tmp_path, {
        "pkg/svc.py": """
            def install(flight, ep):
                profile, tk, tsalt, rk, rsalt = ep.srtp_keys()
                flight.record("install", n=len(rk), shape=tk.shape,
                              profile=profile)
        """,
    })
    assert _flow(r.findings) == []


def test_secretflow_pragma_scope(tmp_path):
    """A sink-line pragma suppresses exactly that flow."""
    files = {
        "pkg/svc.py": """
            def install(flight, ep):
                k = ep.export_keying_material()
                flight.record("a", key=k)  # jitlint: disable=secret-flow

                flight.record("b", key=k)
        """,
    }
    r = _tree(tmp_path, files)
    flows = _flow(r.findings)
    assert len(flows) == 1 and flows[0].line == 6


def test_secretflow_local_name_not_reseeded(tmp_path):
    """FP guard: a locally-assigned variable that merely SOUNDS secret
    (a conference dict key) follows dataflow, not its name."""
    r = _tree(tmp_path, {
        "pkg/service/lifecycle.py": """
            class Mgr:
                def _conf_key(self, shard, conf):
                    return f"{shard}:{conf}"

                def promote(self, flight, conf):
                    key = self._conf_key(0, conf)
                    flight.record("promoted", conf=key)
        """,
    })
    assert _flow(r.findings) == []


def test_secretflow_declassified_transform_output_clean(tmp_path):
    """FP guard: protect/unprotect outputs are wire data — taint stops
    at the AEAD boundary instead of smearing into unpacked verdicts."""
    r = _tree(tmp_path, {
        "pkg/service/lifecycle.py": """
            def on_media(flight, table, batch):
                data, auth_ok, sid = table.unprotect_rtp(batch)
                flight.record("rx", sid=sid, ok=auth_ok)
        """,
    })
    assert _flow(r.findings) == []


def test_secretflow_cycle_terminates_and_flows(tmp_path):
    """Call-graph property: mutual recursion converges and still
    carries taint through the cycle's return values."""
    r = _tree(tmp_path, {
        "pkg/m.py": """
            def bounce(key, n):
                if n:
                    return rebound(key, n - 1)
                return key

            def rebound(key, n):
                return bounce(key, n)

            def go(flight, ep):
                k = bounce(ep.export_keying_material(), 3)
                flight.record("x", k=k)
        """,
    })
    assert len(_flow(r.findings)) == 1


def test_secretflow_ambiguous_dispatch_no_summary(tmp_path):
    """Call-graph property: a method name defined by several classes
    does not resolve — no summary flows, no phantom finding."""
    r = _tree(tmp_path, {
        "pkg/m.py": """
            class Dtls:
                def grab(self, ep):
                    return ep.export_keying_material()

            class Stats:
                def grab(self, ep):
                    return 42

            def go(flight, obj, ep):
                v = obj.grab(ep)
                flight.record("x", v=v)
        """,
    })
    assert _flow(r.findings) == []


def test_planeaffinity_tick_reachable_handshake_fires(tmp_path):
    """TP: the tick root reaching `ep.feed(...)`-driving control code
    is the static twin of handshake_tick_thread_feeds == 0."""
    r = _tree(tmp_path, {
        "libjitsi_tpu/io/loop.py": """
            class MediaLoop:
                def tick(self):
                    self.assoc.ingest(b"x", ("h", 1))
        """,
        "libjitsi_tpu/control/dtls.py": """
            class AssocTable:
                def ingest(self, dgram, addr):
                    ep = self.pending[addr]
                    return ep.feed(dgram)
        """,
    })
    flags = _flow(r.findings, "plane-affinity")
    assert len(flags) == 1
    assert "handshake" in flags[0].message
    assert flags[0].trace[0]["note"] == "plane root"
    assert flags[0].trace[0]["symbol"] == "MediaLoop.tick"


def test_planeaffinity_dual_annotation_cuts(tmp_path):
    """The reviewable escape hatch: plane=dual cuts traversal at the
    documented legacy boundary without flagging."""
    r = _tree(tmp_path, {
        "libjitsi_tpu/io/loop.py": """
            class MediaLoop:
                def tick(self):
                    self.assoc.ingest(b"x", ("h", 1))
        """,
        "libjitsi_tpu/control/dtls.py": """
            class AssocTable:
                # jitlint: plane=dual
                def ingest(self, dgram, addr):
                    ep = self.pending[addr]
                    return ep.feed(dgram)
        """,
    })
    assert _flow(r.findings, "plane-affinity") == []


def test_planeaffinity_barrier_mediated_install_clean(tmp_path):
    """FP guard + TP pair: an install inside the commit barrier is the
    sanctioned surface; the same install reached around the barrier
    fires."""
    r = _tree(tmp_path, {
        "libjitsi_tpu/service/lifecycle.py": """
            class StreamLifecycleManager:
                def poll(self):
                    self.commit_endpoints()
                    self._sneak_install()

                def commit_endpoints(self):
                    self.rx_table.add_stream(1, b"k", b"s")

                def _sneak_install(self):
                    self.rx_table.add_stream(2, b"k", b"s")
        """,
    })
    flags = _flow(r.findings, "plane-affinity")
    assert len(flags) == 1
    assert flags[0].symbol.endswith("_sneak_install")
    assert "staged commit barrier" in flags[0].message


def test_index_cache_roundtrip_and_stale_invalidation(tmp_path):
    """Second run over an unchanged tree is all cache hits with
    identical findings; editing one file re-checks exactly that file."""
    files = {
        "pkg/svc.py": """
            def install(flight, ep):
                k = ep.export_keying_material()
                flight.record("x", key=k)
        """,
        "pkg/other.py": """
            def helper():
                return 1
        """,
    }
    r1 = _tree(tmp_path, files)
    assert r1.cache_misses == 2 and r1.cache_hits == 0
    assert len(_flow(r1.findings)) == 1

    r2 = _tree(tmp_path, files)
    assert r2.cache_hits == 2 and r2.cache_misses == 0
    assert len(_flow(r2.findings)) == 1
    assert r2.findings[0].content_key == r1.findings[0].content_key

    # content edit invalidates exactly the edited file
    files["pkg/other.py"] = "def helper():\n    return 2\n"
    r3 = _tree(tmp_path, files)
    assert r3.cache_hits == 1 and r3.cache_misses == 1

    # a cache written by a different analysis version is discarded
    from libjitsi_tpu.analysis import index as index_mod
    cpath = tmp_path / ".jitlint_index.json"
    doc = json.loads(cpath.read_text())
    doc["version"] = "stale"
    cpath.write_text(json.dumps(doc))
    assert index_mod.load_cache(str(cpath)) == {}
    r4 = _tree(tmp_path, files)
    assert r4.cache_misses == 2


def test_changed_mode_trusts_unchanged_files(tmp_path, monkeypatch):
    """--changed: git names the changed set; everything outside its
    reverse-dependency closure is served from the cache untouched."""
    if subprocess.run(["git", "--version"], capture_output=True).returncode:
        pytest.skip("git unavailable")
    files = {
        "pkg/__init__.py": "",
        "pkg/base.py": """
            def helper():
                return 1
        """,
        "pkg/user.py": """
            from pkg.base import helper

            def go():
                return helper()
        """,
    }
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    monkeypatch.chdir(tmp_path)
    for cmd in (["git", "init", "-q"],
                ["git", "add", "."],
                ["git", "-c", "user.email=t@t", "-c", "user.name=t",
                 "commit", "-qm", "seed"]):
        subprocess.run(cmd, check=True, capture_output=True)

    bpath = str(tmp_path / "baseline.json")
    r1 = run_lint([str(tmp_path / "pkg")], baseline_path=bpath)
    assert r1.cache_misses == 3

    # no changes: --changed trusts the whole tree from the cache
    r2 = run_lint([str(tmp_path / "pkg")], baseline_path=bpath,
                  changed_only=True)
    assert r2.cache_hits == 3 and r2.cache_misses == 0

    # editing base.py: it and its importer (user.py) leave the trusted
    # set — base.py re-parses (miss), user.py is re-read but its sha
    # still matches (hit), __init__ is trusted without a read
    (tmp_path / "pkg/base.py").write_text(
        "def helper():\n    return 2\n")
    r3 = run_lint([str(tmp_path / "pkg")], baseline_path=bpath,
                  changed_only=True)
    assert r3.cache_misses == 1 and r3.cache_hits == 2


def test_baseline_justification_required(tmp_path):
    """Drift guard: a baseline entry with no `why` is itself a
    finding."""
    files = {
        "pkg/clean.py": """
            def ok():
                return 1
        """,
    }
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    bpath = tmp_path / "baseline.json"
    bpath.write_text(json.dumps({"entries": [
        {"key": "secret-taint:pkg/x.py:f:abc:0", "why": ""},
    ]}))
    r = run_lint([str(tmp_path / "pkg")], baseline_path=str(bpath))
    msgs = [f.message for f in r.findings if f.rule == "drift"]
    assert any("justification" in m or "why" in m for m in msgs)


def test_fixed_process_one_is_plane_dual():
    """Production fix: the legacy inline-DTLS path is a declared
    plane=dual boundary — tick-reachable handshake work is otherwise a
    finding (static twin of handshake_tick_thread_feeds == 0)."""
    path = os.path.join(PKG, "control", "dtls.py")
    with open(path) as fh:
        ctx = FileContext(path, "libjitsi_tpu/control/dtls.py",
                          fh.read())
    from libjitsi_tpu.analysis.callgraph import extract_defs
    functions, _ = extract_defs(ctx)
    fn = functions["DtlsAssociationTable._process_one"]
    assert fn["plane"] == "dual"
