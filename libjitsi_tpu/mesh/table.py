"""ShardedSrtpTable — the production SRTP table running on a device mesh.

Round 3 sharded raw *kernels* (mesh/sharded.py) but every
product object stayed single-chip.  This table is the product object
sharded: the same `SrtpStreamTable` host control plane (header parse,
RFC 3711 App A index estimation, replay windows, kdr epochs, size-class
bucketing — all of context.py, unchanged) with the DEVICE side row-
partitioned over a `jax.sharding.Mesh`:

- key tables `[S, R, 16]` / `[S, 2, 5]` / `[S, 128, 128]` live sharded
  on the row axis — device d owns rows [d*S/n, (d+1)*S/n); nothing is
  replicated;
- each batch is grouped by owning device on the host (the control plane
  already knows every packet's row), padded per device to the row class
  (`core/packet.py:ROW_CLASSES`) the hottest device's rows need, and
  the crypto runs under `shard_map` with ZERO collectives: a packet's
  key material is chip-local by construction — stream-data-parallelism
  exactly as SURVEY §2.7 prescribes.  The plan is handed the REAL
  rows (`_pads_rows` False: the host plane above pads nothing), so a
  mesh launch is padded once and its lanes come from the five classes
  the lifecycle ladder warms;
- results stay DEVICE-RESIDENT in lane layout until materialized: the
  scatter back to wire order is deferred (`_LazyArray`), so
  `protect_rtp_async` keeps its launch-overlap contract in mesh mode
  and the bridges compose with a mesh as they are: the mixer's
  `pipelined=True`, and the SFU's one tick shape, which dispatches a
  tick's fan-out and collects it in the next.

Reference: `SRTPTransformer`'s per-SSRC context map scaled by running
more JVMs; here the ONE table spans the mesh and `RTPTranslatorImpl`-
scale fan-outs (SURVEY §3.4) ride the same row partition.

Profile scope: ALL four cipher modes shard.  AES-CM /
NULL ride the two-table seam; AES-F8's second key schedule is one more
`[S, R, 16]` tensor on the same row partition; AES-GCM shards both its
per-row form AND the grouped-GHASH form (per-device group grids —
picked per shape by `kernels.registry` measurement; the single-chip
table picks by a rule of the shape, `context._gcm_form_grid`, and this
race is the four-chip cell's to settle).  SRTCP runs sharded on the RTCP key tables —
control traffic must not silently hop to a single-chip path.
"""

from __future__ import annotations

import functools
import weakref
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from libjitsi_tpu.mesh.compat import shard_map

from libjitsi_tpu.core import staging
from libjitsi_tpu.core.packet import (CLASS_HEADROOM, LENGTH_CLASSES,
                                      ROW_CLASSES, _round_rows)
from libjitsi_tpu.kernels import registry as _registry
from libjitsi_tpu.transform.srtp import kernel
from libjitsi_tpu.transform.srtp.context import (SrtpStreamTable,
                                                 _split_unprotect,
                                                 _uniform_off)
from libjitsi_tpu.transform.srtp.policy import Cipher, SrtpProfile
from libjitsi_tpu.utils.tracing import span_of

#: the shard_map programs of a mesh, shared by every table and
#: translator on it: rx table, tx table and the warm ladder's scratch
#: tables launch the same programs, so a scratch table's warm-up warms
#: the live table's (a jit object a table would each compile its own)
_MESH_PROGRAMS: Dict[Mesh, Dict[Tuple, "jax.stages.Wrapped"]] = {}


def _named(fn, name: str):
    """`fn` under the name its program gets in the trace, the compile
    log and the lowered module (`jit_<name>`)."""
    fn.__name__ = fn.__qualname__ = name
    return fn


class _LazyArray:
    """Deferred scatter-to-wire-order of one sharded-launch output.

    Holds the device array in `[n_dev, per(, W)]` lane layout plus the
    plan's inverse map; the D2H transfer and host scatter happen on
    first materialization (`np.asarray`, `block_until_ready`, or
    `astype` of an already-materialized value).  This deferral is what
    lets `protect_rtp_async`/`translate_async` overlap launches in mesh
    mode: `PendingProtect`/`PendingTranslate` hold these until
    `.result()` while the next batch's plan/dispatch proceeds.
    """

    __slots__ = ("_dev", "_inv", "_dtype", "_np")

    def __init__(self, dev, inv, dtype=None):
        self._dev, self._inv, self._dtype = dev, inv, dtype
        self._np = None

    def _materialize(self) -> np.ndarray:
        if self._np is None:
            a = np.asarray(self._dev)
            a = a.reshape(-1, *a.shape[2:]) if a.ndim > 1 else a
            if self._inv is not None:   # None: affine plan, wire order
                a = a[self._inv]
            if self._dtype is not None:
                a = a.astype(self._dtype)
            self._np = a
            self._dev = None
        return self._np

    def astype(self, dtype):
        if self._np is not None:
            return self._np.astype(dtype)
        return _LazyArray(self._dev, self._inv, dtype)

    def block_until_ready(self):
        self._materialize()
        return self

    def __array__(self, dtype=None, copy=None):
        a = self._materialize()
        if dtype is not None and a.dtype != np.dtype(dtype):
            a = a.astype(dtype)
        return a


class ShardedRowsMixin:
    """Shared sharding scaffolding for row-partitioned product objects
    (the SRTP table and the fan-out translator must keep identical
    geometry or same-mesh deployments desync): partition sizes, the
    `_dev`-invalidation mirror, and the sharded device cache (one entry
    per named table group — "rtp", "rtcp")."""

    #: the host plane above the seams pads no rows (`bucket_by_size`,
    #: `_rtcp_row_pad`, `_cycle_rows`): `_OwnerPlan` pads the lanes a
    #: chip to the row class of the hottest chip, once
    _pads_rows = False

    def _init_sharding(self, mesh: Mesh, capacity: int) -> None:
        n_dev = int(mesh.devices.size)
        if capacity % n_dev:
            raise ValueError(f"capacity {capacity} not divisible by "
                             f"{n_dev} mesh devices")
        self.mesh = mesh
        # rows map over EVERY mesh axis: a 1-D "streams" mesh and the
        # 2-D (dcn, streams) multi-host mesh both flatten onto the row
        # partition (device order = row-major over the axes)
        self._axes = tuple(mesh.axis_names)
        self.n_dev = n_dev
        self.rows_per = capacity // n_dev
        self._sh_dev: Dict[str, Tuple] = {}
        self._sh_fns = _MESH_PROGRAMS.setdefault(mesh, {})
        # times the key tables were placed on the mesh (a re-keying
        # drops the copies; the next launch places them again)
        self.placements = 0
        # rows of the last call each shard owned (`mesh_rows_per_shard`)
        self.shard_rows = np.zeros(n_dev, dtype=np.int64)

    # the parent classes use `self._dev = None` as their invalidation
    # signal (every key mutator sets it); mirror that onto the sharded
    # copies so they re-place on the next launch after any re-keying
    @property
    def _dev(self):
        return self.__dev

    @_dev.setter
    def _dev(self, value):
        self.__dev = value
        if value is None:
            self._sh_dev = {}

    def _sharded_tables(self, group: str):
        """Subclass hook: the numpy master tensors to place for a named
        group ("rtp"/"rtcp"), all `[S, ...]` row-major."""
        raise NotImplementedError

    def _sharded_device(self, group: str = "rtp") -> Tuple:
        got = self._sh_dev.get(group)
        if got is None:
            spec = NamedSharding(self.mesh, P(self._axes, None, None))
            got = tuple(jax.device_put(t, spec)
                        for t in self._sharded_tables(group))
            self._sh_dev[group] = got
            self.placements += 1
            if hasattr(self, "_aliased"):
                # the table's COW discipline repoints masters before
                # in-place mutation when this is set
                self._aliased = True
        return got

    def _sharded_call(self, fn, tabs, ids, lane_args, extra_args=(),
                      plan=None):
        """Plan/gather/dispatch shared by every sharded seam that
        keeps an array an argument (table CM protect/F8/GCM/SRTCP, the
        translator's GCM fan-outs; the served CM unprotect and fan-out
        pack one plane, `_packed_call`): route rows to their
        owning chips (`owner_plan`: the plan and the lane gathers),
        put each lane array on the mesh a block a chip, and run `fn`
        under shard_map.  `lane_args` are per-row arrays (1-D like
        length/off/roc or N-D like data/iv) routed through the plan;
        `extra_args` are already device-wide arrays passed through
        as-is (grouped-GCM grids, fan-out packet blocks).  Callers that
        pre-built the plan (to derive grids from it) pass it via
        `plan`.  Returns (outputs in lane layout `[n_dev, per(, W)]`,
        still on the device; the plan; arrays that crossed; their
        bytes)."""
        ids = np.asarray(ids, dtype=np.int64)
        with span_of(getattr(self, "tracer", None), "owner_plan",
                     rows=len(ids), shards=self.n_dev):
            if plan is None:
                plan = _OwnerPlan(ids, self.capacity, self.rows_per,
                                  self.n_dev)
            lanes = [local_rows(plan, ids, self.capacity, self.rows_per,
                                self.n_dev)]
            for a in lane_args:
                a = np.asarray(a)
                # identity routing: the lane gather is a reshape
                lanes.append(a.reshape(self.n_dev, plan.per, *a.shape[1:])
                             if plan.affine else a[plan.slot])
        self.shard_rows = plan.counts
        dev, n, nbytes = self._put_lanes(lanes)
        outs = fn(*tabs, *dev, *(jnp.asarray(e) for e in extra_args))
        return outs, plan, n, nbytes

    def _put_lanes(self, lanes):
        """`staging.put_each` of lane arrays `[n_dev, per, ...]`: each
        crosses once, a block to the chip that owns it, in the dtype
        the program takes (64-bit words are cut to 32 on the host, as
        JAX would on the device).  Returns (device arrays, how many
        crossed, their bytes)."""
        host = []
        for a in lanes:
            if a.dtype.itemsize == 8:
                a = a.astype(np.uint32 if a.dtype.kind == "u"
                             else np.int32)
            host.append(a)
        return staging.put_each(host, lambda a: NamedSharding(
            self.mesh, P(self._axes, *([None] * (a.ndim - 1)))))

    def _sharded_launch(self, fn, tabs, ids, lane_args, extra_args=(),
                        plan=None):
        """`_sharded_call` with one `_LazyArray` per output — the
        scatter back to wire order is DEFERRED until materialization,
        keeping the async contract (the protect, F8, GCM and SRTCP
        seams; the served CM unprotect and fan-out hand one packed
        plane to a `staging.Launch`, `_packed_call`)."""
        outs, plan, _n, _nbytes = self._sharded_call(
            fn, tabs, ids, lane_args, extra_args, plan)
        inv = None if plan.affine else plan.inv   # affine: wire order
        return tuple(_LazyArray(o, inv) for o in outs)

    def _mesh_launch(self, outs, plan: "_OwnerPlan", h2d_arrays: int,
                     h2d_bytes: int, dtypes) -> staging.Launch:
        """A `staging.Launch` over a sharded call's outputs: `fetch()`
        copies each lane-layout output back once (counted as it
        crosses) and scatters it to wire order (`mesh_scatter`), cast to
        `dtypes` (None: as it came).  Its counts are the plan's:
        `shards`, `lanes` a chip, `rows_hottest_shard`, `affine`."""
        n = len(plan.inv)
        inv = None if plan.affine else plan.inv
        tracer = getattr(self, "tracer", None)

        def scatter(*host):
            with span_of(tracer, "mesh_scatter", rows=n):
                out = []
                for a, dt in zip(host, dtypes):
                    a = a.reshape(-1, *a.shape[2:])
                    if inv is not None:   # None: affine plan, wire order
                        a = a[inv]
                    out.append(a if dt is None else a.astype(dt))
                return tuple(out)

        counts = self._plan_counts(plan)
        return staging.Launch(outs, scatter, h2d_arrays=h2d_arrays,
                              h2d_bytes=h2d_bytes, counts=counts,
                              d2h_counts=counts)

    def _plan_counts(self, plan: "_OwnerPlan") -> dict:
        """What a mesh call's spans book of its plan."""
        return {"shards": self.n_dev, "lanes": plan.per,
                "rows_hottest_shard": int(plan.counts.max()),
                "affine": int(plan.affine)}

    def _packed_call(self, fn, plane: np.ndarray, ids, words, iv,
                     split) -> staging.Launch:
        """The served CM seams (RTP unprotect, fan-out): ONE packed
        `uint8` lane plane to the mesh and one back (core/staging.py),
        a block a chip each way.

        `plane` is the seam's staging plane in wire order, the packet
        bytes in its leading columns.  Word 0 is the CHIP-LOCAL key row
        (`ids` clipped into the table, modulo the rows a chip), `words`
        are the seam's other three (length, payload offset, ROC).  The
        plan gathers the packed plane into lanes (`owner_plan`; a
        reshape when affine; a pad lane repeats a real row, whose local
        word is in range wherever it lands), one `staging.put` puts it
        on the mesh (`<seam>_put`, a block a chip), and `fn`
        (`_packed_fn`) gives one plane of the same shape back.
        `fetch()` copies it once, takes it to wire order
        (`mesh_scatter`) and ends in `split`, the one-chip seam's."""
        ids = np.asarray(ids, dtype=np.int64)
        n = len(ids)
        tracer = getattr(self, "tracer", None)
        local = np.clip(ids, 0, self.capacity - 1) % self.rows_per
        staging.pack(plane, (local, *words), iv)
        with span_of(tracer, "owner_plan", rows=n, shards=self.n_dev):
            plan = _OwnerPlan(ids, self.capacity, self.rows_per,
                              self.n_dev)
            # identity routing: the lane gather is a reshape
            lanes = (plane.reshape(self.n_dev, plan.per, plane.shape[-1])
                     if plan.affine else plane[plan.slot])
        self.shard_rows = plan.counts
        out = fn(*self._sharded_device("rtp"), staging.put(
            lanes, NamedSharding(self.mesh, P(self._axes, None, None))))
        inv = None if plan.affine else plan.inv

        def scatter(host):
            with span_of(tracer, "mesh_scatter", rows=n):
                host = host.reshape(-1, host.shape[-1])
                if inv is not None:   # None: affine plan, wire order
                    host = host[inv]
            return split(host)

        counts = self._plan_counts(plan)
        return staging.Launch((out,), scatter, h2d_arrays=1,
                              h2d_bytes=lanes.nbytes, counts=counts,
                              d2h_counts=counts)

    def _packed_fn(self, key: Tuple, name: str, kfn):
        """The shard_map program of a packed seam, shared a mesh under
        `key` and named `name` in the trace: the one-chip packed
        program's body (`context._unprotect_rtp_packed_impl`,
        `sfu.translator._fanout_protect`) over this chip's lane block
        and this chip's shard of the key tables.  `kfn` is
        `kernel.srtp_unprotect` or `kernel.srtp_protect_rows` with its
        static arguments bound; whatever it returns beside the bytes
        rides back as the plane's words."""
        fn = self._sh_fns.get(key)
        if fn is not None:
            return fn

        def _run(tab_rk, tab_mid, plane):
            # per-shard leading axis is 1 (this chip's lane block)
            data, w, iv = staging.unpack(plane[0])
            rk, mid = kernel.gather_keys(staging.as_i32(w[:, 0]), tab_rk,
                                         tab_mid)
            out = kfn(data, staging.as_i32(w[:, 1]),
                      staging.as_i32(w[:, 2]), rk, iv, mid, w[:, 3])
            return staging.repack(*out)[None]

        row3 = P(self._axes, None, None)
        fn = jax.jit(shard_map(
            _named(_run, name), mesh=self.mesh,
            in_specs=(row3, row3, row3), out_specs=row3,
            check_vma=False))
        # setdefault: concurrent warm-ups of one key, and every table
        # and translator of the mesh, share ONE jit (`_MESH_PROGRAMS`)
        return self._sh_fns.setdefault(key, fn)


def local_rows(plan: "_OwnerPlan", ids: np.ndarray, capacity: int,
               rows_per: int, n_dev: int) -> np.ndarray:
    """Per-lane chip-local row indices for a planned batch: global row
    id minus the owning chip's base offset (lanes holding another
    chip's pad row clamp into range and produce garbage the scatter
    drops).  ONE implementation for every sharded consumer — the table
    and the fan-out translator must agree with _OwnerPlan's layout."""
    s = np.clip(np.asarray(ids, dtype=np.int64), 0, capacity - 1)[
        plan.slot]
    base = (np.arange(n_dev, dtype=np.int64) * rows_per)[:, None]
    return np.clip(s - base, 0, rows_per - 1).astype(np.int32)


class _OwnerPlan:
    """Host-side routing of one batch onto the row partition: `slot`
    [n_dev, per] gathers batch rows into per-device lanes (pads repeat a
    real row — crypto on device is stateless, pads are dropped at
    scatter); `inv` [B] maps each original row to its flat lane;
    `counts` [n_dev] are the rows each device owns.

    `per`, the lanes a chip, is the ROW CLASS (`core/packet.py`
    `ROW_CLASSES`, multiples of the largest beyond it) that the
    hottest device's rows need: the one rule of the mesh's shape space,
    so a mesh launch takes the five lane counts the lifecycle ladder
    warms whatever the tick's skew, and, handed the real rows, is
    padded once.
    Fully vectorized — no Python loop over devices (the loop showed
    at 64k-batch x 8-device shapes)."""

    __slots__ = ("slot", "inv", "per", "affine", "counts")

    def __init__(self, stream: np.ndarray, capacity: int, rows_per: int,
                 n_dev: int):
        s = np.clip(stream, 0, capacity - 1)
        n = len(s)
        owner = s // rows_per
        # Affine fast path (conference-affinity placement's steady
        # state, mesh/placement.py): the batch already arrives
        # shard-major with equal per-shard counts that fill a row
        # class — rows are drawn from contiguous per-shard ranges, so no
        # argsort, no scattered writes and no pad lane.  Identity
        # routing: slot is a reshape, inv is arange.
        cnt = n // n_dev if n_dev else 0
        self.affine = bool(
            n and n == cnt * n_dev and cnt == _round_rows(cnt)
            and np.array_equal(owner,
                               np.repeat(np.arange(n_dev), cnt)))
        if self.affine:
            self.per = cnt
            self.counts = np.full(n_dev, cnt, dtype=np.int64)
            self.slot = np.arange(n, dtype=np.int64).reshape(n_dev, cnt)
            self.inv = np.arange(n, dtype=np.int64)
            return
        order = np.argsort(owner, kind="stable")
        self.counts = counts = np.bincount(owner, minlength=n_dev)
        self.per = per = _round_rows(int(counts.max()) if n else 1)
        starts = np.concatenate(([0], np.cumsum(counts)))
        dev_sorted = owner[order]
        lane = np.arange(n, dtype=np.int64) - starts[dev_sorted]
        # pads repeat each device's FIRST routed row; devices with no
        # rows fall back to the batch's first row overall
        first = np.full(n_dev, order[0] if n else 0, dtype=np.int64)
        has = counts > 0
        first[has] = order[starts[:-1][has]]
        self.slot = np.broadcast_to(first[:, None], (n_dev, per)).copy()
        self.slot[dev_sorted, lane] = order
        self.inv = np.empty(n, dtype=np.int64)
        self.inv[order] = dev_sorted * per + lane


def mesh_gcm_grid(local: np.ndarray):
    """Per-device grouped-GHASH grids over an `_OwnerPlan`'s lane
    layout — the mesh form of `context._gcm_grid` (the
    sharded table must not be pinned to the per-row form the round-4
    data showed losing 2.3x at 64k rows).

    `local` [n_dev, per] are chip-local key rows per lane.  Returns
    (grid [n_dev, Gp, Pp] int32 lane-index-or-minus-one, us [n_dev, Gp]
    int32 local stream rows, inv [n_dev, per] int32) with Gp/Pp shared
    pow2 shapes across devices, or None when structurally unusable
    (tiny lanes, all-distinct streams, or skew so heavy the padded grid
    would more than double the GHASH work — same guards as the
    single-chip grid).
    """
    n_dev, per = local.shape
    if per < 8:
        return None
    order2 = np.argsort(local, axis=1, kind="stable")
    ss = np.take_along_axis(local, order2, 1)
    firsts = np.ones_like(ss, dtype=bool)
    firsts[:, 1:] = ss[:, 1:] != ss[:, :-1]
    grp = np.cumsum(firsts, axis=1) - 1
    g = int(grp[:, -1].max()) + 1
    if g == per:      # every lane its own stream: grouped ≡ per-row
        return None
    pos = np.arange(per, dtype=np.int64)[None, :]
    fpos = np.maximum.accumulate(np.where(firsts, pos, 0), axis=1)
    rank = pos - fpos
    p = int(rank.max()) + 1
    gp = 1 << max(g - 1, 0).bit_length()
    pp = 1 << max(p - 1, 0).bit_length()
    if gp * pp > 2 * per:
        return None
    d_idx = np.repeat(np.arange(n_dev), per)
    grid = np.full((n_dev, gp, pp), -1, dtype=np.int32)
    grid[d_idx, grp.ravel(), rank.ravel()] = \
        order2.ravel().astype(np.int32)
    us = np.zeros((n_dev, gp), dtype=np.int32)
    us[d_idx, grp.ravel()] = ss.ravel().astype(np.int32)
    inv = np.empty((n_dev, per), dtype=np.int32)
    np.put_along_axis(inv, order2, (grp * pp + rank).astype(np.int32), 1)
    return grid, us, inv


class _MeshSeamToken:
    """Registry handle for a mesh table's GCM seam.

    The module-global `kernels.registry` keys its measured choices by
    argument signature; passing the TABLE itself would retain every
    table (and its ~16 MiB GHASH masters) in the registry's choice
    dict forever and force a re-benchmark per instance.  This token
    hashes by GEOMETRY (capacity, mesh size, profile) — tables with
    identical geometry share one measured choice (their shard programs
    are identical), and the weakref lets dead tables be collected.
    """

    __slots__ = ("geom", "ref")

    def __init__(self, table: "ShardedSrtpTable"):
        self.geom = (table.capacity, table.n_dev, table.profile.value)
        self.ref = weakref.ref(table)

    def __hash__(self):
        return hash(self.geom)

    def __eq__(self, other):
        return (isinstance(other, _MeshSeamToken)
                and self.geom == other.geom)


# Measured grouped-vs-per-row choice for the MESH table (the
# single-chip table left the registry: `context._gcm_form_grid`): both
# providers take the full argument list; per_row ignores the grid
# machinery.  The seam
# token rides in the signature, so choices are per (geometry, batch
# shape) — measured once per deployment geometry, shared by same-shape
# tables (warmup's scratch table pins the live table's choice).

def _mesh_gcm_protect_grouped(token, stream, data, length, off, iv12,
                              off_const):
    return token.ref()._gcm_mesh_launch("gcm_protect_grouped", stream,
                                        data, length, off, iv12,
                                        off_const)


def _mesh_gcm_protect_per_row(token, stream, data, length, off, iv12,
                              off_const):
    return token.ref()._gcm_mesh_launch("gcm_protect", stream, data,
                                        length, off, iv12, off_const)


def _mesh_gcm_unprotect_grouped(token, stream, data, length, off, iv12,
                                off_const):
    return token.ref()._gcm_mesh_launch("gcm_unprotect_grouped", stream,
                                        data, length, off, iv12,
                                        off_const)


def _mesh_gcm_unprotect_per_row(token, stream, data, length, off, iv12,
                                off_const):
    return token.ref()._gcm_mesh_launch("gcm_unprotect", stream, data,
                                        length, off, iv12, off_const)


_registry.register("mesh_gcm_rtp_protect", "grouped",
                   _mesh_gcm_protect_grouped)
_registry.register("mesh_gcm_rtp_protect", "per_row",
                   _mesh_gcm_protect_per_row)
_registry.register("mesh_gcm_rtp_unprotect", "grouped",
                   _mesh_gcm_unprotect_grouped)
_registry.register("mesh_gcm_rtp_unprotect", "per_row",
                   _mesh_gcm_unprotect_per_row)


class ShardedSrtpTable(ShardedRowsMixin, SrtpStreamTable):
    """`SrtpStreamTable` whose RTP *and* RTCP crypto runs sharded."""

    def __init__(self, capacity: int, mesh: Mesh,
                 profile: SrtpProfile =
                 SrtpProfile.AES_CM_128_HMAC_SHA1_80):
        self._init_sharding(mesh, capacity)
        super().__init__(capacity, profile)

    def _sharded_tables(self, group: str):
        if group == "rtp":
            t = [self._rk_rtp,
                 self._gm_rtp if self._gcm else self._mid_rtp]
            if self._f8:
                t.append(self._rk_f8_rtp)
        else:
            t = [self._rk_rtcp,
                 self._gm_rtcp if self._gcm else self._mid_rtcp]
            if self._f8:
                t.append(self._rk_f8_rtcp)
        return tuple(t)

    @classmethod
    def restore(cls, snap: dict, mesh: Mesh) -> "ShardedSrtpTable":
        """Resume a snapshot as a MESH table (a checkpointed mesh
        deployment must come back sharded, not silently single-chip)."""
        from libjitsi_tpu.transform.srtp.policy import SrtpProfile

        t = cls(len(snap["active"]), mesh,
                SrtpProfile(snap["profile"]))
        t._load_state(snap)
        return t

    def _scratch(self) -> "ShardedSrtpTable":
        """The warm ladder's throwaway table: sharded over this table's
        mesh, so `warmup_rtp` / `warmup_rtcp` (the base class's, run as
        they are) compile the shard_map programs this table launches,
        at the lanes the owner plan gives their batches."""
        return ShardedSrtpTable(self.capacity, self.mesh, self.profile)

    def warmup(self, max_batch: int, payload_lens=(160, 480)) -> None:
        """Pre-compile the shard_map programs so live ticks never absorb
        an XLA compile (the same discipline as AudioMixer's setup-time
        warmup), for a deployment that runs no lifecycle ladder: the
        ladder's own steps (`warmup_rtp`, `warmup_rtcp`) for every row
        class up to the one holding `max_batch` (worst-case skew parks
        a whole batch on one chip, and the plan's lanes are row
        classes), per bucketing width class (`payload_lens`: one packet
        size inside each of `bucket_by_size`'s first two; batches in
        the terminal full-width class, like rare offsets, still compile
        lazily), and for GCM the registry's grouped/per-row measurement
        (advisor r5: the measurement compiles both providers and times
        12 launches — that must happen here, ON THIS table, not on the
        first live batch).  Called by ConferenceBridge.warmup();
        standalone deployments call it before going live."""
        top = _round_rows(max(1, max_batch))
        for rc in ROW_CLASSES:
            if rc > top:
                break
            for plen in payload_lens:
                self.warmup_rtp(rc, payload_len=plen)
            self.warmup_rtcp(rc)
        if self._gcm:
            self._warmup_gcm_registry(
                max_batch, tuple(c + CLASS_HEADROOM
                                 for c in LENGTH_CLASSES[:2]))

    def _warmup_gcm_registry(self, max_batch: int, capacities) -> None:
        """Drive THIS table's GCM registry seams with synthetic args so
        the grouped/per-row compiles and the 12-launch measurement
        happen off the media path and land in THIS table's program
        cache (a scratch table would pin the registry choice via the
        geometry token but leave the live table's jit closures cold —
        advisor r5).  Pure dispatch: these seams touch no host crypto
        state (replay/tx planes live in the callers above them)."""
        rng = np.random.default_rng(0)
        n = max(1, min(self.capacity, 64))
        for cap in capacities:
            for bsz in ROW_CLASSES:
                if bsz > max(ROW_CLASSES[0], max_batch):
                    break
                # heavy stream reuse: the grouped grid must be
                # structurally usable or the measurement would only
                # ever exercise the per-row provider
                stream = np.sort(
                    np.resize(np.arange(n, dtype=np.int64), bsz))
                data = rng.integers(0, 256, (bsz, cap), dtype=np.uint8)
                length = np.full(bsz, 172, np.int32)
                off = np.full(bsz, 12, np.int32)
                iv12 = rng.integers(0, 256, (bsz, 12), dtype=np.uint8)
                for op in ("mesh_gcm_rtp_protect",
                           "mesh_gcm_rtp_unprotect"):
                    outs = _registry.call(op, self._token(), stream,
                                          data, length, off, iv12, 12)
                    jax.block_until_ready(outs)

    # ------------------------------------------------------- sharded seams
    def _run_sharded(self, op: str, stream, batch, hdr, length,
                     tail_args):
        """One unpacked RTP program over a part (`_sharded_launch`:
        deferred scatters)."""
        off_const = _uniform_off(hdr.payload_off, batch.capacity)
        fn = self._shard_fn(op, self.policy.auth_tag_len,
                            self.policy.cipher != Cipher.NULL, off_const)
        return self._sharded_launch(
            fn, self._sharded_device("rtp"), stream,
            [batch.data, np.asarray(length, dtype=np.int32),
             hdr.payload_off, *tail_args])

    @staticmethod
    def _roc32(v) -> np.ndarray:
        return (np.asarray(v, dtype=np.uint64)
                & 0xFFFFFFFF).astype(np.uint32)

    def _cm_rtp_protect_call(self, stream, batch, hdr, iv, v):
        data, olen = self._run_sharded("protect", stream, batch, hdr,
                                       batch.length, [iv, self._roc32(v)])
        return data, olen.astype(np.int32)

    def _cm_rtp_unprotect_call(self, stream, batch, hdr, iv, v, length
                               ) -> staging.Launch:
        """The seam's contract is `SrtpStreamTable`'s: the part comes
        with a staging plane (`batch.plane`; `batch.data` is its
        leading columns), into which chip-local row, length, payload
        offset, ROC (`v` mod 2**32) and IV are packed; ONE lane plane
        goes to the mesh, a block a chip, and one comes back
        (`_packed_call`).  Returns the `staging.Launch` in flight, whose
        `fetch()` gives host (data, media_len, auth_ok)."""
        fn = self._shard_fn(
            "unprotect", self.policy.auth_tag_len,
            self.policy.cipher != Cipher.NULL,
            _uniform_off(hdr.payload_off, batch.capacity))
        return self._packed_call(
            fn, batch.plane, stream,
            (length, hdr.payload_off, v & 0xFFFFFFFF), iv,
            _split_unprotect)

    # ------------------------------------------------------------------ F8
    def _f8_rtp_protect_call(self, stream, batch, hdr, iv, v):
        """Sharded AES-F8: the second key schedule `[S, R, 16]` rides
        the same row partition as the first."""
        data, olen = self._run_sharded("f8_protect", stream, batch, hdr,
                                       batch.length, [iv, self._roc32(v)])
        return data, olen.astype(np.int32)

    def _f8_rtp_unprotect_call(self, stream, batch, hdr, iv, v, length):
        data, mlen, auth_ok = self._run_sharded(
            "f8_unprotect", stream, batch, hdr, length,
            [iv, self._roc32(v)])
        return data, mlen.astype(np.int32), auth_ok

    # ----------------------------------------------------------------- GCM
    def _gcm_rtp_protect_call(self, stream, batch, hdr, iv12):
        """Sharded AEAD: BOTH forms shard — per-row (key schedule +
        GHASH matrix gathers chip-local) and grouped-GHASH (per-device
        group grids, `mesh_gcm_grid`); the winner is picked per shape
        by registry measurement (never a hardcoded per-row choice; the
        single-chip table's rule is `context._gcm_form_grid`)."""
        off_const = _uniform_off(hdr.payload_off, batch.capacity)
        data, olen = _registry.call(
            "mesh_gcm_rtp_protect", self._token(),
            np.asarray(stream, dtype=np.int64), batch.data,
            np.asarray(batch.length, dtype=np.int32), hdr.payload_off,
            np.asarray(iv12), off_const)
        return data, olen.astype(np.int32)

    def _gcm_rtp_unprotect_call(self, stream, batch, hdr, iv12, length
                                ) -> staging.Launch:
        """As the CM seam: the part comes with a staging plane under
        GCM too (`batch.data` is its leading columns) and nothing is
        packed; what goes back is a `staging.Launch` holding the three
        deferred scatters; five arrays are routed to their owning
        chips."""
        off_const = _uniform_off(hdr.payload_off, batch.capacity)
        stream = np.asarray(stream, dtype=np.int64)
        length = np.asarray(length, dtype=np.int32)
        iv12 = np.asarray(iv12)
        data, mlen, auth_ok = _registry.call(
            "mesh_gcm_rtp_unprotect", self._token(), stream, batch.data,
            length, hdr.payload_off, iv12, off_const)
        return staging.Launch(
            (data, mlen.astype(np.int32), auth_ok), h2d_arrays=5,
            h2d_bytes=batch.data.nbytes + length.nbytes + iv12.nbytes
            + 8 * batch.batch_size)

    def _token(self) -> _MeshSeamToken:
        tok = getattr(self, "_seam_token", None)
        if tok is None:
            tok = self._seam_token = _MeshSeamToken(self)
        return tok

    def _gcm_mesh_launch(self, op: str, stream, data, length, off, iv12,
                         off_const):
        """One sharded GCM launch, per-row or grouped.  The grouped
        form builds per-device group grids from the owner plan; when no
        usable grid exists (skew/all-distinct) it degrades to the
        per-row program — the registry then just measures a tie."""
        fn = self._shard_fn(op, 0, True, off_const)
        tabs = self._sharded_device("rtp")
        if not op.endswith("_grouped"):
            return self._sharded_launch(
                fn, tabs, stream, [data, length, off, iv12])
        ids = np.asarray(stream, dtype=np.int64)
        plan = _OwnerPlan(ids, self.capacity, self.rows_per, self.n_dev)
        local = local_rows(plan, ids, self.capacity, self.rows_per,
                           self.n_dev)
        gg = mesh_gcm_grid(local)
        if gg is None:
            return self._sharded_launch(
                self._shard_fn(op[: -len("_grouped")], 0, True,
                               off_const),
                tabs, stream, [data, length, off, iv12], plan=plan)
        return self._sharded_launch(fn, tabs, stream,
                                    [data, length, off, iv12],
                                    extra_args=gg, plan=plan)

    # ----------------------------------------------------------- SRTCP
    def _rtcp_protect_call(self, stream, batch, iv, index_word,
                           encrypting: bool, f8: bool = False):
        """Sharded SRTCP protect on the row-partitioned RTCP tables
        (a mesh deployment must not silently hop to a
        single-chip path for control traffic)."""
        fn = self._shard_fn("rtcp_f8_protect" if f8 else "rtcp_protect",
                            self.policy.auth_tag_len, encrypting, None)
        return self._sharded_launch(
            fn, self._sharded_device("rtcp"), stream,
            [batch.data, np.asarray(batch.length, dtype=np.int32), iv,
             np.asarray(index_word)])

    def _rtcp_unprotect_call(self, stream, batch, iv, length,
                             encrypting: bool, f8: bool = False):
        fn = self._shard_fn(
            "rtcp_f8_unprotect" if f8 else "rtcp_unprotect",
            self.policy.auth_tag_len, encrypting, None)
        return self._sharded_launch(
            fn, self._sharded_device("rtcp"), stream,
            [batch.data, np.asarray(length, dtype=np.int32), iv])

    def _gcm_rtcp_seal_call(self, stream, kin, klen, iv12):
        """Sharded AEAD SRTCP: the RTP gcm shard program re-runs on the
        RTCP table group (same shapes, aad pinned at 12 by the host
        layout shuffle in context.py)."""
        n = len(np.asarray(klen))
        return self._sharded_launch(
            self._shard_fn("gcm_protect", 0, True, 12),
            self._sharded_device("rtcp"), stream,
            [kin, np.asarray(klen, dtype=np.int32),
             np.full(n, 12, np.int32), iv12])

    def _gcm_rtcp_open_call(self, stream, kin, klen, iv12):
        n = len(np.asarray(klen))
        return self._sharded_launch(
            self._shard_fn("gcm_unprotect", 0, True, 12),
            self._sharded_device("rtcp"), stream,
            [kin, np.asarray(klen, dtype=np.int32),
             np.full(n, 12, np.int32), iv12])

    # ------------------------------------------------------- shard programs
    def _shard_fn(self, op: str, tag_len: int, encrypt: bool, off_const):
        if op.startswith("gcm_"):
            # GCM's tag/encrypt are fixed by the kernel: normalize them
            # OUT of the cache key so warmup and the live seams can
            # never build the same program under different keys
            tag_len, encrypt = 0, True
        key = (op, tag_len, encrypt, off_const)
        fn = self._sh_fns.get(key)
        if fn is not None:
            return fn
        row3 = P(self._axes, None, None)
        lanes = P(self._axes, None)
        f8 = op.startswith("f8_") or op.startswith("rtcp_f8_")
        if op == "unprotect":
            # the served CM unprotect: one packed plane each way
            return self._packed_fn(
                key, "mesh_unprotect_rtp", functools.partial(
                    kernel.srtp_unprotect, tag_len=tag_len,
                    encrypt=encrypt, payload_off_const=off_const))
        if op.startswith("gcm_"):
            fn = self._build_gcm_fn(op, off_const, row3, lanes)
        elif op.startswith("rtcp_"):
            fn = self._build_rtcp_fn(op, tag_len, encrypt, f8, row3,
                                     lanes)
        else:
            fn = self._build_rtp_fn(op, tag_len, encrypt, f8, off_const,
                                    row3, lanes)
        # setdefault: concurrent warm-ups of one key, and every table
        # of the mesh, share ONE jit (`_MESH_PROGRAMS`)
        return self._sh_fns.setdefault(key, fn)

    def _build_rtp_fn(self, op, tag_len, encrypt, f8, off_const, row3,
                      lanes):
        """The unpacked RTP programs: the CM `protect`, and F8 both
        ways (the CM unprotect is `_packed_fn`'s)."""
        unprot = op.endswith("unprotect")
        if f8:
            kfn = kernel.srtp_unprotect if unprot else kernel.srtp_protect

            def _run(tab_rk, tab_mid, tab_f8, local, data, length, off,
                     iv, roc):
                out = kfn(data[0], length[0], off[0], tab_rk[local[0]],
                          iv[0], tab_mid[local[0]], roc[0], tag_len,
                          encrypt, payload_off_const=off_const,
                          f8_round_keys=tab_f8[local[0]])
                return tuple(o[None] for o in out)
            in_specs = (row3, row3, row3, lanes, row3, lanes, lanes,
                        row3, lanes)
        else:
            def _run(tab_rk, tab_mid, local, data, length, off, iv, roc):
                # per-shard leading axis is 1 (this chip's lane block)
                out = kernel.srtp_protect(
                    data[0], length[0], off[0], tab_rk[local[0]], iv[0],
                    tab_mid[local[0]], roc[0], tag_len, encrypt,
                    payload_off_const=off_const)
                return tuple(o[None] for o in out)
            in_specs = (row3, row3, lanes, row3, lanes, lanes, row3,
                        lanes)
        return jax.jit(shard_map(
            _named(_run, f"mesh_{op}_rtp"), mesh=self.mesh,
            in_specs=in_specs,
            out_specs=(row3, lanes, lanes) if unprot else (row3, lanes),
            check_vma=False))

    def _build_gcm_fn(self, op, off_const, row3, lanes):
        from libjitsi_tpu.kernels import gcm as gcm_kernel

        grouped = op.endswith("_grouped")
        base = op[: -len("_grouped")] if grouped else op
        unprot = base == "gcm_unprotect"
        if grouped:
            gfn = gcm_kernel.gcm_protect_grouped if not unprot \
                else gcm_kernel.gcm_unprotect_grouped

            def _run(tab_rk, tab_gm, local, data, length, off, iv12,
                     grid, us, inv):
                out = gfn(data[0], length[0], off[0], tab_rk[local[0]],
                          tab_gm[us[0]], iv12[0], grid[0], inv[0],
                          aad_const=off_const)
                return tuple(o[None] for o in out)

            in_specs = (row3, row3, lanes, row3, lanes, lanes, row3,
                        row3, lanes, lanes)
        else:
            gfn = gcm_kernel.gcm_protect if not unprot \
                else gcm_kernel.gcm_unprotect

            def _run(tab_rk, tab_gm, local, data, length, off, iv12):
                out = gfn(data[0], length[0], off[0], tab_rk[local[0]],
                          tab_gm[local[0]], iv12[0],
                          aad_const=off_const)
                return tuple(o[None] for o in out)

            in_specs = (row3, row3, lanes, row3, lanes, lanes, row3)
        return jax.jit(shard_map(
            _named(_run, f"mesh_{op}"), mesh=self.mesh,
            in_specs=in_specs,
            out_specs=(row3, lanes, lanes) if unprot else (row3, lanes),
            check_vma=False))

    def _build_rtcp_fn(self, op, tag_len, encrypt, f8, row3, lanes):
        unprot = op.endswith("unprotect")
        if unprot:
            if f8:
                def _run(tab_rk, tab_mid, tab_f8, local, data, length,
                         iv):
                    out = kernel.srtcp_unprotect(
                        data[0], length[0], tab_rk[local[0]], iv[0],
                        tab_mid[local[0]], tag_len, encrypt,
                        f8_round_keys=tab_f8[local[0]])
                    return tuple(o[None] for o in out)
                in_specs = (row3, row3, row3, lanes, row3, lanes, row3)
            else:
                def _run(tab_rk, tab_mid, local, data, length, iv):
                    out = kernel.srtcp_unprotect(
                        data[0], length[0], tab_rk[local[0]], iv[0],
                        tab_mid[local[0]], tag_len, encrypt)
                    return tuple(o[None] for o in out)
                in_specs = (row3, row3, lanes, row3, lanes, row3)
            out_specs = (row3, lanes, lanes, lanes, lanes)
        else:
            if f8:
                def _run(tab_rk, tab_mid, tab_f8, local, data, length,
                         iv, word):
                    out = kernel.srtcp_protect(
                        data[0], length[0], tab_rk[local[0]], iv[0],
                        tab_mid[local[0]], word[0], tag_len, encrypt,
                        f8_round_keys=tab_f8[local[0]])
                    return tuple(o[None] for o in out)
                in_specs = (row3, row3, row3, lanes, row3, lanes, row3,
                            lanes)
            else:
                def _run(tab_rk, tab_mid, local, data, length, iv,
                         word):
                    out = kernel.srtcp_protect(
                        data[0], length[0], tab_rk[local[0]], iv[0],
                        tab_mid[local[0]], word[0], tag_len, encrypt)
                    return tuple(o[None] for o in out)
                in_specs = (row3, row3, lanes, row3, lanes, row3, lanes)
            out_specs = (row3, lanes)
        return jax.jit(shard_map(
            _named(_run, f"mesh_{op}"), mesh=self.mesh,
            in_specs=in_specs, out_specs=out_specs, check_vma=False))
