"""SrtpStreamTable — batched SRTP/SRTCP crypto contexts for S streams.

The reference allocates one mutable `SRTPCryptoContext`/`SRTCPCryptoContext`
per SSRC (org.jitsi.impl.neomedia.transform.srtp.SRTPTransformer keeps a
Map<ssrc, context>) and runs per-packet.  Here the contexts for all streams
are dense struct-of-arrays:

- device-resident tensors: AES round keys `[S, R, 16]`, HMAC midstates
  `[S, 2, 5]` — gathered by per-packet stream id inside the jitted kernel;
- host arrays: session salts (IV construction), ROC / highest-index, replay
  windows, SRTCP indices — the tiny sequential state machine that cannot
  vmap (RFC 3711 Appendix A estimation + §3.3.2 replay) stays in NumPy.

One table holds one crypto profile (homogeneous `[S, R, 16]` shape); mixed
deployments use one table per profile and partition batches — mirrors the
reference where each stream's policy is fixed at context creation.

A "stream" row is one direction of one SSRC: use separate tables (or
disjoint row ranges) for tx and rx, as the reference does via separate
forward/reverse context maps.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from libjitsi_tpu.core import staging
from libjitsi_tpu.core.packet import (ROW_CLASSES, PacketBatch,
                                      _round_rows, bucket_by_size,
                                      unbucket)
from libjitsi_tpu.core.rtp_math import (
    _segments,
    chain_packet_indices,
    estimate_packet_index,
    segment_ranks,
)
from libjitsi_tpu.kernels import gcm as gcm_kernel
from libjitsi_tpu.kernels.aes import (aes_encrypt_np, expand_key,
                                      expand_keys_batch, f8_m)
from libjitsi_tpu.kernels.ghash import (GM_BYTES, ghash_matrix,
                                        ghash_matrix_batch)
from libjitsi_tpu.kernels.sha1 import hmac_precompute, hmac_precompute_batch
from libjitsi_tpu.rtp import header as rtp_header
from libjitsi_tpu.transform.srtp import kernel, replay
from libjitsi_tpu.transform.srtp.kdf import (derive_session_keys,
                                             derive_session_keys_batch)
from libjitsi_tpu.transform.srtp.policy import Cipher, SrtpPolicy, SrtpProfile
from libjitsi_tpu.utils.tracing import span_of


# --- jitted wrappers: gather per-stream key material on device -------------

@functools.partial(
    jax.jit, static_argnames=("tag_len", "encrypt", "off_const"))
def _protect_rtp_dev(tab_rk, tab_mid, stream, data, length, payload_off, iv,
                     roc, tag_len: int, encrypt: bool, off_const=None,
                     tab_f8=None):
    rk, mid, f8 = kernel.gather_keys(stream, tab_rk, tab_mid, tab_f8)
    return kernel.srtp_protect(
        data, length, payload_off, rk, iv, mid, roc,
        tag_len, encrypt, payload_off_const=off_const, f8_round_keys=f8)


def _unprotect_rtp_impl(tab_rk, tab_mid, stream, data, length, payload_off,
                        iv, roc, tag_len: int, encrypt: bool, off_const=None,
                        tab_f8=None):
    rk, mid, f8 = kernel.gather_keys(stream, tab_rk, tab_mid, tab_f8)
    return kernel.srtp_unprotect(
        data, length, payload_off, rk, iv, mid, roc,
        tag_len, encrypt, payload_off_const=off_const, f8_round_keys=f8)


_unprotect_rtp_dev = jax.jit(
    _unprotect_rtp_impl, static_argnames=("tag_len", "encrypt", "off_const"))

# donated twin for the ingest seam: the H2D staging buffer minted from
# the recv arena (`jnp.asarray(batch.data)`) is consumed exactly once,
# so donating it lets XLA alias the decrypted output into the staged
# input instead of allocating a second batch-width buffer — the last
# host-side copy of the ingest leg.  Selected only off-CPU
# (`_donate_ingest`): the CPU backend ignores donation with a per-call
# warning.
_unprotect_rtp_dev_donated = jax.jit(
    _unprotect_rtp_impl, static_argnames=("tag_len", "encrypt", "off_const"),
    donate_argnums=(3,))


def _donate_ingest() -> bool:
    """Donate the arena-backed packet buffer through the jit boundary
    only where it buys a device allocation back (non-CPU backends; on
    CPU XLA ignores the donation hint).  LIBJITSI_TPU_FORCE_DONATE=1
    forces the donated twins on for CPU-tier soak/parity runs."""
    import os
    if os.environ.get("LIBJITSI_TPU_FORCE_DONATE", ""):
        return True
    return jax.default_backend() != "cpu"


def _unprotect_rtp_dev_call(*args, **kwargs):
    fn = (_unprotect_rtp_dev_donated if _donate_ingest()
          else _unprotect_rtp_dev)
    return fn(*args, **kwargs)


def _unprotect_rtp_packed_impl(tab_rk, tab_mid, plane, tag_len: int,
                               encrypt: bool, off_const=None):
    """`_unprotect_rtp_impl` on one packed plane (core/staging.py):
    words stream, length, payload offset, ROC; out words media length,
    auth verdict.  The arithmetic between is the unpacked call's."""
    data, w, iv = staging.unpack(plane)
    rk, mid = kernel.gather_keys(staging.as_i32(w[:, 0]), tab_rk, tab_mid)
    out, mlen, auth_ok = kernel.srtp_unprotect(
        data, staging.as_i32(w[:, 1]), staging.as_i32(w[:, 2]), rk, iv,
        mid, w[:, 3], tag_len, encrypt, payload_off_const=off_const)
    return staging.repack(out, mlen, auth_ok)


_unprotect_rtp_packed = jax.jit(
    _unprotect_rtp_packed_impl,
    static_argnames=("tag_len", "encrypt", "off_const"))

# donated twin, as `_unprotect_rtp_dev_donated`: the plane that comes
# back has the staged plane's shape, so XLA writes it over the input
_unprotect_rtp_packed_donated = jax.jit(
    _unprotect_rtp_packed_impl,
    static_argnames=("tag_len", "encrypt", "off_const"),
    donate_argnums=(2,))


def _split_unprotect(host):
    """The unprotect's plane back on the host -> (data, media_len,
    auth_ok)."""
    data, words = staging.split_out(host, 2)
    return data, words[:, 0], words[:, 1].astype(bool)


def _split_unpacked(data, mlen, auth_ok):
    """An unpacked unprotect's three arrays back on the host -> (data,
    media_len, auth_ok)."""
    return data, mlen.astype(np.int32), auth_ok


def _uniform_off(payload_off, width: int) -> "int | None":
    """Static payload offset when the whole batch agrees (the common case:
    fixed 12-byte headers).  Lets the kernel use the pad-shift keystream
    alignment instead of the per-row gather.  Out-of-range offsets (a
    forged ext_words field can claim a header larger than the packet) fall
    back to the gather path, which clamps per row and lets such packets
    die on auth failure instead of crashing the trace."""
    off = np.asarray(payload_off)
    if off.size and np.all(off == off.flat[0]):
        v = int(off.flat[0])
        if 0 <= v < width:
            return v
    return None


@functools.partial(jax.jit, static_argnames=("tag_len", "encrypt"))
def _protect_rtcp_dev(tab_rk, tab_mid, stream, data, length, iv, index_word,
                      tag_len: int, encrypt: bool, tab_f8=None):
    return kernel.srtcp_protect(
        data, length, tab_rk[stream], iv, tab_mid[stream], index_word,
        tag_len, encrypt,
        f8_round_keys=None if tab_f8 is None else tab_f8[stream])


@functools.partial(jax.jit, static_argnames=("tag_len", "encrypt"))
def _unprotect_rtcp_dev(tab_rk, tab_mid, stream, data, length, iv,
                        tag_len: int, encrypt: bool, tab_f8=None):
    return kernel.srtcp_unprotect(
        data, length, tab_rk[stream], iv, tab_mid[stream], tag_len, encrypt,
        f8_round_keys=None if tab_f8 is None else tab_f8[stream])


def _rtcp_row_pad(n: int):
    """Row indices padding an RTCP batch up to its ROW_CLASSES bucket by
    cycling the real rows — the device calls are pure w.r.t. table state
    (index assignment and replay bookkeeping run on the REAL rows on the
    host), so repeats are safe and padded output rows are sliced off.
    Bounds the compiled RTCP shape space to the row classes instead of
    one cache entry per distinct per-tick RTCP count (which churns
    without bound on a live bridge).  None when already on a boundary."""
    n_pad = _round_rows(n)
    return np.resize(np.arange(n), n_pad) if n_pad > n else None


@functools.partial(jax.jit, static_argnames=("aad_const",))
def _protect_gcm_dev(tab_rk, tab_gm, stream, data, length, aad_len, iv12,
                     aad_const=None):
    rk, gm = kernel.gather_keys(stream, tab_rk, tab_gm)
    return gcm_kernel.gcm_protect(
        data, length, aad_len, rk, gm, iv12, aad_const=aad_const)


def _unprotect_gcm_impl(tab_rk, tab_gm, plane, aad_const=None):
    """The per-row GCM RTP unprotect on one packed plane
    (core/staging.py): words stream, length, payload offset (GCM has no
    ROC word), the 12-byte IV in the first 12 IV columns; out words
    media length, auth verdict.  The arithmetic between is
    `gcm_kernel.gcm_unprotect`'s."""
    data, w, iv = staging.unpack(plane)
    rk, gm = kernel.gather_keys(staging.as_i32(w[:, 0]), tab_rk, tab_gm)
    out, mlen, auth_ok = gcm_kernel.gcm_unprotect(
        data, staging.as_i32(w[:, 1]), staging.as_i32(w[:, 2]), rk, gm,
        iv[:, :12], aad_const=aad_const)
    return staging.repack(out, mlen, auth_ok)


_unprotect_gcm_dev = jax.jit(
    _unprotect_gcm_impl, static_argnames=("aad_const",))

# donated twin — see _unprotect_rtp_packed_donated
_unprotect_gcm_dev_donated = jax.jit(
    _unprotect_gcm_impl, static_argnames=("aad_const",),
    donate_argnums=(2,))


def _unprotect_gcm_dev_call(*args, **kwargs):
    fn = (_unprotect_gcm_dev_donated if _donate_ingest()
          else _unprotect_gcm_dev)
    return fn(*args, **kwargs)


@functools.partial(jax.jit, static_argnames=("aad_const",))
def _open_gcm_dev(tab_rk, tab_gm, stream, data, length, aad_len, iv12,
                  aad_const=None):
    """The per-row GCM open with an array an argument: SRTCP's
    (`_gcm_rtcp_open_call`), as `_protect_gcm_dev` is its seal."""
    rk, gm = kernel.gather_keys(stream, tab_rk, tab_gm)
    return gcm_kernel.gcm_unprotect(
        data, length, aad_len, rk, gm, iv12, aad_const=aad_const)


@functools.partial(jax.jit, static_argnames=("aad_const",))
def _protect_gcm_grouped_dev(tab_rk, tab_gm, stream, data, length,
                             aad_len, iv12, grid_rows, ustream, inv_pos,
                             aad_const=None):
    return gcm_kernel.gcm_protect_grouped(
        data, length, aad_len, tab_rk[stream], tab_gm[ustream], iv12,
        grid_rows, inv_pos, aad_const=aad_const)


@functools.partial(jax.jit, static_argnames=("aad_const",))
def _unprotect_gcm_grouped_dev(tab_rk, tab_gm, stream, data, length,
                               aad_len, iv12, grid_rows, ustream,
                               inv_pos, aad_const=None):
    return gcm_kernel.gcm_unprotect_grouped(
        data, length, aad_len, tab_rk[stream], tab_gm[ustream], iv12,
        grid_rows, inv_pos, aad_const=aad_const)


def _gcm_grid(stream: np.ndarray):
    """Group batch rows by stream for the grouped-GHASH path.

    Returns (grid_rows [G, P] int32 row-index-or-minus-one, ustream [G]
    int64, inv_pos [B] int32), with G and P rounded up to powers of two
    so jit shapes stay cacheable — or None when the grouped path is
    structurally unusable (stream skew so heavy the padded grid would
    more than double the GHASH work).  Whether a grid that exists is
    USED is `_gcm_form_grid`'s to say, from the call's shape alone.
    """
    n = len(stream)
    if n < 8:      # dispatch-dominated: nothing to win, skip the grid
        return None
    order, s_o, first, grp, fpos = _segments(stream)
    g = int(grp[-1]) + 1
    if g == n:     # every row its own stream: grouped ≡ per-row
        return None
    rank = np.arange(n, dtype=np.int64) - fpos[grp]
    p = int(rank.max()) + 1
    gp = 1 << max(g - 1, 0).bit_length()
    pp = 1 << max(p - 1, 0).bit_length()
    if gp * pp > 2 * n:
        return None
    grid = np.full((gp, pp), -1, dtype=np.int32)
    grid[grp, rank] = order
    ustream = np.zeros(gp, dtype=np.int64)
    ustream[:g] = s_o[fpos]
    inv = np.empty(n, dtype=np.int32)
    inv[order] = (grp * pp + rank).astype(np.int32)
    return grid, ustream, inv


#: padded rows of one call above which the grouped GHASH form is used:
#: the largest row class a served table meets.  On the v5e the two
#: forms are within 13 % of each other in every class up to it, and at
#: the grids a live tick yields grouped is level or 7-9 % slower
#: (`scripts/gcm_forms_bench.py`; PERF.md, Findings, PR 31)
GCM_GROUPED_ABOVE_ROWS = ROW_CLASSES[-1]


def _gcm_form_grid(stream: np.ndarray):
    """Per-row or grouped GHASH for one GCM RTP call, as the grid to
    run it with: `_gcm_grid(stream)` for the grouped form, None for the
    per-row one.  A pure function of the call's shape — the padded rows
    and whether a grid exists — so equal shapes take equal forms,
    nothing is timed, and a warm-up that drives a shape warms the
    program a live tick of that shape runs.

    Grouped reads each stream's 16 KiB matrix once for the stream's P
    rows; per-row gathers a matrix a row.  Inside the row classes
    (`ROW_CLASSES`, all a served table launches) per-row it is: one
    program a class, whatever the streams of a tick are, where a grid's
    (G, P) changes with them — `bucket_by_size` pads a part by cycling
    its rows, which yields grids of 2 or 4 rows a stream and up to
    twice the part's rows, and there grouped computes more than it
    saves; the grid (a sort of the rows) is not even built.  Above them
    — a bulk caller's batch, where per-row gathers 268 MB of matrices
    for 16,384 rows — grouped, if a grid exists.  Neither the grid's
    (G, P) nor the width moved the choice on the v5e."""
    if len(stream) <= GCM_GROUPED_ABOVE_ROWS:
        return None
    return _gcm_grid(stream)


# --- keystream-cache fast path (transform/srtp/keystream.py) ---------------
# On an all-rows window hit the tick pays only the fused XOR + GHASH
# kernel; the slot gathers ride inside the jit boundary so the cache
# tables stay device-resident between fills.

@functools.partial(jax.jit, static_argnames=("aad_const",))
def _protect_gcm_cached_dev(ks_tab, ek_tab, slot, tab_gm, stream, data,
                            length, aad_const: int):
    return gcm_kernel.gcm_protect_cached(
        data, length, ks_tab[slot], ek_tab[slot], tab_gm[stream],
        aad_const=aad_const)


@functools.partial(jax.jit, static_argnames=("aad_const",))
def _unprotect_gcm_cached_dev(ks_tab, ek_tab, slot, tab_gm, stream, data,
                              length, aad_const: int):
    return gcm_kernel.gcm_unprotect_cached(
        data, length, ks_tab[slot], ek_tab[slot], tab_gm[stream],
        aad_const=aad_const)


@functools.partial(jax.jit, static_argnames=("aad_const", "packed"))
def _protect_gcm_cached_grouped_dev(ks_tab, ek_tab, slot, tab_gm, stream,
                                    data, length, grid_rows, ustream,
                                    inv_pos, aad_const: int,
                                    packed: bool = False):
    return gcm_kernel.gcm_protect_cached_grouped(
        data, length, ks_tab[slot], ek_tab[slot], tab_gm[ustream],
        grid_rows, inv_pos, aad_const=aad_const, packed=packed)


@functools.partial(jax.jit, static_argnames=("aad_const", "packed"))
def _unprotect_gcm_cached_grouped_dev(ks_tab, ek_tab, slot, tab_gm,
                                      stream, data, length, grid_rows,
                                      ustream, inv_pos, aad_const: int,
                                      packed: bool = False):
    return gcm_kernel.gcm_unprotect_cached_grouped(
        data, length, ks_tab[slot], ek_tab[slot], tab_gm[ustream],
        grid_rows, inv_pos, aad_const=aad_const, packed=packed)


class SrtpStreamTable:
    """Batched crypto contexts for up to `capacity` streams of one profile."""

    #: a call's rows are padded to their row class (`ROW_CLASSES`) here,
    #: before the device seam.  The mesh table says False: its owner
    #: plan pads the lanes a chip, once (mesh/table.py)
    _pads_rows = True

    def __init__(self, capacity: int = 1024,
                 profile: SrtpProfile = SrtpProfile.AES_CM_128_HMAC_SHA1_80):
        self.profile = profile
        self.policy: SrtpPolicy = profile.policy
        self.capacity = capacity
        self._gcm = self.policy.cipher == Cipher.AES_GCM
        self._f8 = self.policy.cipher == Cipher.AES_F8
        rounds = {16: 11, 32: 15}[self.policy.enc_key_len]

        s = capacity
        self.active = np.zeros(s, dtype=bool)
        # device-side key material (numpy master copy; pushed lazily)
        self._rk_rtp = np.zeros((s, rounds, 16), dtype=np.uint8)
        self._mid_rtp = np.zeros((s, 2, 5), dtype=np.uint32)
        self._rk_rtcp = np.zeros((s, rounds, 16), dtype=np.uint8)
        self._mid_rtcp = np.zeros((s, 2, 5), dtype=np.uint32)
        if self._gcm:
            # per-stream GHASH matrices (H = AES_K(0), RFC 7714): the MXU
            # form of the GF(2^128) multiply — see kernels/ghash.py
            self._gm_rtp = np.zeros((s, 128, 128), dtype=np.int8)
            self._gm_rtcp = np.zeros((s, 128, 128), dtype=np.int8)
        if self._f8:
            # second schedule per stream: E(k_e XOR m) for IV' (RFC 3711
            # §4.1.2.2; reference SRTPCipherF8.deriveForIV analog)
            self._rk_f8_rtp = np.zeros((s, rounds, 16), dtype=np.uint8)
            self._rk_f8_rtcp = np.zeros((s, rounds, 16), dtype=np.uint8)
        self._dev = None  # cached jnp copies
        self._aliased = False  # device copies may alias host buffers
        # host-side IV salts (16B, low 2 bytes zero)
        self._salt_rtp = np.zeros((s, 16), dtype=np.uint8)
        self._salt_rtcp = np.zeros((s, 16), dtype=np.uint8)
        # sequential per-stream state
        self.tx_ext = np.full(s, -1, dtype=np.int64)  # last sent ext index
        self.rx_max = np.full(s, -1, dtype=np.int64)  # highest authed index
        self.rx_mask = np.zeros(s, dtype=np.uint64)
        self.rtcp_tx_index = np.full(s, -1, dtype=np.int64)
        self.rtcp_rx_max = np.full(s, -1, dtype=np.int64)
        self.rtcp_rx_mask = np.zeros(s, dtype=np.uint64)
        # per-stream receive-failure accounting (RTP + RTCP combined):
        # the supervisor's quarantine detector reads per-tick deltas of
        # these to isolate an SSRC storming garbage (service/supervisor).
        # Size-class bucket padding can double-count an auth failure
        # (padding rows duplicate real rows and also fail auth) — fine
        # for a rate threshold; replay_reject counts only window-based
        # rejects, never the in-batch dedup kills padding produces.
        self.auth_fail = np.zeros(s, dtype=np.int64)
        self.replay_reject = np.zeros(s, dtype=np.int64)
        # key-derivation-rate re-keying (reference:
        # BaseSRTPCryptoContext.keyDerivationRate): master material is
        # retained for kdr>0 streams and session keys are re-derived when
        # a packet index crosses an index//kdr epoch boundary
        self.kdr = np.zeros(s, dtype=np.int64)
        self._epoch_rtp = np.zeros(s, dtype=np.int64)
        self._epoch_rtcp = np.zeros(s, dtype=np.int64)
        self._masters: Dict[int, Tuple[bytes, bytes]] = {}
        # the one outstanding dispatch-only unprotect (pipelined rx):
        # its replay/counter commit is forced before any state reader
        # or new dispatch can observe a stale window
        self._inflight_unprotect: "PendingUnprotect | None" = None
        # optional keystream pregeneration cache (GCM only; enabled via
        # enable_keystream_cache).  None keeps every path stock — the
        # mesh subclasses override the _gcm_rtp_*_call seams and must
        # never see a cache consult ahead of them.
        self._ks_cache = None
        # device-side (stream, grid) conversions memoized by the batch's
        # stream pattern: an SFU's batch composition is stable tick over
        # tick, so the grouping grid and its device arrays are reused
        # instead of recomputed + re-device_put per batch (the cached
        # fast path is host-bound without this)
        self._grid_memo: dict = {}
        # a bridge hands its loop's PipelineTracer here
        # (`unprotect_host` / `unprotect_wait`); a table standing
        # alone spans nothing
        self.tracer = None

    def enable_keystream_cache(self, window: int = 64,
                               ks_bytes: int = 256,
                               pool: Optional[int] = None,
                               debug: bool = False):
        """Attach an off-tick keystream pregeneration cache (GCM only).

        The tick-path protect/unprotect then serves the fused
        XOR + GHASH kernels on window hit and falls back bit-exactly to
        the stock path on miss; `fill()` must run between ticks (the
        lifecycle plane does this for bridge tables).  Returns the
        cache for direct priming/inspection."""
        if not self._gcm:
            raise ValueError(
                "keystream cache requires an AEAD-GCM profile")
        from libjitsi_tpu.transform.srtp.keystream import KeystreamCache
        self._ks_cache = KeystreamCache(self, window=window,
                                        ks_bytes=ks_bytes, pool=pool,
                                        debug=debug)
        return self._ks_cache

    def _commit_inflight_unprotect(self) -> None:
        """Ordering barrier for the pipelined receive path: host replay
        state of the outstanding `unprotect_rtp_async` must land before
        anything re-reads or mutates per-stream RX state."""
        p = self._inflight_unprotect
        if p is not None:
            p.commit()

    def commit_inflight(self) -> None:
        """Public commit barrier: materialize the outstanding async
        unprotect's auth verdicts (a fenced wait on ITS device work)
        and land the replay-window update now, instead of implicitly
        inside the next dispatch."""
        self._commit_inflight_unprotect()

    def _cow_tables(self) -> None:
        """Copy-on-write before any key-table mutation.

        Also the safe point to force the pipelined receive commit:
        every table mutator funnels through here, and a pending
        unprotect must not commit replay state into rows a mutation is
        about to recycle.

        On the CPU backend `jnp.asarray` can zero-copy ALIAS the host
        numpy buffers (see the project's asarray-alias note), so writing
        keys in place while async/pipelined work is in flight would feed
        mutated keys to already-dispatched kernels.  Re-pointing the
        numpy attributes at fresh copies leaves any aliased device
        arrays reading the old, still-consistent buffers; `_dev = None`
        makes the next launch re-upload the new ones.

        Copies happen at most once per dispatch episode (`_aliased` is
        set by `_device()` and cleared here), so a loop of installs —
        or a kdr epoch re-keying many streams — pays ONE table copy,
        not one per stream (a 10k GCM table is ~340 MB of matrices).
        """
        self._commit_inflight_unprotect()
        if self._ks_cache is not None:
            # keys are about to change somewhere in the table: cached
            # keystream windows may be stale — drop them all (they
            # refill off-tick; the per-stream served high-water in the
            # cache survives, preserving never-serve-twice)
            self._ks_cache.invalidate()
        if not self._aliased:
            self._dev = None
            return
        self._aliased = False
        self._rk_rtp = self._rk_rtp.copy()
        self._rk_rtcp = self._rk_rtcp.copy()
        self._mid_rtp = self._mid_rtp.copy()
        self._mid_rtcp = self._mid_rtcp.copy()
        if self._gcm:
            self._gm_rtp = self._gm_rtp.copy()
            self._gm_rtcp = self._gm_rtcp.copy()
        if self._f8:
            self._rk_f8_rtp = self._rk_f8_rtp.copy()
            self._rk_f8_rtcp = self._rk_f8_rtcp.copy()
        self._salt_rtp = self._salt_rtp.copy()
        self._salt_rtcp = self._salt_rtcp.copy()
        self._dev = None

    # ------------------------------------------------------------------ keys
    def add_stream(self, sid: int, master_key: bytes, master_salt: bytes,
                   kdr: int = 0) -> None:
        """Derive session keys and install them at row `sid`.

        Reference: SRTPContextFactory + SRTPCryptoContext.deriveSrtpKeys.
        """
        p = self.policy
        if len(master_key) != p.enc_key_len:
            raise ValueError(
                f"master key must be {p.enc_key_len}B for {self.profile.value}")
        if len(master_salt) != p.salt_len:
            raise ValueError(f"master salt must be {p.salt_len}B")
        ks = derive_session_keys(
            master_key, master_salt, enc_key_len=p.enc_key_len,
            auth_key_len=p.auth_key_len, salt_len=p.salt_len, kdr=kdr)
        self._install_session_keys(sid, ks)
        self.tx_ext[sid] = -1
        self.rx_max[sid] = -1
        self.rx_mask[sid] = 0
        self.rtcp_tx_index[sid] = -1
        self.rtcp_rx_max[sid] = -1
        self.rtcp_rx_mask[sid] = 0
        self.auth_fail[sid] = 0
        self.replay_reject[sid] = 0
        self.kdr[sid] = kdr
        self._epoch_rtp[sid] = 0
        self._epoch_rtcp[sid] = 0
        if kdr:
            self._masters[sid] = (bytes(master_key), bytes(master_salt))
        else:
            self._masters.pop(sid, None)
        self.active[sid] = True
        self._dev = None

    def add_streams(self, sids, master_keys, master_salts,
                    kdr=0) -> None:
        """Vectorized bulk install: `add_stream` for many rows at once.

        The install plane at scale — conference join storms, checkpoint
        restore, a 10k-stream bootstrap — runs the KDF, AES key
        schedules, HMAC midstates and (for GCM) GHASH matrices as single
        vectorized passes instead of a per-stream Python loop.
        Reference: SRTPContextFactory per context; the batching has no
        reference analog (its per-object design installs one at a time).
        """
        sids = np.asarray(sids, dtype=np.int64)
        mks = np.atleast_2d(np.asarray(master_keys, dtype=np.uint8))
        mss = np.atleast_2d(np.asarray(master_salts, dtype=np.uint8))
        s = len(sids)
        p = self.policy
        if mks.shape != (s, p.enc_key_len):
            raise ValueError(
                f"master keys must be [{s}, {p.enc_key_len}] for "
                f"{self.profile.value}, got {mks.shape}")
        if mss.shape != (s, p.salt_len):
            raise ValueError(f"master salts must be [{s}, {p.salt_len}]")
        kdr_arr = np.broadcast_to(np.asarray(kdr, dtype=np.int64), (s,))

        ksb = derive_session_keys_batch(
            mks, mss, enc_key_len=p.enc_key_len,
            auth_key_len=p.auth_key_len, salt_len=p.salt_len)

        self._cow_tables()
        self._rk_rtp[sids] = expand_keys_batch(ksb.rtp_enc)
        self._rk_rtcp[sids] = expand_keys_batch(ksb.rtcp_enc)
        if self._gcm:
            for rk_tab, gm_tab in ((self._rk_rtp, self._gm_rtp),
                                   (self._rk_rtcp, self._gm_rtcp)):
                h = aes_encrypt_np(rk_tab[sids],
                                   np.zeros((s, 16), np.uint8))
                gm_tab[sids] = ghash_matrix_batch(h).astype(np.int8)
        else:
            self._mid_rtp[sids] = hmac_precompute_batch(ksb.rtp_auth)
            self._mid_rtcp[sids] = hmac_precompute_batch(ksb.rtcp_auth)
        if self._f8:
            # F8 needs E(k_e XOR m) per stream; the m derivation is
            # byte math but the schedule re-expansion batches fine
            for enc, salt, rkf in (
                    (ksb.rtp_enc, ksb.rtp_salt, self._rk_f8_rtp),
                    (ksb.rtcp_enc, ksb.rtcp_salt, self._rk_f8_rtcp)):
                masked = np.stack([
                    np.frombuffer(
                        bytes(a ^ b for a, b in zip(
                            bytes(enc[i]),
                            f8_m(bytes(enc[i]), bytes(salt[i])))),
                        dtype=np.uint8)
                    for i in range(s)])
                rkf[sids] = expand_keys_batch(masked)
        self._salt_rtp[sids, : p.salt_len] = ksb.rtp_salt
        self._salt_rtp[sids, p.salt_len:] = 0
        self._salt_rtcp[sids, : p.salt_len] = ksb.rtcp_salt
        self._salt_rtcp[sids, p.salt_len:] = 0

        self.tx_ext[sids] = -1
        self.rx_max[sids] = -1
        self.rx_mask[sids] = 0
        self.rtcp_tx_index[sids] = -1
        self.rtcp_rx_max[sids] = -1
        self.rtcp_rx_mask[sids] = 0
        self.auth_fail[sids] = 0
        self.replay_reject[sids] = 0
        self.kdr[sids] = kdr_arr
        self._epoch_rtp[sids] = 0
        self._epoch_rtcp[sids] = 0
        for i, sid in enumerate(sids):
            if kdr_arr[i]:
                self._masters[int(sid)] = (mks[i].tobytes(),
                                           mss[i].tobytes())
            else:
                self._masters.pop(int(sid), None)
        self.active[sids] = True
        self._dev = None
        if self._ks_cache is not None:
            self._ks_cache.forget(sids)

    def _install_session_keys(self, sid: int, ks) -> None:
        """Pack one stream's derived session keys into the device tables
        (shared by add_stream and kdr epoch re-derivation)."""
        p = self.policy
        self._cow_tables()
        self._rk_rtp[sid] = expand_key(ks.rtp_enc)
        self._rk_rtcp[sid] = expand_key(ks.rtcp_enc)
        if self._gcm:
            for rk, gm in ((self._rk_rtp, self._gm_rtp),
                           (self._rk_rtcp, self._gm_rtcp)):
                h = bytes(aes_encrypt_np(rk[sid],
                                         np.zeros((1, 16), np.uint8))[0])
                gm[sid] = ghash_matrix(h).astype(np.int8)
        else:
            self._mid_rtp[sid] = hmac_precompute(ks.rtp_auth)
            self._mid_rtcp[sid] = hmac_precompute(ks.rtcp_auth)
        if self._f8:
            for enc, salt, rkf in ((ks.rtp_enc, ks.rtp_salt, self._rk_f8_rtp),
                                   (ks.rtcp_enc, ks.rtcp_salt,
                                    self._rk_f8_rtcp)):
                m = f8_m(enc, salt)
                rkf[sid] = expand_key(bytes(a ^ b for a, b in zip(enc, m)))
        self._salt_rtp[sid, : p.salt_len] = np.frombuffer(ks.rtp_salt, np.uint8)
        self._salt_rtp[sid, p.salt_len:] = 0
        self._salt_rtcp[sid, : p.salt_len] = np.frombuffer(ks.rtcp_salt,
                                                           np.uint8)
        self._salt_rtcp[sid, p.salt_len:] = 0
        self._dev = None
        if self._ks_cache is not None:
            self._ks_cache.forget(sid)

    def _rtcp_pad(self, n: int):
        """`_rtcp_row_pad` where this table pads rows, else None."""
        return _rtcp_row_pad(n) if self._pads_rows else None

    def _scratch(self) -> "SrtpStreamTable":
        """An empty table that launches the programs this one launches:
        same class, capacity (the key tables' rows are part of every
        program's signature) and profile."""
        return SrtpStreamTable(self.capacity, self.profile)

    def warmup_rtp(self, batch_size: int, packets_per_stream: int = 4,
                   payload_len: int = 160) -> None:
        """Pre-compile the RTP protect/unprotect programs for the given
        batch shape, OFF the media path.  For GCM the batch drives the
        form that `_gcm_form_grid` picks for its shape, which is the form
        a live tick of that shape runs: nothing is timed here.  Runs on
        a THROWAWAY table of this table's class and shape
        (`_scratch`: a mesh table's is sharded over its mesh, so what
        is warmed is what it launches) so the real table's tx indices
        and replay windows are untouched; jit caches are process-global,
        so the real path hits them warm."""
        scratch = self._scratch()
        n = max(1, min(self.capacity,
                       batch_size // max(packets_per_stream, 1)))
        rng = np.random.default_rng(0)
        sids = np.arange(n)
        mks = rng.integers(0, 256, (n, self.policy.enc_key_len),
                           dtype=np.uint8)
        mss = rng.integers(0, 256, (n, self.policy.salt_len),
                           dtype=np.uint8)
        scratch.add_streams(sids, mks, mss)
        pp = -(-batch_size // n)
        streams = np.repeat(sids, pp)[:batch_size]
        seqs = segment_ranks(streams) + 1
        pls = [b"\x00" * payload_len] * batch_size
        b = rtp_header.build(pls, seqs.tolist(),
                             [0] * batch_size,
                             (0x4000 + streams).tolist(),
                             [96] * batch_size,
                             stream=streams.tolist())
        wire = scratch.protect_rtp(b)
        scratch.unprotect_rtp(wire)
        src = self._ks_cache
        if src is not None and pp < src.window:
            # cached-path twin: the stock shapes above stay warm (a
            # cache miss must not compile in a tick), and a primed
            # scratch cache compiles the fused hit-path kernels plus
            # the off-tick fill scatter for the same batch shapes.
            # The rx leg runs on a second table with the same keys —
            # protect consumes the tx cache's slots, so hitting on
            # unprotect needs a window of its own.
            cw = dict(window=src.window, ks_bytes=src.ks_bytes,
                      pool=src.pool)
            ssrcs = 0x4000 + sids
            ctx = scratch.enable_keystream_cache(**cw)
            ctx.prime(sids, ssrcs)
            b2 = rtp_header.build(pls, ((seqs + pp) & 0xFFFF).tolist(),
                                  [0] * batch_size,
                                  (0x4000 + streams).tolist(),
                                  [96] * batch_size,
                                  stream=streams.tolist())
            wire2 = scratch.protect_rtp(b2)
            scratch_rx = self._scratch()
            scratch_rx.add_streams(sids, mks, mss)
            crx = scratch_rx.enable_keystream_cache(**cw)
            crx.prime(sids, ssrcs, start=1 + pp)
            scratch_rx.unprotect_rtp(wire2)

    def warmup_rtcp(self, batch_size: int = 1) -> None:
        """Pre-compile the SRTCP protect/unprotect programs for the row
        class covering `batch_size` — control traffic rides the same
        zero-recompile discipline as media (the per-tick RTCP count is
        row-class padded, so one warm per class covers every count in
        it).  Scratch table, same rationale as `warmup_rtp`."""
        scratch = self._scratch()
        scratch.add_stream(0, b"\x00" * self.policy.enc_key_len,
                           b"\x00" * self.policy.salt_len)
        # minimal valid compound: one empty receiver report (PT 201)
        blob = bytes([0x80, 201, 0, 1]) + (0x4000).to_bytes(4, "big")
        b = PacketBatch.from_payloads([blob] * max(1, batch_size),
                                      stream=[0] * max(1, batch_size))
        wire = scratch.protect_rtcp(b)
        scratch.unprotect_rtcp(wire)

    @staticmethod
    def _row_subset(batch: PacketBatch, rows: np.ndarray) -> PacketBatch:
        return PacketBatch(batch.data[rows].copy(),
                           np.asarray(batch.length)[rows].copy(),
                           np.asarray(batch.stream)[rows].copy())

    def _kdr_active(self, stream: np.ndarray) -> bool:
        valid = (stream >= 0) & (stream < self.capacity)
        return bool((self.kdr[np.clip(stream, 0, self.capacity - 1)]
                     * valid > 0).any())

    def _epoch_plan(self, stream: np.ndarray, idx: np.ndarray,
                    rtcp: bool):
        """kdr re-keying plan (RFC 3711 §4.3; reference
        keyDerivationRate): group rows into sequential WAVES such that
        within a wave each kdr stream sits in a single key epoch
        r = index DIV kdr.  Unmapped rows (stream<0) and kdr=0 streams
        ride wave 0 untouched.  Returns (waves, r): `waves` is None when
        one wave suffices (the common case — caller applies the epoch
        and processes the whole batch), else a list of row-index arrays
        to process in order, re-applying epochs before each.

        Pre-auth caveat: on the receive side the epoch comes from the
        index ESTIMATE (keys must exist before tags can be checked —
        inherent to the RFC); forged wild seqs can thrash the epoch, but
        derivation is deterministic from the retained master key, so the
        next genuine batch re-derives correctly.
        """
        n = len(stream)
        valid = (stream >= 0) & (stream < self.capacity)
        kdr = np.where(valid, self.kdr[np.clip(stream, 0,
                                               self.capacity - 1)], 0)
        active = kdr > 0
        r = np.where(active, idx // np.maximum(kdr, 1), 0)
        if not active.any():
            return None, r
        waves = []
        remaining = np.ones(n, dtype=bool)
        first_wave = True
        while remaining.any():
            act = np.nonzero(remaining & active)[0]
            wave = remaining & ~active if first_wave else                 np.zeros(n, dtype=bool)
            if len(act):
                s_act = stream[act]
                uniq, first_pos = np.unique(s_act, return_index=True)
                fr = np.full(self.capacity, -1, dtype=np.int64)
                fr[uniq] = r[act[first_pos]]
                wave[act[r[act] == fr[s_act]]] = True
            waves.append(np.nonzero(wave)[0])
            remaining &= ~wave
            first_wave = False
        if len(waves) == 1:
            return None, r
        return waves, r

    def _apply_epochs(self, stream: np.ndarray, r: np.ndarray,
                      rtcp: bool) -> None:
        """Re-derive session keys for any kdr stream whose stored epoch
        differs from its rows' (single) epoch in this wave."""
        valid = (stream >= 0) & (stream < self.capacity)
        kdr = np.where(valid, self.kdr[np.clip(stream, 0,
                                               self.capacity - 1)], 0)
        act = np.nonzero(kdr > 0)[0]
        if not len(act):
            return
        p = self.policy
        uniq, first_pos = np.unique(stream[act], return_index=True)
        epochs = (self._epoch_rtcp if rtcp else self._epoch_rtp)
        for sid, ri in zip(uniq.tolist(),
                           r[act[first_pos]].tolist()):
            if ri == epochs[sid] or sid not in self._masters:
                continue
            mk, ms = self._masters[sid]
            kd = int(self.kdr[sid])
            # the other plane (RTP vs RTCP) keeps ITS stored epoch —
            # both planes' keys are reinstalled in one shot
            r_rtp = ri if not rtcp else int(self._epoch_rtp[sid])
            r_rtcp = ri if rtcp else int(self._epoch_rtcp[sid])
            ks = derive_session_keys(
                mk, ms, enc_key_len=p.enc_key_len,
                auth_key_len=p.auth_key_len, salt_len=p.salt_len,
                kdr=kd, index=r_rtp * kd, srtcp_index=r_rtcp * kd)
            self._install_session_keys(sid, ks)
            epochs[sid] = ri

    @staticmethod
    def _merge_row_results(total: int, parts):
        """Merge [(rows, PacketBatch, ok_or_None, idx_or_None)] back into
        one (batch, ok, idx) preserving row order (shared by the four
        epoch-wave call sites)."""
        need = max(o.capacity for _, o, _, _ in parts)
        out = PacketBatch.empty(total, need)
        ok = np.zeros(total, dtype=bool)
        idx = np.zeros(total, dtype=np.int64)
        for rows, o, okp, idxp in parts:
            out.data[rows, :o.capacity] = o.data
            out.length[rows] = o.length
            out.stream[rows] = o.stream
            if okp is not None:
                ok[rows] = okp
            if idxp is not None:
                idx[rows] = idxp
        return out, ok, idx

    def _estimate_rx_indices(self, stream: np.ndarray,
                             seq: np.ndarray) -> np.ndarray:
        """Receive-side 48-bit index estimation.  Established streams:
        RFC 3711 App A estimate against the last *authenticated* state,
        exactly like the reference's guessIndex — immune to forged
        packets earlier in the same batch.  Fresh streams (no
        authenticated packet yet): chain within the batch so a seq wrap
        right after the random initial seq still indexes correctly."""
        base = self.rx_max[np.maximum(stream, 0)]
        s_l = np.where(base >= 0, base & 0xFFFF, -1)
        roc = np.where(base >= 0, base >> 16, 0)
        _, idx_est = estimate_packet_index(seq, s_l, roc)
        idx_chain = chain_packet_indices(stream, seq, self.rx_max)
        return np.where(base >= 0, idx_est, idx_chain)

    def remove_stream(self, sid: int) -> None:
        self.remove_streams([sid])

    def remove_streams(self, sids) -> None:
        """Vectorized bulk teardown: `remove_stream` for many rows in
        one pass — the evict half of the lifecycle plane.

        Key material is zeroed (a recycled row must never authenticate
        under a departed stream's keys) and ALL sequential state is
        reset so the row is immediately reusable by a future
        add_stream/add_streams with no leftover replay window, rollover
        counter, or kdr epoch.  The whole batch pays ONE copy-on-write
        table copy instead of one per stream, so a join/leave storm
        evicting hundreds of streams costs the same table copy a single
        evict does.
        """
        sids = np.asarray(sids, dtype=np.int64)
        if sids.size == 0:
            return
        self.active[sids] = False
        self._cow_tables()
        self._rk_rtp[sids] = 0
        self._rk_rtcp[sids] = 0
        self._mid_rtp[sids] = 0
        self._mid_rtcp[sids] = 0
        if self._gcm:
            self._gm_rtp[sids] = 0
            self._gm_rtcp[sids] = 0
        if self._f8:
            self._rk_f8_rtp[sids] = 0
            self._rk_f8_rtcp[sids] = 0
        self._salt_rtp[sids] = 0
        self._salt_rtcp[sids] = 0
        for sid in sids:
            self._masters.pop(int(sid), None)
        self.tx_ext[sids] = -1
        self.rx_max[sids] = -1
        self.rx_mask[sids] = 0
        self.rtcp_tx_index[sids] = -1
        self.rtcp_rx_max[sids] = -1
        self.rtcp_rx_mask[sids] = 0
        self.kdr[sids] = 0
        self.auth_fail[sids] = 0
        self.replay_reject[sids] = 0
        self._epoch_rtp[sids] = 0
        self._epoch_rtcp[sids] = 0
        self._dev = None
        if self._ks_cache is not None:
            self._ks_cache.forget(sids)

    def move_rows(self, src_sids, dst_sids) -> None:
        """Relocate live streams to new rows BIT-EXACT — the crypto half
        of a placement rebalance (mesh/placement.py): a conference
        migrating to another shard carries every row's keys, rollover
        counters, replay windows and kdr epochs unchanged, so no packet
        in flight before the move authenticates differently after it.

        One copy-on-write episode for the whole batch, and the source
        rows are torn down through `remove_streams`'s zeroing discipline
        (a vacated row must not keep departed key material).  Callers
        sequence this between ticks behind the lifecycle commit barrier.
        """
        src = np.asarray(src_sids, dtype=np.int64)
        dst = np.asarray(dst_sids, dtype=np.int64)
        if src.size != dst.size:
            raise ValueError("src/dst length mismatch")
        if src.size == 0:
            return
        if not self.active[src].all():
            raise ValueError("cannot move inactive rows")
        if self.active[dst].any():
            raise ValueError("destination rows occupied")
        self._cow_tables()
        for tab in (self._rk_rtp, self._rk_rtcp, self._mid_rtp,
                    self._mid_rtcp, self._salt_rtp, self._salt_rtcp,
                    self.tx_ext, self.rx_max, self.rx_mask,
                    self.rtcp_tx_index, self.rtcp_rx_max,
                    self.rtcp_rx_mask, self.auth_fail,
                    self.replay_reject, self.kdr, self._epoch_rtp,
                    self._epoch_rtcp):
            tab[dst] = tab[src]
        if self._gcm:
            self._gm_rtp[dst] = self._gm_rtp[src]
            self._gm_rtcp[dst] = self._gm_rtcp[src]
        if self._f8:
            self._rk_f8_rtp[dst] = self._rk_f8_rtp[src]
            self._rk_f8_rtcp[dst] = self._rk_f8_rtcp[src]
        for s, d in zip(src, dst):
            m = self._masters.pop(int(s), None)
            if m is not None:
                self._masters[int(d)] = m
        self.active[dst] = True
        if self._ks_cache is not None:
            # dst inherits src's served high-water: the material is the
            # same keys under a new row id, and never-serve-twice must
            # keep holding across the rename
            self._ks_cache.move(src, dst)
        # masters already relocated; remove_streams zeroes the rest
        self.remove_streams(src)

    def _device(self):
        if self._dev is None:
            aux_rtp = self._gm_rtp if self._gcm else self._mid_rtp
            aux_rtcp = self._gm_rtcp if self._gcm else self._mid_rtcp
            self._dev = (
                jnp.asarray(self._rk_rtp), jnp.asarray(aux_rtp),
                jnp.asarray(self._rk_rtcp), jnp.asarray(aux_rtcp),
            )
            if self._f8:
                self._dev_f8 = (jnp.asarray(self._rk_f8_rtp),
                                jnp.asarray(self._rk_f8_rtcp))
            self._aliased = True
        return self._dev

    def _require_active(self, stream: np.ndarray) -> None:
        """Protect-path guard: every row must map to an installed stream.

        Unmapped rows (stream=-1, the PacketBatch default) would otherwise
        wrap via negative indexing and corrupt another row's tx state; the
        reference throws for a missing forward context likewise.
        """
        bad = (stream < 0) | (stream >= self.capacity) | ~self.active[
            np.clip(stream, 0, self.capacity - 1)]
        if np.any(bad):
            raise KeyError(
                f"protect on unmapped/inactive stream ids "
                f"{np.unique(stream[bad]).tolist()}")

    # ------------------------------------------------------------------ IVs
    def _cm_iv(self, salt16: np.ndarray, ssrc: np.ndarray,
               index: np.ndarray) -> np.ndarray:
        """RFC 3711 §4.1.1: IV = (salt << 16) ^ (ssrc << 64) ^ (index << 16)."""
        iv = salt16.copy()
        ssrc = np.asarray(ssrc, dtype=np.int64)
        index = np.asarray(index, dtype=np.int64)
        for k in range(4):
            iv[:, 4 + k] ^= ((ssrc >> (8 * (3 - k))) & 0xFF).astype(np.uint8)
        for k in range(6):
            iv[:, 8 + k] ^= ((index >> (8 * (5 - k))) & 0xFF).astype(np.uint8)
        return iv

    @staticmethod
    def _f8_rtp_iv(hdr, roc: np.ndarray) -> np.ndarray:
        """RFC 3711 §4.1.2.1: IV = 0x00 || M,PT || SEQ || TS || SSRC || ROC."""
        n = len(hdr.seq)
        iv = np.zeros((n, 16), dtype=np.uint8)
        iv[:, 1] = ((np.asarray(hdr.marker) << 7) | np.asarray(hdr.pt)
                    ).astype(np.uint8)
        iv[:, 2] = (hdr.seq >> 8) & 0xFF
        iv[:, 3] = hdr.seq & 0xFF
        ts = np.asarray(hdr.ts, dtype=np.int64)
        ssrc = np.asarray(hdr.ssrc, dtype=np.int64)
        roc = np.asarray(roc, dtype=np.int64)
        for k in range(4):
            sh = 8 * (3 - k)
            iv[:, 4 + k] = (ts >> sh) & 0xFF
            iv[:, 8 + k] = (ssrc >> sh) & 0xFF
            iv[:, 12 + k] = (roc >> sh) & 0xFF
        return iv

    @staticmethod
    def _f8_rtcp_iv(data: np.ndarray, index_word: np.ndarray) -> np.ndarray:
        """RFC 3711 §4.1.2.4: IV = 0..0(32) || E||index || first 8 bytes of
        the RTCP packet (V,P,RC,PT,length,SSRC)."""
        n = len(index_word)
        iv = np.zeros((n, 16), dtype=np.uint8)
        w = np.asarray(index_word, dtype=np.int64)
        for k in range(4):
            iv[:, 4 + k] = (w >> (8 * (3 - k))) & 0xFF
        iv[:, 8:16] = data[:, :8]
        return iv

    def _gcm_rtp_iv(self, salt: np.ndarray, ssrc: np.ndarray,
                    index: np.ndarray) -> np.ndarray:
        """RFC 7714 §8.1: IV = (00 00 || SSRC || ROC || SEQ) XOR salt."""
        return gcm_kernel.srtp_gcm_iv(salt, ssrc, index)

    def _gcm_rtcp_iv(self, salt: np.ndarray, ssrc: np.ndarray,
                     index: np.ndarray) -> np.ndarray:
        """RFC 7714 §9.1: IV = (00 00 || SSRC || 00 00 || index) XOR salt."""
        iv = salt[:, :12].copy()
        ssrc = np.asarray(ssrc, dtype=np.int64)
        index = np.asarray(index, dtype=np.int64)
        for k in range(4):
            iv[:, 2 + k] ^= ((ssrc >> (8 * (3 - k))) & 0xFF).astype(np.uint8)
        for k in range(4):
            iv[:, 8 + k] ^= ((index >> (8 * (3 - k))) & 0xFF).astype(np.uint8)
        return iv

    # ------------------------------------------------------------------ RTP
    def protect_rtp(self, batch: PacketBatch) -> PacketBatch:
        """Encrypt + tag a batch of outgoing RTP (rows in send order).

        Mixed-size batches are split into width/row size classes at this
        device boundary (SURVEY §7): narrow rows run narrow kernels and
        the jit cache stays bounded.  Padding rows repeat a real row —
        state-safe here (duplicate index: tx max unchanged) — and are
        dropped on reassembly.
        Reference: SRTPTransformer.transform → SRTPCryptoContext.transformPacket.
        """
        if batch.batch_size == 0:
            return batch
        stream0 = np.asarray(batch.stream, dtype=np.int64)
        if self._kdr_active(stream0):
            hdr0 = rtp_header.parse(batch)
            idx0 = chain_packet_indices(stream0, hdr0.seq, self.tx_ext)
            waves, r = self._epoch_plan(stream0, idx0, rtcp=False)
            if waves is not None:
                # one pass per epoch wave, keys re-applied before each
                done = []
                for w in waves:
                    sub = self.protect_rtp(self._row_subset(batch, w))
                    done.append((w, sub, None, None))
                out, _, _ = self._merge_row_results(batch.batch_size, done)
                return out
            self._apply_epochs(stream0, r, rtcp=False)
        parts = bucket_by_size(batch, pad_rows=self._pads_rows)
        done = [(rows, self._protect_rtp_direct(part), n)
                for rows, part, n in parts]
        out, _ = unbucket(done, batch.batch_size, batch.capacity)
        return out

    def protect_rtp_async(self, batch: PacketBatch) -> "PendingProtect":
        """Dispatch-only protect: device work is enqueued and host TX
        state is fully updated, but results are NOT materialized —
        `.result()` does that.  This is the double-buffering seam
        (SURVEY §7 step 4's latency budget): dispatch batch N+1 while
        batch N's bytes are still in flight; protect's host state
        (chain index + tx max) depends only on inputs, so pipelining is
        state-safe at any depth, and key-table mutations while parts are
        pending are safe because every mutator goes through
        `_cow_tables` (in-flight kernels keep reading the old buffers).
        kdr re-keying batches fall back to the sync path (epoch waves
        are inherently sequential).
        """
        if batch.batch_size == 0:
            return PendingProtect([], 0, batch.capacity, done=batch)
        stream0 = np.asarray(batch.stream, dtype=np.int64)
        if self._kdr_active(stream0):
            return PendingProtect([], 0, batch.capacity,
                                  done=self.protect_rtp(batch))
        parts = bucket_by_size(batch, pad_rows=self._pads_rows)
        pend = [(rows, self._protect_rtp_dispatch(part), n)
                for rows, part, n in parts]
        return PendingProtect(pend, batch.batch_size, batch.capacity)

    def _protect_rtp_direct(self, batch: PacketBatch) -> PacketBatch:
        data, length, stream = self._protect_rtp_dispatch(batch)
        return PacketBatch(np.asarray(data),
                           np.asarray(length, dtype=np.int32), stream)

    def _protect_rtp_dispatch(self, batch: PacketBatch):
        """Device dispatch + host state update; returns device arrays
        (data, length) plus the stream ids, WITHOUT materializing."""
        hdr = rtp_header.parse(batch)
        stream = np.asarray(batch.stream, dtype=np.int64)
        self._require_active(stream)
        max_len = int(np.max(batch.length, initial=0))
        if max_len + self.policy.auth_tag_len > batch.capacity:
            raise ValueError(
                f"packet of {max_len}B + {self.policy.auth_tag_len}B tag "
                f"exceeds batch capacity {batch.capacity}")
        idx = chain_packet_indices(stream, hdr.seq, self.tx_ext)
        v = idx >> 16

        if self._gcm:
            out = (None if self._ks_cache is None
                   else self._gcm_rtp_protect_cached(stream, batch, hdr,
                                                     idx))
            if out is None:
                iv12 = self._gcm_rtp_iv(self._salt_rtp[stream],
                                        hdr.ssrc, idx)
                out = self._gcm_rtp_protect_call(stream, batch, hdr,
                                                 iv12)
            data, length = out
        elif self._f8:
            iv = self._f8_rtp_iv(hdr, v)
            data, length = self._f8_rtp_protect_call(stream, batch, hdr,
                                                     iv, v)
        else:
            iv = self._cm_iv(self._salt_rtp[stream], hdr.ssrc, idx)
            data, length = self._cm_rtp_protect_call(stream, batch, hdr,
                                                     iv, v)
        np.maximum.at(self.tx_ext, stream, idx)
        return data, length, batch.stream

    def _gcm_stage(self, stream, batch, hdr, iv12, length, grid):
        """The arguments of a GCM RTP call that does not pack, on the
        device, an array an argument: `protect_rtp` in either form, and
        the grouped unprotect, whose `grid` arrays have another shape
        than the rows.  Returns (device arguments behind the two key
        tables, what `staging.Launch` books for them: the arrays that
        crossed, their bytes, and the span's counts `gm_gather_bytes` —
        a 16 KiB GHASH matrix a padded row, or a group of the grid —
        and `grouped`)."""
        host = [np.asarray(stream, dtype=np.int32), batch.data,
                np.asarray(length, dtype=np.int32),
                np.asarray(hdr.payload_off, dtype=np.int32), iv12]
        groups = len(stream)
        if grid is not None:
            gr, us, inv = grid
            host += [gr, us.astype(np.int32), inv]
            groups = len(us)
        dev, n, nbytes = staging.put_each(host)
        return dev, {
            "h2d_arrays": n, "h2d_bytes": nbytes,
            "counts": {"gm_gather_bytes": groups * GM_BYTES,
                       "grouped": int(grid is not None)}}

    def _gcm_rtp_protect_call(self, stream, batch, hdr, iv12):
        """AEAD-GCM RTP protect device call — like the CM seam, the
        mesh table overrides exactly this (per-row form, row-local);
        single-chip takes grouped or per-row as `_gcm_form_grid` says of
        the call's shape."""
        aad_const = _uniform_off(hdr.payload_off, batch.capacity)
        tab_rk, tab_gm, _, _ = self._device()
        grid = _gcm_form_grid(stream)
        dev, _ = self._gcm_stage(stream, batch, hdr, iv12, batch.length,
                                 grid)
        fn = _protect_gcm_dev if grid is None else _protect_gcm_grouped_dev
        return fn(tab_rk, tab_gm, *dev, aad_const=aad_const)

    def _gcm_rtp_unprotect_call(self, stream, batch, hdr, iv12, length
                                ) -> staging.Launch:
        """AEAD-GCM RTP unprotect seam — see `_gcm_rtp_protect_call`.

        The per-row form (all a served table launches) takes the part
        as the CM seam does: stream, length, payload offset and the
        12-byte IV are packed into `batch.plane` behind the packet
        bytes, ONE array goes to the device, donated off the CPU, and
        one plane comes back.  The grouped form stages an array an
        argument (`_gcm_stage`).  Returns the `staging.Launch` in
        flight, whose `fetch()` gives host (data, media_len, auth_ok)
        and whose counts are of the arrays that crossed."""
        aad_const = _uniform_off(hdr.payload_off, batch.capacity)
        tab_rk, tab_gm, _, _ = self._device()
        grid = _gcm_form_grid(stream)
        if grid is None:
            plane = batch.plane
            staging.pack(plane, (stream, length, hdr.payload_off), iv12)
            out = _unprotect_gcm_dev_call(
                tab_rk, tab_gm, staging.put(plane), aad_const=aad_const)
            return staging.Launch(
                (out,), _split_unprotect, h2d_arrays=1,
                h2d_bytes=plane.nbytes,
                counts={"gm_gather_bytes": len(stream) * GM_BYTES,
                        "grouped": 0})
        dev, staged = self._gcm_stage(stream, batch, hdr, iv12, length,
                                      grid)
        return staging.Launch(
            _unprotect_gcm_grouped_dev(tab_rk, tab_gm, *dev,
                                       aad_const=aad_const),
            _split_unpacked, **staged)

    def _gcm_grid_dev(self, stream):
        """(stream_dev, grid_dev-or-None) for this batch's stream
        pattern, memoized by the pattern bytes; the grid is None where
        `_gcm_form_grid` picks the per-row form.  Purely positional —
        the grid groups row indices by equal stream values — so rekey /
        forget / move never invalidate it; only a different batch
        composition does, and those are rare tick-over-tick.  The memo
        is keyed by PUBLIC wire data only (stream-id positions), so
        host branching on it is taint-clean."""
        pat = stream.tobytes()
        hit = self._grid_memo.get(pat)
        if hit is None:
            sdev = jnp.asarray(stream, dtype=jnp.int32)
            grid = _gcm_form_grid(stream)
            if grid is not None:
                gr, us, inv = grid
                grid = (jnp.asarray(gr), jnp.asarray(us, dtype=jnp.int32),
                        jnp.asarray(inv))
            if len(self._grid_memo) >= 64:
                self._grid_memo.clear()
            hit = self._grid_memo[pat] = (sdev, grid)
        return hit

    def _gcm_rtp_protect_cached(self, stream, batch, hdr, idx):
        """Keystream-cache fast path for protect: on an all-rows window
        hit, run the fused XOR + GHASH kernel on pregenerated keystream
        and tag-mask rows — no AES launch on the tick.  Returns None on
        any miss (reorder beyond window, consumed slot, non-uniform
        AAD, unknown SSRC, oversize payload) and the stock seam runs
        bit-exactly instead."""
        aad_const = _uniform_off(hdr.payload_off, batch.capacity)
        length = np.asarray(batch.length, dtype=np.int64)
        ct_len = length - (aad_const if aad_const is not None else 0)
        got = self._ks_cache.claim(stream, hdr.ssrc, idx, ct_len,
                                   aad_const is not None)
        if got is None:
            return None
        ks_tab, ek_tab, slot = got
        _, tab_gm, _, _ = self._device()
        sdev, grid = self._gcm_grid_dev(stream)
        if grid is not None:
            gr, us, inv = grid
            return _protect_gcm_cached_grouped_dev(
                ks_tab, ek_tab, jnp.asarray(slot), tab_gm, sdev,
                jnp.asarray(batch.data), jnp.asarray(batch.length),
                gr, us, inv, aad_const=aad_const)
        return _protect_gcm_cached_dev(
            ks_tab, ek_tab, jnp.asarray(slot), tab_gm, sdev,
            jnp.asarray(batch.data), jnp.asarray(batch.length),
            aad_const=aad_const)

    def _gcm_rtp_unprotect_cached(self, stream, batch, hdr, idx, length):
        """Keystream-cache fast path for unprotect; returns (data,
        media_len, auth_ok) or None on miss — see
        `_gcm_rtp_protect_cached`.  The claimed slots are consumed even
        if authentication later fails: a corrupted packet must not
        leave its slot claimable by a replayed twin."""
        aad_const = _uniform_off(hdr.payload_off, batch.capacity)
        ct_len = (np.asarray(length, dtype=np.int64) - gcm_kernel.TAG_LEN
                  - (aad_const if aad_const is not None else 0))
        got = self._ks_cache.claim(stream, hdr.ssrc, idx, ct_len,
                                   aad_const is not None)
        if got is None:
            return None
        ks_tab, ek_tab, slot = got
        _, tab_gm, _, _ = self._device()
        sdev, grid = self._gcm_grid_dev(stream)
        if grid is not None:
            gr, us, inv = grid
            return _unprotect_gcm_cached_grouped_dev(
                ks_tab, ek_tab, jnp.asarray(slot), tab_gm, sdev,
                jnp.asarray(batch.data), jnp.asarray(length),
                gr, us, inv, aad_const=aad_const)
        return _unprotect_gcm_cached_dev(
            ks_tab, ek_tab, jnp.asarray(slot), tab_gm, sdev,
            jnp.asarray(batch.data), jnp.asarray(length),
            aad_const=aad_const)

    def _f8_rtp_protect_call(self, stream, batch, hdr, iv, v):
        """AES-F8 RTP protect device call — like the CM seam, the mesh
        table overrides exactly this (the second key schedule shards on
        the same row partition as the first)."""
        tab_rk, tab_mid, _, _ = self._device()
        return _protect_rtp_dev(
            tab_rk, tab_mid, jnp.asarray(stream, dtype=jnp.int32),
            jnp.asarray(batch.data), jnp.asarray(batch.length),
            jnp.asarray(hdr.payload_off), jnp.asarray(iv),
            jnp.asarray(v & 0xFFFFFFFF, dtype=jnp.uint32),
            self.policy.auth_tag_len, True,
            off_const=_uniform_off(hdr.payload_off, batch.capacity),
            tab_f8=self._dev_f8[0])

    def _f8_rtp_unprotect_call(self, stream, batch, hdr, iv, v, length):
        """AES-F8 RTP unprotect device call (see _f8_rtp_protect_call);
        returns (data, media_len, auth_ok)."""
        tab_rk, tab_mid, _, _ = self._device()
        return _unprotect_rtp_dev_call(
            tab_rk, tab_mid, jnp.asarray(stream, dtype=jnp.int32),
            jnp.asarray(batch.data), jnp.asarray(length),
            jnp.asarray(hdr.payload_off), jnp.asarray(iv),
            jnp.asarray(v & 0xFFFFFFFF, dtype=jnp.uint32),
            self.policy.auth_tag_len, True,
            off_const=_uniform_off(hdr.payload_off, batch.capacity),
            tab_f8=self._dev_f8[0])

    def _cm_rtp_protect_call(self, stream, batch, hdr, iv, v):
        """AES-CM/NULL RTP protect device call — the mesh table
        (mesh/table.py) overrides exactly this seam with a shard_map
        over row-partitioned key tables; the host plane above is
        shared verbatim."""
        tab_rk, tab_mid, _, _ = self._device()
        return _protect_rtp_dev(
            tab_rk, tab_mid, jnp.asarray(stream, dtype=jnp.int32),
            jnp.asarray(batch.data), jnp.asarray(batch.length),
            jnp.asarray(hdr.payload_off), jnp.asarray(iv),
            jnp.asarray(v & 0xFFFFFFFF, dtype=jnp.uint32),
            self.policy.auth_tag_len, self.policy.cipher != Cipher.NULL,
            off_const=_uniform_off(hdr.payload_off, batch.capacity))

    def _cm_rtp_unprotect_call(self, stream, batch, hdr, iv, v, length
                               ) -> staging.Launch:
        """AES-CM/NULL RTP unprotect device call — the seam the mesh
        table overrides (see _cm_rtp_protect_call).

        Takes the part as `bucket_by_size(tail=staging.TAIL)` made it:
        `batch.plane` holds the packet bytes and room behind them, into
        which stream, length, payload offset, ROC (`v` mod 2**32) and
        IV are packed here; ONE array goes to the device and one plane
        comes back.  Returns the `staging.Launch` in flight, whose
        `fetch()` gives host arrays (data, media_len, auth_ok)."""
        p = self.policy
        tab_rk, tab_mid, _, _ = self._device()
        plane = batch.plane
        staging.pack(plane, (stream, length, hdr.payload_off,
                             v & 0xFFFFFFFF), iv)
        fn = (_unprotect_rtp_packed_donated if _donate_ingest()
              else _unprotect_rtp_packed)
        out = fn(tab_rk, tab_mid, staging.put(plane), p.auth_tag_len,
                 p.cipher != Cipher.NULL,
                 off_const=_uniform_off(hdr.payload_off, batch.capacity))
        return staging.Launch((out,), _split_unprotect, h2d_arrays=1,
                              h2d_bytes=plane.nbytes)

    def _unpacked_launch(self, out, batch, length, iv) -> staging.Launch:
        """An F8 call's or a keystream-cache hit's (data, media_len,
        auth_ok) behind the packed seams' face: those calls stage data,
        lengths and IVs as they are and stream, payload offset and ROC
        as a word a row each, booked by that formula.  (The stock CM
        and per-row GCM calls pack one plane; the grouped GCM call
        counts its arrays: `_gcm_rtp_unprotect_call`.)"""
        return staging.Launch(
            out, _split_unpacked, h2d_arrays=6,
            h2d_bytes=batch.data.nbytes + length.nbytes
            + (0 if iv is None else iv.nbytes) + 12 * batch.batch_size)

    def _part_tail(self) -> int:
        """Room `bucket_by_size` leaves behind an unprotect part's
        bytes: the CM and the per-row GCM call pack their arguments
        there."""
        return 0 if self._f8 else staging.TAIL

    def unprotect_rtp(self, batch: PacketBatch, return_index: bool = False):
        """Auth-check, replay-check and decrypt incoming RTP.

        Returns (batch', ok) — or (batch', ok, index) with the estimated
        48-bit packet indices when `return_index` (the SFU translator
        re-uses the authenticated sender index for every fan-out leg).
        Rows with ok=False keep their original bytes (the reference drops
        them; callers filter by the mask).

        Size-class bucketed like protect_rtp; the repeated padding rows
        are exact duplicates, which the replay dedup kills while the real
        copy (earlier in its sub-batch) survives.
        Reference: SRTPTransformer.reverseTransform →
        SRTPCryptoContext.reverseTransformPacket.
        """
        self._commit_inflight_unprotect()
        if batch.batch_size == 0:
            ok0 = np.zeros(0, dtype=bool)
            if return_index:
                return batch, ok0, np.zeros(0, dtype=np.int64)
            return batch, ok0
        stream0 = np.asarray(batch.stream, dtype=np.int64)
        if self._kdr_active(stream0):
            hdr0 = rtp_header.parse(batch)
            idx0 = self._estimate_rx_indices(stream0, hdr0.seq)
            waves, r = self._epoch_plan(stream0, idx0, rtcp=False)
            if waves is not None:
                done = []
                for w in waves:
                    o, okp, idxp = self.unprotect_rtp(
                        self._row_subset(batch, w), True)
                    done.append((w, o, okp, idxp))
                out, ok, idx = self._merge_row_results(batch.batch_size,
                                                       done)
                if return_index:
                    return out, ok, idx
                return out, ok
            self._apply_epochs(stream0, r, rtcp=False)
        # the padding to the row and width classes and the reassembly
        # are host work round the device call: `unprotect_host`, like
        # the two host stretches inside `_unprotect_rtp_direct` (the
        # leaves are entered once per size class and sum)
        with span_of(self.tracer, "unprotect_host",
                     rows=batch.batch_size):
            parts = bucket_by_size(batch, tail=self._part_tail(),
                                   pad_rows=self._pads_rows)
        done, masks = [], []
        idx_parts = []
        for rows, part, n in parts:
            o, okp, idxp = self._unprotect_rtp_direct(part, True, n)
            done.append((rows, o, n))
            masks.append(np.asarray(okp))
            idx_parts.append((rows, idxp[:n]))
        with span_of(self.tracer, "unprotect_host"):
            out, ok = unbucket(done, batch.batch_size, batch.capacity,
                               masks)
            # ok=False rows keep their original bytes (contract above)
            out.data[~ok, :] = 0
            take = min(out.capacity, batch.capacity)
            out.data[~ok, :take] = batch.data[~ok, :take]
            out.length[~ok] = np.asarray(batch.length)[~ok]
            idx = np.zeros(batch.batch_size, dtype=np.int64)
            for rows, idxp in idx_parts:
                idx[rows] = idxp
        if return_index:
            return out, ok, idx
        return out, ok

    def unprotect_rtp_async(self, batch: PacketBatch,
                            return_index: bool = False
                            ) -> "PendingUnprotect":
        """Dispatch-only unprotect: device auth/decrypt is enqueued,
        results are NOT materialized — the deep-pipelined receive seam
        (the launch overlaps the next recv window).

        Unlike protect, unprotect's host state (replay window, failure
        counters) depends on the device verdicts, so NOTHING host-side
        commits at dispatch; `PendingUnprotect.commit()` does, and the
        table force-commits the outstanding pending before any new
        unprotect (sync or async), any key-table mutation
        (`_cow_tables`) and any snapshot — so successive windows always
        replay-check against a current window, in dispatch order.  kdr
        epoch batches fall back to the sync path (inherently
        sequential).  `.result()` returns (batch, ok[, index]) exactly
        like `unprotect_rtp`; failed rows keep their original bytes,
        which means `batch` (possibly a recv-arena view) is read again
        at materialization time — arena callers keep it pinned until
        then.
        """
        self._commit_inflight_unprotect()
        if batch.batch_size == 0:
            ok0 = np.zeros(0, dtype=bool)
            done = ((batch, ok0, np.zeros(0, dtype=np.int64))
                    if return_index else (batch, ok0))
            return PendingUnprotect(self, [], batch, return_index,
                                    done=done)
        stream0 = np.asarray(batch.stream, dtype=np.int64)
        if self._kdr_active(stream0):
            done = self.unprotect_rtp(batch, return_index)
            return PendingUnprotect(self, [], batch, return_index,
                                    done=done)
        parts = bucket_by_size(batch, tail=self._part_tail(),
                                   pad_rows=self._pads_rows)
        pend = [(rows, self._unprotect_rtp_dispatch(part), n)
                for rows, part, n in parts]
        p = PendingUnprotect(self, pend, batch, return_index)
        self._inflight_unprotect = p
        return p

    def _unprotect_rtp_dispatch(self, batch: PacketBatch) -> dict:
        """Per-part device dispatch for the async unprotect: header
        parse, index estimation and the device call — no host RX state
        is read beyond `rx_max` (index estimation, current thanks to
        the commit barrier) and none is written."""
        p = self.policy
        hdr = rtp_header.parse(batch)
        stream = np.asarray(batch.stream, dtype=np.int64)
        length = np.asarray(batch.length, dtype=np.int32)
        valid = ((hdr.version == 2)
                 & (length >= hdr.header_len + p.auth_tag_len)
                 & self.active[stream] & (stream >= 0))
        idx = self._estimate_rx_indices(stream, hdr.seq)
        v = idx >> 16
        if self._gcm:
            out = (None if self._ks_cache is None
                   else self._gcm_rtp_unprotect_cached(stream, batch,
                                                       hdr, idx, length))
            if out is None:
                launch = self._gcm_rtp_unprotect_call(
                    stream, batch, hdr,
                    self._gcm_rtp_iv(self._salt_rtp[stream], hdr.ssrc,
                                     idx), length)
            else:
                launch = self._unpacked_launch(out, batch, length, None)
        elif self._f8:
            iv = self._f8_rtp_iv(hdr, v)
            launch = self._unpacked_launch(
                self._f8_rtp_unprotect_call(stream, batch, hdr, iv, v,
                                            length), batch, length, iv)
        else:
            iv = self._cm_iv(self._salt_rtp[stream], hdr.ssrc, idx)
            launch = self._cm_rtp_unprotect_call(
                stream, batch, hdr, iv, v, length)
        return {"part": batch, "stream": stream, "length": length,
                "valid": valid, "idx": idx, "launch": launch}

    def _unprotect_rtp_direct(self, batch: PacketBatch,
                              return_index: bool = False,
                              n_real: Optional[int] = None):
        """One size class: host, device, host.  `n_real` of the
        batch's rows are packets, the rest pad the row class."""
        p = self.policy
        tracer = self.tracer
        with span_of(tracer, "unprotect_host"):
            hdr = rtp_header.parse(batch)
            stream = np.asarray(batch.stream, dtype=np.int64)
            length = np.asarray(batch.length, dtype=np.int32)
            # NOTE: hdr.valid is deliberately not used here — its
            # padding-length sanity check reads the last byte, which at
            # this point is still ciphertext/tag; padded packets would
            # be dropped at random.
            valid = ((hdr.version == 2)
                     & (length >= hdr.header_len + p.auth_tag_len)
                     & self.active[stream] & (stream >= 0))

            idx = self._estimate_rx_indices(stream, hdr.seq)
            v = idx >> 16
            not_replayed = replay.check(self.rx_max, self.rx_mask, stream,
                                        idx)
            if self._gcm:
                # the keystream cache builds its own; a miss needs ours
                iv = (self._gcm_rtp_iv(self._salt_rtp[stream], hdr.ssrc,
                                       idx)
                      if self._ks_cache is None else None)
            elif self._f8:
                iv = self._f8_rtp_iv(hdr, v)
            else:
                iv = self._cm_iv(self._salt_rtp[stream], hdr.ssrc, idx)

        # from the staging of the arguments to the outputs as host
        # arrays: what the tick thread spends round the device call, in
        # the four phases every seam books (`unprotect_put` opens
        # inside the call: core/staging.py)
        with span_of(tracer, "unprotect_wait",
                     rows=batch.batch_size if n_real is None else n_real,
                     rows_padded=batch.batch_size) as sp:
            with staging.dispatch(tracer, "unprotect"):
                if self._gcm:
                    out = (None if self._ks_cache is None
                           else self._gcm_rtp_unprotect_cached(
                               stream, batch, hdr, idx, length))
                    if out is None:
                        if iv is None:
                            iv = self._gcm_rtp_iv(self._salt_rtp[stream],
                                                  hdr.ssrc, idx)
                        launch = self._gcm_rtp_unprotect_call(
                            stream, batch, hdr, iv, length)
                    else:
                        launch = self._unpacked_launch(out, batch, length,
                                                       iv)
                elif self._f8:
                    launch = self._unpacked_launch(
                        self._f8_rtp_unprotect_call(stream, batch, hdr, iv,
                                                    v, length),
                        batch, length, iv)
                else:
                    launch = self._cm_rtp_unprotect_call(
                        stream, batch, hdr, iv, v, length)
            with span_of(tracer, "unprotect_block"):
                # this seam fetched with no wait before it (until PR
                # 37), and `np.asarray` of an output in flight asks for
                # the copy first: kept, or the copy back would start
                # only once this thread had woken (0.18 ms a call on
                # the v5e, PERF.md, PR 37)
                launch.copy_back_async().block_until_ready()
            with span_of(tracer, "unprotect_d2h") as d2h:
                data, mlen, auth_ok = launch.fetch()
                d2h.note(d2h_arrays=launch.d2h_arrays,
                         d2h_bytes=launch.d2h_bytes, **launch.d2h_counts)
            sp.note(h2d_arrays=launch.h2d_arrays,
                    h2d_bytes=launch.h2d_bytes,
                    d2h_arrays=launch.d2h_arrays,
                    d2h_bytes=launch.d2h_bytes, **launch.counts)

        with span_of(tracer, "unprotect_host"):
            srow = np.clip(stream, 0, self.capacity - 1)
            np.add.at(self.auth_fail, srow,
                      valid & not_replayed & ~auth_ok)
            np.add.at(self.replay_reject, srow, valid & ~not_replayed)
            ok = valid & not_replayed & auth_ok
            # in-batch duplicate indices: keep the first *authenticated*
            # occurrence (a forged front-runner fails auth and must not
            # block the genuine copy later in the batch)
            ok &= ~replay.dedup_first(stream, idx, ok)
            replay.update(self.rx_max, self.rx_mask, stream, idx, ok)

            out_data = np.where(ok[:, None], data, batch.data)
            out_len = np.where(ok, mlen, length).astype(np.int32)
            out = PacketBatch(out_data, out_len, batch.stream)
        if return_index:
            return out, ok, idx
        return out, ok

    # ----------------------------------------------------------------- RTCP
    def protect_rtcp(self, batch: PacketBatch) -> PacketBatch:
        """Encrypt + index + tag outgoing compound RTCP.

        Reference: SRTCPTransformer.transform → SRTCPCryptoContext.
        SRTCP index is assigned sequentially per stream, E-bit set when the
        session encrypts (RFC 3711 §3.4).
        """
        stream = np.asarray(batch.stream, dtype=np.int64)
        self._require_active(stream)
        max_len = int(np.max(batch.length, initial=0))
        if max_len + 4 + self.policy.auth_tag_len > batch.capacity:
            raise ValueError(
                f"packet of {max_len}B + index/tag exceeds capacity "
                f"{batch.capacity}")
        # per-stream sequential index assignment, stable in batch order
        index = self.rtcp_tx_index[stream] + 1 + segment_ranks(stream)
        if self._kdr_active(stream):
            waves, r = self._epoch_plan(stream, index, rtcp=True)
            if waves is not None:
                done = []
                for w in waves:
                    sub = self.protect_rtcp(self._row_subset(batch, w))
                    done.append((w, sub, None, None))
                out, _, _ = self._merge_row_results(batch.batch_size, done)
                return out
            self._apply_epochs(stream, r, rtcp=True)
        ssrc = rtp_header.read_u32(batch.data, 4)
        if self._gcm:
            out = self._protect_rtcp_gcm(batch, stream, ssrc, index)
            np.maximum.at(self.rtcp_tx_index, stream, index)
            return out
        encrypting = self.policy.cipher != Cipher.NULL
        e = np.int64(1 << 31) if encrypting else np.int64(0)
        index_word = index | e

        if self._f8:
            iv = self._f8_rtcp_iv(batch.data, index_word)
            enc_flag, f8 = True, True
        else:
            iv = self._cm_iv(self._salt_rtcp[stream], ssrc, index)
            enc_flag, f8 = encrypting, False
        n = batch.batch_size
        pad = self._rtcp_pad(n)
        if pad is None:
            data, length = self._rtcp_protect_call(
                stream, batch, iv, index_word, enc_flag, f8=f8)
        else:
            data, length = self._rtcp_protect_call(
                stream[pad],
                PacketBatch(batch.data[pad],
                            np.asarray(batch.length)[pad],
                            np.asarray(batch.stream)[pad]),
                iv[pad], index_word[pad], enc_flag, f8=f8)
            data = np.asarray(data)[:n]
            length = np.asarray(length)[:n]
        np.maximum.at(self.rtcp_tx_index, stream, index)
        return PacketBatch(np.asarray(data), np.asarray(length, dtype=np.int32),
                           batch.stream)

    def _rtcp_protect_call(self, stream, batch, iv, index_word,
                           encrypting: bool, f8: bool = False):
        """SRTCP protect device call (CM/NULL/F8) — the mesh table
        overrides this seam too: a mesh deployment must not silently
        hop to a single-chip path for control traffic."""
        _, _, tab_rk, tab_mid = self._device()
        return _protect_rtcp_dev(
            tab_rk, tab_mid, jnp.asarray(stream, dtype=jnp.int32),
            jnp.asarray(batch.data), jnp.asarray(batch.length),
            jnp.asarray(iv), jnp.asarray(index_word),
            self.policy.auth_tag_len, encrypting,
            tab_f8=self._dev_f8[1] if f8 else None)

    def _rtcp_unprotect_call(self, stream, batch, iv, length,
                             encrypting: bool, f8: bool = False):
        """SRTCP unprotect device call (CM/NULL/F8); returns
        (data, media_len, auth_ok, e_bit, index)."""
        _, _, tab_rk, tab_mid = self._device()
        return _unprotect_rtcp_dev(
            tab_rk, tab_mid, jnp.asarray(stream, dtype=jnp.int32),
            jnp.asarray(batch.data), jnp.asarray(length),
            jnp.asarray(iv), self.policy.auth_tag_len, encrypting,
            tab_f8=self._dev_f8[1] if f8 else None)

    def _gcm_rtcp_seal_call(self, stream, kin, klen, iv12):
        """AEAD-GCM SRTCP seal device call on the kernel-layout buffer
        (hdr8 || ESRTCP word || plaintext) — mesh overrides this seam
        with the RTCP tables sharded on the same row partition."""
        tab_rk, tab_aux = self._device()[2], self._device()[3]
        n = len(klen)
        return _protect_gcm_dev(
            tab_rk, tab_aux, jnp.asarray(stream, dtype=jnp.int32),
            jnp.asarray(kin), jnp.asarray(klen, dtype=jnp.int32),
            jnp.asarray(np.full(n, 12, np.int32)), jnp.asarray(iv12),
            aad_const=12)

    def _gcm_rtcp_open_call(self, stream, kin, klen, iv12):
        """AEAD-GCM SRTCP open device call (see _gcm_rtcp_seal_call);
        returns (data, media_len, auth_ok)."""
        tab_rk, tab_aux = self._device()[2], self._device()[3]
        n = len(klen)
        return _open_gcm_dev(
            tab_rk, tab_aux, jnp.asarray(stream, dtype=jnp.int32),
            jnp.asarray(kin), jnp.asarray(klen, dtype=jnp.int32),
            jnp.asarray(np.full(n, 12, np.int32)), jnp.asarray(iv12),
            aad_const=12)

    def _protect_rtcp_gcm(self, batch: PacketBatch, stream, ssrc, index
                          ) -> PacketBatch:
        """RFC 7714 §9: AAD = RTCP header(8) || ESRTCP word; the index
        word rides *after* the ciphertext+tag on the wire.  Host shuffles
        the layout around the batched kernel (RTCP is low-rate)."""
        n = batch.batch_size
        cap = batch.capacity
        length = np.asarray(batch.length, dtype=np.int32)
        plen = length - 8
        word = (index | (1 << 31)).astype(np.int64)  # E always set: AEAD
        wb = np.zeros((n, 4), dtype=np.uint8)
        for k in range(4):
            wb[:, k] = (word >> (8 * (3 - k))) & 0xFF
        kin = np.zeros_like(batch.data)
        kin[:, :8] = batch.data[:, :8]
        kin[:, 8:12] = wb
        cols = np.arange(cap, dtype=np.int64)[None, :]
        src = np.clip(cols - 4, 0, cap - 1)
        shifted = np.take_along_axis(batch.data, src, axis=1)
        sel = (cols >= 12) & (cols < (12 + plen)[:, None])
        kin = np.where(sel, shifted, kin).astype(np.uint8)

        iv12 = self._gcm_rtcp_iv(self._salt_rtcp[stream], ssrc, index)
        pad = self._rtcp_pad(n)
        if pad is None:
            out, out_len = self._gcm_rtcp_seal_call(stream, kin,
                                                    12 + plen, iv12)
            out = np.asarray(out)
        else:
            out, out_len = self._gcm_rtcp_seal_call(
                stream[pad], kin[pad], (12 + plen)[pad], iv12[pad])
            out = np.asarray(out)[:n]
        # wire: hdr8 || ct || tag || word
        wire = np.zeros_like(out)
        wire[:, :8] = out[:, :8]
        sel2 = (cols >= 8) & (cols < (8 + plen + 16)[:, None])
        unshift = np.take_along_axis(out, np.minimum(cols + 4, cap - 1),
                                     axis=1)
        wire = np.where(sel2, unshift, wire).astype(np.uint8)
        wpos = 8 + plen + 16
        for k in range(4):
            np.put_along_axis(wire, (wpos + k)[:, None].astype(np.int64),
                              wb[:, k][:, None], axis=1)
        return PacketBatch(wire, (wpos + 4).astype(np.int32), batch.stream)

    def unprotect_rtcp(self, batch: PacketBatch
                       ) -> Tuple[PacketBatch, np.ndarray]:
        """Auth-check, replay-check and decrypt incoming SRTCP."""
        p = self.policy
        stream = np.asarray(batch.stream, dtype=np.int64)
        length = np.asarray(batch.length, dtype=np.int32)
        valid = (length >= 8 + 4 + p.auth_tag_len) & self.active[stream] & (
            stream >= 0)

        # host-parse the trailer: E||index (GCM: after the tag, RFC 7714;
        # CM: before the tag, RFC 3711)
        tpos = np.maximum(length - (4 if self._gcm
                                    else p.auth_tag_len + 4), 0)
        word = np.zeros(len(stream), dtype=np.int64)
        for k in range(4):
            col = np.minimum(tpos + k, batch.capacity - 1)
            word = (word << 8) | np.take_along_axis(
                batch.data, col[:, None].astype(np.int32), axis=1)[:, 0]
        index = word & 0x7FFFFFFF
        if self._kdr_active(stream):
            waves, r = self._epoch_plan(stream, index, rtcp=True)
            if waves is not None:
                done = []
                for w in waves:
                    o, kk = self.unprotect_rtcp(self._row_subset(batch, w))
                    done.append((w, o, kk, None))
                out, ok, _ = self._merge_row_results(batch.batch_size, done)
                return out, ok
            self._apply_epochs(stream, r, rtcp=True)
        ssrc = rtp_header.read_u32(batch.data, 4)
        not_replayed = replay.check(self.rtcp_rx_max, self.rtcp_rx_mask,
                                    stream, index)

        if self._gcm:
            data, mlen, auth_ok = self._unprotect_rtcp_gcm(
                batch, stream, ssrc, index, word, length)
        else:
            if self._f8:
                iv = self._f8_rtcp_iv(batch.data, word)
                enc_flag, f8 = True, True
            else:
                iv = self._cm_iv(self._salt_rtcp[stream], ssrc, index)
                enc_flag, f8 = p.cipher != Cipher.NULL, False
            n = batch.batch_size
            pad = self._rtcp_pad(n)
            if pad is None:
                data, mlen, auth_ok, _e, _idx = self._rtcp_unprotect_call(
                    stream, batch, iv, length, enc_flag, f8=f8)
            else:
                data, mlen, auth_ok, _e, _idx = self._rtcp_unprotect_call(
                    stream[pad],
                    PacketBatch(batch.data[pad], length[pad],
                                np.asarray(batch.stream)[pad]),
                    iv[pad], length[pad], enc_flag, f8=f8)
                data = np.asarray(data)[:n]
                mlen = np.asarray(mlen)[:n]
                auth_ok = np.asarray(auth_ok)[:n]
        auth_ok = np.asarray(auth_ok)
        srow = np.clip(stream, 0, self.capacity - 1)
        np.add.at(self.auth_fail, srow, valid & not_replayed & ~auth_ok)
        np.add.at(self.replay_reject, srow, valid & ~not_replayed)
        ok = valid & not_replayed & auth_ok
        ok &= ~replay.dedup_first(stream, index, ok)
        replay.update(self.rtcp_rx_max, self.rtcp_rx_mask, stream, index, ok)

        data = np.asarray(data)
        mlen = np.asarray(mlen, dtype=np.int32)
        out_data = np.where(ok[:, None], data, batch.data)
        out_len = np.where(ok, mlen, length).astype(np.int32)
        return PacketBatch(out_data, out_len, batch.stream), ok

    def _unprotect_rtcp_gcm(self, batch: PacketBatch, stream, ssrc, index,
                            word, length):
        """Reverse of `_protect_rtcp_gcm`: reshape wire
        hdr8 || ct || tag || word into the kernel's hdr8 || word || ct ||
        tag layout, open, and emit hdr8 || plaintext."""
        n = batch.batch_size
        cap = batch.capacity
        ctlen = np.maximum(length - 8 - 16 - 4, 0)
        wb = np.zeros((n, 4), dtype=np.uint8)
        for k in range(4):
            wb[:, k] = (np.asarray(word, np.int64) >> (8 * (3 - k))) & 0xFF
        cols = np.arange(cap, dtype=np.int64)[None, :]
        kin = np.zeros_like(batch.data)
        kin[:, :8] = batch.data[:, :8]
        kin[:, 8:12] = wb
        shifted = np.take_along_axis(batch.data,
                                     np.clip(cols - 4, 0, cap - 1), axis=1)
        sel = (cols >= 12) & (cols < (12 + ctlen + 16)[:, None])
        kin = np.where(sel, shifted, kin).astype(np.uint8)

        iv12 = self._gcm_rtcp_iv(self._salt_rtcp[stream], ssrc, index)
        pad = self._rtcp_pad(n)
        if pad is None:
            dec, _, auth_ok = self._gcm_rtcp_open_call(
                stream, kin, 12 + ctlen + 16, iv12)
            dec = np.asarray(dec)
        else:
            dec, _, auth_ok = self._gcm_rtcp_open_call(
                stream[pad], kin[pad], (12 + ctlen + 16)[pad], iv12[pad])
            dec = np.asarray(dec)[:n]
            auth_ok = np.asarray(auth_ok)[:n]
        out = np.zeros_like(dec)
        out[:, :8] = dec[:, :8]
        unshift = np.take_along_axis(dec, np.minimum(cols + 4, cap - 1),
                                     axis=1)
        sel2 = (cols >= 8) & (cols < (8 + ctlen)[:, None])
        out = np.where(sel2, unshift, out).astype(np.uint8)
        return out, (8 + ctlen).astype(np.int32), np.asarray(auth_ok)

    # ------------------------------------------------------------ checkpoint
    def snapshot(self) -> dict:
        """Serializable crypto-state snapshot (ROC/replay survive restarts —
        without them streams die; see SURVEY §5 checkpoint/resume)."""
        self._commit_inflight_unprotect()
        snap = {
            "profile": self.profile.value,
            "active": self.active.copy(),
            "rk_rtp": self._rk_rtp.copy(), "mid_rtp": self._mid_rtp.copy(),
            "rk_rtcp": self._rk_rtcp.copy(), "mid_rtcp": self._mid_rtcp.copy(),
            "salt_rtp": self._salt_rtp.copy(), "salt_rtcp": self._salt_rtcp.copy(),
            "tx_ext": self.tx_ext.copy(), "rx_max": self.rx_max.copy(),
            "rx_mask": self.rx_mask.copy(),
            "rtcp_tx_index": self.rtcp_tx_index.copy(),
            "rtcp_rx_max": self.rtcp_rx_max.copy(),
            "rtcp_rx_mask": self.rtcp_rx_mask.copy(),
            "auth_fail": self.auth_fail.copy(),
            "replay_reject": self.replay_reject.copy(),
        }
        if self._gcm:
            snap["gm_rtp"] = self._gm_rtp.copy()
            snap["gm_rtcp"] = self._gm_rtcp.copy()
        if self._f8:
            snap["rk_f8_rtp"] = self._rk_f8_rtp.copy()
            snap["rk_f8_rtcp"] = self._rk_f8_rtcp.copy()
        snap["kdr"] = self.kdr.copy()
        snap["epoch_rtp"] = self._epoch_rtp.copy()
        snap["epoch_rtcp"] = self._epoch_rtcp.copy()
        snap["masters"] = dict(self._masters)
        return snap

    @classmethod
    def restore(cls, snap: dict) -> "SrtpStreamTable":
        t = cls(capacity=len(snap["active"]),
                profile=SrtpProfile(snap["profile"]))
        t._load_state(snap)
        return t

    def _load_state(self, snap: dict) -> None:
        """Adopt a snapshot's crypto state (shared by the single-chip
        and mesh restore constructors)."""
        self.active = snap["active"].copy()
        self._rk_rtp = snap["rk_rtp"].copy()
        self._mid_rtp = snap["mid_rtp"].copy()
        self._rk_rtcp = snap["rk_rtcp"].copy()
        self._mid_rtcp = snap["mid_rtcp"].copy()
        self._salt_rtp = snap["salt_rtp"].copy()
        self._salt_rtcp = snap["salt_rtcp"].copy()
        self.tx_ext = snap["tx_ext"].copy()
        self.rx_max = snap["rx_max"].copy()
        self.rx_mask = snap["rx_mask"].copy()
        self.rtcp_tx_index = snap["rtcp_tx_index"].copy()
        self.rtcp_rx_max = snap["rtcp_rx_max"].copy()
        self.rtcp_rx_mask = snap["rtcp_rx_mask"].copy()
        if "auth_fail" in snap:      # older snapshots lack the counters
            self.auth_fail = snap["auth_fail"].copy()
            self.replay_reject = snap["replay_reject"].copy()
        if self._gcm:
            self._gm_rtp = snap["gm_rtp"].copy()
            self._gm_rtcp = snap["gm_rtcp"].copy()
        if self._f8:
            self._rk_f8_rtp = snap["rk_f8_rtp"].copy()
            self._rk_f8_rtcp = snap["rk_f8_rtcp"].copy()
        if "kdr" in snap:
            self.kdr = snap["kdr"].copy()
            self._epoch_rtp = snap["epoch_rtp"].copy()
            self._epoch_rtcp = snap["epoch_rtcp"].copy()
            self._masters = dict(snap["masters"])
        self._dev = None
        if self._ks_cache is not None:
            # restored keys may differ from every cached epoch: reset
            # the cache's per-stream history wholesale
            self._ks_cache.forget(np.arange(self.capacity))


class PendingProtect:
    """An in-flight `protect_rtp_async` call.

    Host state is already committed; the device results materialize on
    `result()` (one blocking transfer per size-class part).  The object
    is single-shot: result() caches and re-returns.
    """

    def __init__(self, parts, batch_size: int, capacity: int,
                 done: "PacketBatch | None" = None):
        self._parts = parts
        self._batch_size = batch_size
        self._capacity = capacity
        self._done = done

    def block_until_ready(self) -> "PendingProtect":
        """Fence the dispatched device work without transferring it
        back — the phase profiler's device_compute/d2h boundary."""
        if self._done is None:
            try:
                import jax

                for _rows, arrs, _n in self._parts:
                    jax.block_until_ready(
                        [a for a in arrs if a is not None])
            except Exception:
                pass
        return self

    def result(self) -> PacketBatch:
        if self._done is None:
            done = [(rows, PacketBatch(np.asarray(data),
                                       np.asarray(length, dtype=np.int32),
                                       stream), n)
                    for rows, (data, length, stream), n in self._parts]
            out, _ = unbucket(done, self._batch_size, self._capacity)
            self._done = out
            self._parts = []
        return self._done


class PendingUnprotect:
    """An in-flight `unprotect_rtp_async` call.

    The device auth/decrypt is dispatched; host RX state is NOT — the
    replay verdict chain (check → dedup → update) must run in dispatch
    order against current windows, so it is deferred to `commit()`,
    which the owning table forces before any newer unprotect, key
    mutation or snapshot can observe stale state.  `result()` commits,
    then assembles the output batch: failed rows keep their ORIGINAL
    bytes, read from the dispatched batch at materialization time (so
    a recv-arena view must stay pinned until then).  Single-shot:
    result() caches and re-returns.
    """

    def __init__(self, table, parts, batch: PacketBatch,
                 return_index: bool, done=None):
        self._table = table
        self._parts = parts
        self._batch = batch
        self._return_index = return_index
        self._committed = done is not None
        self._ok_parts: "list | None" = None
        self._done = done

    def block_until_ready(self) -> "PendingUnprotect":
        """Fence the dispatched device work without transferring it
        back (phase-profiler boundary)."""
        if self._done is None:
            try:
                for _rows, rec, _n in self._parts:
                    rec["launch"].block_until_ready()
            except Exception:
                pass
        return self

    def commit(self) -> None:
        """Materialize the auth verdicts and commit host replay state +
        failure counters, per size-class part IN ORDER (each part's
        replay check sees the previous part's update, exactly like the
        sync path)."""
        if self._committed:
            return
        self._committed = True
        t = self._table
        if t._inflight_unprotect is self:
            t._inflight_unprotect = None
        self._ok_parts = []
        for _rows, rec, _n in self._parts:
            stream, idx, valid = rec["stream"], rec["idx"], rec["valid"]
            _data, _mlen, auth_ok = rec["launch"].fetch()
            not_replayed = replay.check(t.rx_max, t.rx_mask, stream, idx)
            srow = np.clip(stream, 0, t.capacity - 1)
            np.add.at(t.auth_fail, srow, valid & not_replayed & ~auth_ok)
            np.add.at(t.replay_reject, srow, valid & ~not_replayed)
            ok = valid & not_replayed & auth_ok
            ok &= ~replay.dedup_first(stream, idx, ok)
            replay.update(t.rx_max, t.rx_mask, stream, idx, ok)
            self._ok_parts.append(ok)

    def result(self):
        """(batch, ok) — or (batch, ok, index) when dispatched with
        `return_index` — matching `unprotect_rtp`'s contract."""
        if self._done is not None:
            return self._done
        self.commit()
        batch = self._batch
        done, masks, idx_parts = [], [], []
        for (rows, rec, n), ok in zip(self._parts, self._ok_parts):
            data, mlen, _auth_ok = rec["launch"].fetch()
            pdat = rec["part"].data
            out_data = np.where(ok[:, None], data, pdat)
            out_len = np.where(ok, mlen, rec["length"]).astype(np.int32)
            done.append((rows, PacketBatch(out_data, out_len,
                                           rec["part"].stream), n))
            masks.append(ok)
            idx_parts.append((rows, rec["idx"][:n]))
        out, okall = unbucket(done, batch.batch_size,
                              batch.capacity, masks)
        # ok=False rows keep their original bytes (sync-path contract)
        out.data[~okall, :] = 0
        take = min(out.capacity, batch.capacity)
        out.data[~okall, :take] = batch.data[~okall, :take]
        out.length[~okall] = np.asarray(batch.length)[~okall]
        if self._return_index:
            idx = np.zeros(batch.batch_size, dtype=np.int64)
            for rows, idxp in idx_parts:
                idx[rows] = idxp
            self._done = (out, okall, idx)
        else:
            self._done = (out, okall)
        self._parts, self._batch, self._ok_parts = [], None, None
        return self._done
