"""RTP translator — the SFU fan-out primitive (BASELINE config #5).

Reference: `org.jitsi.impl.neomedia.rtp.translator.RTPTranslatorImpl`
fans each received packet from one `StreamRTPManager` to all the others,
re-running every receiver leg's send TransformEngineChain — i.e. one SRTP
re-encrypt *per receiver* per packet (SURVEY §3.4).  That multiplicative
crypto load is exactly what the batch design eats: decrypt once, then one
device launch re-encrypts the (packets x receivers) fan-out matrix.

Key observations that make the dense layout small (RFC 3711):
- session keys depend only on each receiver endpoint's master key — all
  forwarded SSRCs on one receiver leg share that key material, so key
  tensors are per *receiver* ([R, rounds, 16]), not per (receiver, ssrc);
- the forwarded packet keeps the sender's SSRC/seq/ts (the SFU does not
  rewrite them), so the SRTP packet index of every receiver copy equals
  the sender's index — per-sender index state, shared by all legs.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import functools
import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np

from libjitsi_tpu.core import staging
from libjitsi_tpu.core.packet import (CLASS_HEADROOM, DEFAULT_CAPACITY,
                                      FANOUT_ROW_CLASSES, LENGTH_CLASSES,
                                      ROW_CLASSES, PacketBatch,
                                      _round_fanout_rows)
from libjitsi_tpu.kernels import gcm as gcm_kernel
from libjitsi_tpu.kernels.aes import aes_encrypt_np, expand_key
from libjitsi_tpu.kernels.ghash import GM_BYTES, ghash_matrix
from libjitsi_tpu.kernels.sha1 import hmac_precompute
from libjitsi_tpu.rtp import header as rtp_header
from libjitsi_tpu.transform.srtp import kernel
from libjitsi_tpu.transform.srtp.kdf import derive_session_keys
from libjitsi_tpu.transform.srtp.policy import Cipher, SrtpProfile
from libjitsi_tpu.utils.tracing import span_of


def _round_width(w: int) -> int:
    """Fan-out data width quantized to the packet size classes (+ tag
    headroom) so the compiled-shape space stays (LENGTH_CLASSES x
    FANOUT_ROW_CLASSES), independent of the tick's exact longest
    packet."""
    for c in LENGTH_CLASSES:
        if w <= c + CLASS_HEADROOM:
            return c + CLASS_HEADROOM
    return w


#: receivers of one list from which the leg-major GCM form is used: a
#: list that fills the smallest row class.  A shorter one is padded to
#: it, and the (legs x packets) grid computes several times the rows
#: of the per-row form (7 legs x 2 packets: 256 rows against 16)
GCM_LEG_MAJOR_MIN_LEGS = ROW_CLASSES[0]


def _gcm_leg_major(legs: int, packets: int) -> bool:
    """Leg-major or per-row GCM fan-out for senders that share one
    receiver list: a pure function of the shape, like
    `context._gcm_form_grid`.  Leg-major reads a leg's 16 KiB matrix once
    for all its packets but pads legs AND packets to their row
    classes: it serves from `GCM_LEG_MAJOR_MIN_LEGS` legs, where that
    grid is at most twice the per-row form's padded rows."""
    return (legs >= GCM_LEG_MAJOR_MIN_LEGS and packets >= 2
            and _round_fanout_rows(legs) * _round_fanout_rows(packets)
            <= 2 * _round_fanout_rows(legs * packets))


def _cycle_rows(n: int) -> Optional[np.ndarray]:
    """Row indices padding `n` up to its FANOUT_ROW_CLASSES bucket by
    cycling the real rows (the bucket_by_size idiom — fan-out encrypt
    reads table state but never writes it, so repeats are SRTP-safe;
    padded output rows are sliced off in PendingTranslate).  None when
    `n` already sits on a class boundary."""
    n_pad = _round_fanout_rows(n)
    return np.resize(np.arange(n), n_pad) if n_pad > n else None


#: what a launch after a tick's first costs the tick thread, in rows of
#: device time.  Measured on the chip with scripts/launch_cost.py
#: (PERF.md section 3 names the run): its `fanout_dispatch` +
#: `fanout_wait` + `fanout_d2h` + `nack_cache` + `egress` read 1.31 ms
#: (quartiles 1.23-1.39) over the 1,024-row program's 1.786 us a row =
#: 734 rows (686-775).  Kept at 752, inside those quartiles, as PR 41
#: measured and shipped it: it was then also the least value at which
#: no tick of at most 1,024 rows is cut (1,024 - 256 - 16; under it
#: ticks of 257-272 rows went out as 256 + 16, which the GCM cell,
#: beside the parent, did not read better for).  With the fan-out's
#: 512-row class that edge lies at 496 (the binding case is 513 rows
#: as 512 + 16), so 752 stands for the measurement alone; no tick of
#: at most 1,024 rows is cut at it either
LAUNCH_COST_ROWS = 752


def plan_launches(rows: int, top: int,
                  launch_cost: Optional[int] = None) -> List[int]:
    """The real rows of each launch of a tick of `rows` fan-out rows,
    in row order; every launch is padded to its own row class (the
    fan-out's: `FANOUT_ROW_CLASSES`), and the classes up to `top`
    (`RtpTranslator.launch_rows`) are the warmed ones.  Whole launches
    of `top` rows go first while more than `top` remain.  What is left
    goes out as one launch in its covering class or, for a class c
    under that, as whole launches of c rows and the
    remainder in the remainder's own class: whichever pads least,
    where a launch after the first counts `launch_cost` rows (fewer
    launches on a tie).  1,164 rows are 1,024 + 140 (padded 1,024 +
    256) and not one launch of 4,096; 300 rows are one launch, padded
    512.  `launch_cost` None: cuts at `top` alone (a translator that
    pads nothing here)."""
    whole, tail = divmod(rows, top)
    if not tail or launch_cost is None:
        return [top] * whole + [tail] * bool(tail)
    plans = [[tail]] + [[c] * (tail // c) + [tail % c] * bool(tail % c)
                        for c in FANOUT_ROW_CLASSES
                        if c < _round_fanout_rows(tail)]
    return [top] * whole + min(plans, key=lambda cut: (
        sum(map(_round_fanout_rows, cut)) + launch_cost * (len(cut) - 1),
        len(cut)))


@functools.partial(jax.jit, static_argnames=("tag_len", "encrypt"),
                   donate_argnums=(2,))
def _fanout_protect(tab_rk, tab_mid, plane, tag_len: int, encrypt: bool):
    """The CM fan-out on one packed plane (core/staging.py): words
    receiver, length, payload offset, ROC; out word the wire length.
    The plane that comes back has the donated plane's shape.  The
    payload offset is the plane's word and nothing else: ONE program a
    (row class, width) whatever header lengths a tick carries
    (`kernel.srtp_protect_rows`)."""
    data, w, iv = staging.unpack(plane)
    rk, mid = kernel.gather_keys(staging.as_i32(w[:, 0]), tab_rk, tab_mid)
    out, out_len = kernel.srtp_protect_rows(
        data, staging.as_i32(w[:, 1]), staging.as_i32(w[:, 2]), rk, iv,
        mid, w[:, 3], tag_len, encrypt)
    return staging.repack(out, out_len)


def _split_fanout(host):
    """The fan-out's plane back on the host -> (wire bytes, lengths)."""
    data, words = staging.split_out(host, 1)
    return data, words[:, 0]


@functools.partial(jax.jit, donate_argnums=(2,))
def _fanout_protect_gcm(tab_rk, tab_gm, plane):
    """The per-row GCM fan-out on one packed plane (core/staging.py):
    words receiver, length, payload offset (GCM has no ROC word), the
    12-byte IV in the first 12 IV columns; out word the wire length.
    The plane that comes back has the donated plane's shape.  As
    `_fanout_protect`, the payload offset is data
    (`gcm_kernel.gcm_protect_rows`)."""
    data, w, iv = staging.unpack(plane)
    rk, gm = kernel.gather_keys(staging.as_i32(w[:, 0]), tab_rk, tab_gm)
    out, out_len = gcm_kernel.gcm_protect_rows(
        data, staging.as_i32(w[:, 1]), staging.as_i32(w[:, 2]), rk, gm,
        iv[:, :12])
    return staging.repack(out, out_len)


@functools.partial(jax.jit, static_argnames=("aad_const",))
def _fanout_protect_gcm_legs(tab_rk, tab_gm, legs, data, length, iv12,
                             aad_const: int):
    """P packets sealed for G legs, leg-major out [G, P, W]: one matrix
    gathered a LEG (`gcm_kernel.gcm_protect_fanout`)."""
    rk, gm = kernel.gather_keys(legs, tab_rk, tab_gm)
    return gcm_kernel.gcm_protect_fanout(data, length, rk, gm, iv12,
                                         aad_const=aad_const)


class RtpTranslator:
    """Decrypt-once / re-encrypt-N fan-out over a receiver key table.

    Receivers are endpoint legs with their own SRTP master keys (the
    `MediaStream`s a videobridge conference holds per participant).
    Senders are identified by their decrypted packets' stream ids; the
    routing table says which receivers get which sender's media.
    """

    #: the per-row fan-out's rows are cycled up to their row class
    #: (`_cycle_rows`) here, in `expand`.  The mesh translator says
    #: False: its owner plan pads the lanes a chip, once
    _pads_rows = True

    def __init__(self, capacity: int = 1024,
                 profile: SrtpProfile = SrtpProfile.AES_CM_128_HMAC_SHA1_80):
        self.profile = profile
        self.policy = profile.policy
        self._gcm = self.policy.cipher == Cipher.AES_GCM
        rounds = {16: 11, 32: 15}[self.policy.enc_key_len]
        self.capacity = capacity
        self.active = np.zeros(capacity, dtype=bool)
        self._rk = np.zeros((capacity, rounds, 16), dtype=np.uint8)
        self._mid = np.zeros((capacity, 2, 5), dtype=np.uint32)
        if self._gcm:
            # per-LEG GHASH matrix (H = AES_K(0), RFC 7714) — a leg
            # constant like the HMAC midstates, gathered or (full-mesh)
            # applied group-wise by `ghash_grouped`
            self._gm = np.zeros((capacity, 128, 128), dtype=np.int8)
        self._salt = np.zeros((capacity, 16), dtype=np.uint8)
        self._dev = None
        # routing: sender sid -> sorted receiver id array
        self._routes: Dict[int, np.ndarray] = {}
        # the longest receiver list ever connected: what `_gcm_leg_major`
        # can select here, hence what `fanout_warmups` warms
        self._max_legs = 0
        #: rows of the largest per-row fan-out launch, a row class: no
        #: launch is larger, and the classes up to it are the shapes a
        #: tick's rows are cut into (`translate_async`,
        #: `plan_launches`).  The lifecycle's warm ladder lowers it to
        #: the largest class it has compiled while that is not yet the
        #: top one (`_bound_fanout`); no option sets it
        self.launch_rows = ROW_CLASSES[-1]
        # device calls the fan-out has made, ticks it cut in two or
        # more, those of them that fit `launch_rows` and were cut by
        # the row classes under it, and the calls by the rows they
        # were padded to (/metrics: `fanout_launches_total`,
        # `fanout_split_ticks_total`, `fanout_class_cut_ticks_total`,
        # `fanout_launch_rows_total{rows}`: the per-row launches of a
        # translator that pads them here, so a row class a label)
        self.fanout_launches = 0
        self.fanout_split_ticks = 0
        self.fanout_class_cut_ticks = 0
        self.fanout_launch_rows: Dict[int, int] = {}
        # launches collected, and those of them whose every array was
        # ready when the collection reached them, so that the wait for
        # them was none (/metrics: `fanout_collect_total`,
        # `fanout_collect_ready_total`: how often the work between a
        # dispatch and its collection hid the whole launch)
        self.fanout_collects = 0
        self.fanout_collects_ready = 0
        # the newest `translate_async` not collected yet: what
        # `translate` of that very batch hands back
        self._in_flight: Optional["PendingTranslate"] = None
        # the bridge hands its loop's PipelineTracer here; a
        # translator standing alone spans nothing
        self.tracer = None

    # ---------------------------------------------------------- receivers
    def add_receiver(self, rid: int, master_key: bytes,
                     master_salt: bytes) -> None:
        p = self.policy
        ks = derive_session_keys(
            master_key, master_salt, enc_key_len=p.enc_key_len,
            auth_key_len=p.auth_key_len, salt_len=p.salt_len)
        self._rk[rid] = expand_key(ks.rtp_enc)
        if self._gcm:
            h = bytes(aes_encrypt_np(self._rk[rid],
                                     np.zeros((1, 16), np.uint8))[0])
            self._gm[rid] = ghash_matrix(h).astype(np.int8)
        else:
            self._mid[rid] = hmac_precompute(ks.rtp_auth)
        self._salt[rid, : p.salt_len] = np.frombuffer(ks.rtp_salt, np.uint8)
        self._salt[rid, p.salt_len:] = 0
        self.active[rid] = True
        self._dev = None

    def add_receivers(self, rids, master_keys, master_salts) -> None:
        """Vectorized bulk `add_receiver` (checkpoint restore, join
        storms): one batched KDF/key-schedule/leg-constant pass instead
        of a per-receiver Python loop — the same install-plane doctrine
        as `SrtpStreamTable.add_streams`."""
        from libjitsi_tpu.kernels.aes import expand_keys_batch
        from libjitsi_tpu.kernels.ghash import ghash_matrix_batch
        from libjitsi_tpu.kernels.sha1 import hmac_precompute_batch
        from libjitsi_tpu.transform.srtp.kdf import \
            derive_session_keys_batch

        rids = np.asarray(rids, dtype=np.int64)
        if len(rids) == 0:
            return
        p = self.policy

        def rows(keys):          # accept bytes rows like add_receiver
            return np.stack([np.frombuffer(bytes(k), dtype=np.uint8)
                             for k in keys])

        ksb = derive_session_keys_batch(
            rows(master_keys), rows(master_salts),
            enc_key_len=p.enc_key_len, auth_key_len=p.auth_key_len,
            salt_len=p.salt_len)
        self._rk[rids] = expand_keys_batch(ksb.rtp_enc)
        if self._gcm:
            h = aes_encrypt_np(self._rk[rids],
                               np.zeros((len(rids), 16), np.uint8))
            self._gm[rids] = ghash_matrix_batch(h).astype(np.int8)
        else:
            self._mid[rids] = hmac_precompute_batch(ksb.rtp_auth)
        self._salt[rids, : p.salt_len] = ksb.rtp_salt
        self._salt[rids, p.salt_len:] = 0
        self.active[rids] = True
        self._dev = None

    def adopt_receivers(self, rids, table) -> None:
        """`add_receivers` for legs whose keys an SRTP table holds
        already: rows `rids` of `table` (an `SrtpStreamTable` of this
        profile keyed with the legs' master keys, the bridge's tx
        table) carry the session key schedule, the leg constant and the
        salt this translator would derive again from the same master
        keys, so a staged wave derives them once."""
        rids = np.asarray(rids, dtype=np.int64)
        if len(rids) == 0:
            return
        if table.profile != self.profile:
            raise ValueError("table of another profile")
        self._rk[rids] = table._rk_rtp[rids]
        if self._gcm:
            self._gm[rids] = table._gm_rtp[rids]
        else:
            self._mid[rids] = table._mid_rtp[rids]
        self._salt[rids] = table._salt_rtp[rids]
        self.active[rids] = True
        self._dev = None

    def remove_receiver(self, rid: int) -> None:
        self.active[rid] = False
        self._rk[rid] = 0
        self._mid[rid] = 0
        if self._gcm:
            self._gm[rid] = 0
        self._dev = None
        for s, rr in list(self._routes.items()):
            self._routes[s] = rr[rr != rid]

    def move_receivers(self, src_rids, dst_rids) -> None:
        """Relocate receiver legs to new rows bit-exact (placement
        rebalance).  Per-leg state is pure key material — schedules,
        GHASH matrices, salts — so the move is an array copy; routes
        referencing the old rows are rewritten in place (the bridge
        rebuilds routes after a migration anyway, but a translator used
        standalone must not keep stale rows routed)."""
        src = np.asarray(src_rids, dtype=np.int64)
        dst = np.asarray(dst_rids, dtype=np.int64)
        if src.size != dst.size:
            raise ValueError("src/dst length mismatch")
        if src.size == 0:
            return
        if not self.active[src].all():
            raise ValueError("cannot move inactive receiver rows")
        if self.active[dst].any():
            raise ValueError("destination receiver rows occupied")
        self._rk[dst] = self._rk[src]
        self._mid[dst] = self._mid[src]
        if self._gcm:
            self._gm[dst] = self._gm[src]
        self._salt[dst] = self._salt[src]
        self.active[dst] = True
        remap = {int(s): int(d) for s, d in zip(src, dst)}
        for s_sid, rr in list(self._routes.items()):
            self._routes[s_sid] = np.asarray(
                [remap.get(int(r), int(r)) for r in rr], dtype=rr.dtype)
        self.active[src] = False
        self._rk[src] = 0
        self._mid[src] = 0
        if self._gcm:
            self._gm[src] = 0
        self._salt[src] = 0
        self._dev = None

    # ------------------------------------------------------------ routing
    def connect(self, sender_sid: int, receiver_ids: Sequence[int]) -> None:
        """Declare that `sender_sid`'s media goes to these receivers
        (reference: the translator's willWrite acceptance per target)."""
        rr = self._routes[sender_sid] = np.unique(
            np.asarray(receiver_ids, dtype=np.int64))
        self._max_legs = max(self._max_legs, len(rr))

    def disconnect(self, sender_sid: int) -> None:
        self._routes.pop(sender_sid, None)

    # ------------------------------------------------------------- warmup
    def fanout_warmups(self, rows: int, payload_len: int = 160
                       ) -> List[Callable[[], None]]:
        """Pre-compiling the fan-out kernels of one FANOUT_ROW_CLASSES
        bucket, off the data path, as one thunk per program: each
        compiles its own program when called and they share nothing, so
        a caller may run them side by side (StreamLifecycleManager
        does, when the population bucket grows, before any admit can
        drive traffic at the new scale).  Covers the class-padded shapes
        translate_async produces.  The payload offset is an operand of
        the per-row program, so one thunk a width covers every header
        length.  Reads the live key tables (row 0, key material
        irrelevant); outputs are garbage and discarded.

        Under GCM the per-row form always; the leg-major form only
        where `_gcm_leg_major` can select it for this translator: some
        connected receiver list reaches `GCM_LEG_MAJOR_MIN_LEGS` (a
        bridge of conferences of 8 never does, and warms none of it).

        Widths: the data path clips the fan-out buffer to the tick's
        largest packet's LENGTH_CLASSES bucket, so this warms the class
        covering `payload_len` (the configured media size) and the
        full-MTU class (video keyframes, FEC bursts)."""
        rows = _round_fanout_rows(max(1, rows))
        tag = self.policy.auth_tag_len
        widths = sorted({_round_width(12 + payload_len + tag),
                         _round_width(DEFAULT_CAPACITY + tag)})
        recv = np.zeros(rows, dtype=np.int64)
        idx = np.zeros(rows, dtype=np.int64)
        length = np.full(rows, 12 + payload_len, dtype=np.int32)
        off = np.full(rows, 12, dtype=np.int32)

        def one(w: int) -> None:
            # fetch the output: compile NOW, off-tick
            plane = staging.alloc(rows, w)
            plane[:, 0] = 0x80
            if self._gcm:
                iv12 = np.zeros((rows, 12), dtype=np.uint8)
                self._gcm_fanout_call(recv, plane, length, off,
                                      iv12).fetch()
            else:
                iv = np.zeros((rows, 16), dtype=np.uint8)
                self._cm_fanout_call(recv, plane, length, off, iv,
                                     idx).fetch()

        def leg_major(w: int, aad: int) -> None:
            # legs = this bucket, packets = the smallest row class
            # (both axes class-padded live)
            p = _round_fanout_rows(1)
            pdata = np.zeros((p, w), dtype=np.uint8)
            plen = np.full(p, 12 + payload_len, dtype=np.int32)
            iv = np.zeros((rows, p, 12), dtype=np.uint8)
            self._gcm_uniform_fanout_call(recv, pdata, plen, iv,
                                          aad).fetch()

        thunks = [functools.partial(one, w) for w in widths]
        if self._gcm and self._max_legs >= GCM_LEG_MAJOR_MIN_LEGS:
            thunks += [functools.partial(leg_major, w, aad)
                       for w in widths for aad in (12, 20)]
        return thunks

    def _device(self):
        if self._dev is None:
            aux = self._gm if self._gcm else self._mid
            self._dev = (jnp.asarray(self._rk), jnp.asarray(aux))
        return self._dev

    # ------------------------------------------------------------ fan-out
    def translate(self, batch: PacketBatch, index: np.ndarray
                  ) -> Tuple[PacketBatch, np.ndarray]:
        """Fan out decrypted sender packets to their receivers, batched.

        batch: decrypted RTP with `stream` = sender sid; `index` [B] is
        each packet's 48-bit SRTP index (from the rx context's
        authenticated estimate — `SrtpStreamTable.unprotect_rtp` leaves
        it in `rx_max`; pass the per-packet values).

        Returns (wire_batch, receiver_ids): P x fanout rows, each row
        protected with its receiver's session key; `receiver_ids` says
        which leg each row goes to.  Packets from senders with no route
        produce no rows.

        Where `translate_async` has dispatched this very batch and
        nobody has collected it, this call collects THAT fan-out and
        dispatches nothing: the bridge dispatches in one tick and calls
        `translate` in the next, so whoever wraps this method on the
        instance (the benchmark's fault `bridge-bitflip`) still sees
        the rows of every one-launch tick.
        """
        pend = self._in_flight
        if pend is None or pend.batch is not batch:
            pend = self.translate_async(batch, index)
        return pend.result()

    def _plan(self, rows: int) -> List[int]:
        """`plan_launches` of `rows` fan-out rows for this translator:
        by the row classes up to `launch_rows` where it pads its rows
        to them here, at `launch_rows` alone where it does not (the
        mesh's: `_OwnerPlan` pads lanes a chip, and a launch there
        costs twice a one-chip launch)."""
        return plan_launches(
            rows, self.launch_rows,
            LAUNCH_COST_ROWS if self._pads_rows else None)

    def translate_async(self, batch: PacketBatch, index: np.ndarray
                        ) -> "PendingTranslate":
        """Dispatch-only `translate`: the fan-out is enqueued, results
        materialize on `.result()` (or a launch at a time on `.each()`).
        The SFU's tick dispatches here, asks for the copy back
        (`pend.copy_back_async()`) and collects in its NEXT tick, so
        the launch and its copy run under the host work between the
        two (`SfuBridge._on_media`).  Until it is collected the pending
        is what `translate` of the same batch returns the result of.

        The (packet, receiver) rows are cut into launches by the row
        classes the warm ladder compiled (`plan_launches`): none over
        `launch_rows`, the largest of them, so whatever the conference
        size and the backlog no launch has a shape the ladder did not
        warm; and a tick that fits `launch_rows` but would pad far up
        to its class goes out as whole launches of a smaller class and
        a remainder in its own (1,164 rows: 1,024 + 256 computed, not
        4,096), where that saves more rows than `LAUNCH_COST_ROWS` a
        further launch.  `pend.launches` says how many; most ticks are
        one launch, as they always were.  Every launch of a tick is
        dispatched before the first is waited for, and they come back
        in row order.  A translator that does not pad rows here (the
        mesh's, `_pads_rows` False) cuts at `launch_rows` alone
        (`_plan`)."""
        tracer = self.tracer
        stream = np.asarray(batch.stream, dtype=np.int64)
        index = np.asarray(index, dtype=np.int64)
        # build the (packet, receiver) expansion on host
        rows: List[int] = []
        recvs: List[np.ndarray] = []
        with span_of(tracer, "route", packets=len(stream)):
            for i, sid in enumerate(stream):
                rr = self._routes.get(int(sid))
                if rr is None or len(rr) == 0:
                    continue
                rows.append(i)
                recvs.append(rr)
        if not rows:
            return self._dispatched(batch, index, [])
        with span_of(tracer, "expand") as sp:
            counts = np.array([len(r) for r in recvs])
            src = np.repeat(np.array(rows, dtype=np.int64), counts)
            recv = np.concatenate(recvs)
            if not np.all(self.active[recv]):
                raise KeyError("route to receiver without installed keys")
            # per-row vectors over the whole tick; the packet bytes are
            # gathered a launch at a time (`_expand_rows`), so a tick
            # that found a backlog builds no [rows, capacity]
            # intermediate
            length = np.asarray(batch.length, dtype=np.int32)[src]
            hdr = rtp_header.parse(batch)
            idx = index[src]
            longest = int(np.max(length, initial=12)) \
                + self.policy.auth_tag_len
            if longest > batch.capacity:
                raise ValueError(
                    "fan-out rows need tag headroom in capacity")
            off0 = self._gcm_legs(batch, rows, recvs, hdr) \
                if self._gcm else None
            if off0 is None:
                # width clips to the tick's largest packet's class, not
                # the wire buffer: voice riding full-MTU rx buffers
                # would pay ~7x keystream over every leg
                pw = _round_width(longest)
                rowv = (src, recv, length, hdr.payload_off[src],
                        hdr.ssrc[src], idx)
                top = self.launch_rows
                sizes = self._plan(len(recv))
                cuts = [(b - n, b) for n, b in
                        zip(sizes, itertools.accumulate(sizes))]
                # the rows of each launch as it leaves here: its row
                # class where this translator pads (each launch's own
                # `expand` books it, `row_class`)
                padded = [_round_fanout_rows(n) if self._pads_rows else n
                          for n in sizes]
                # the row classes cut a tick that fits the largest
                class_cut = int(len(cuts) > 1 and len(recv) <= top)
                sp.note(rows=len(recv), width=pw, launches=len(cuts),
                        legs_max=int(counts.max()), class_cut=class_cut,
                        rows_padded=sum(padded), row_class=padded[0])
                args = self._expand_rows(batch, rowv, pw, *cuts[0])
        if off0 is not None:
            self.fanout_launches += 1
            return self._dispatched(batch, index, [
                self._translate_gcm_legs(batch, rows, recvs[0], hdr,
                                         idx, off0) + (recv, {})])
        call = self._gcm_fanout_call if self._gcm else self._cm_fanout_call
        parts = []
        for k, (a, b) in enumerate(cuts):
            # a launch of a tick that has several says which it is, on
            # each span it books (`launch`: 0, 1, ...); a tick's only
            # launch books what it always did
            nth = {"launch": k} if len(cuts) > 1 else {}
            if k:
                with span_of(tracer, "expand", row_class=padded[k]):
                    args = self._expand_rows(batch, rowv, pw, a, b)
            with staging.dispatch(tracer, "fanout", **nth) as sp:
                launch = call(*args)
                sp.note(h2d_arrays=launch.h2d_arrays,
                        h2d_bytes=launch.h2d_bytes, **launch.counts)
            parts.append((launch, None, recv[a:b], nth))
        self.fanout_launches += len(parts)
        self.fanout_split_ticks += int(len(parts) > 1)
        self.fanout_class_cut_ticks += class_cut
        if self._pads_rows:
            by_rows = self.fanout_launch_rows
            for c in padded:
                by_rows[c] = by_rows.get(c, 0) + 1
        return self._dispatched(batch, index, parts)

    def _dispatched(self, batch, index, parts) -> "PendingTranslate":
        """The pending of `batch`'s launches `parts`, kept as the one
        in flight until it is collected (`translate`)."""
        pend = self._in_flight = PendingTranslate(parts, batch, index,
                                                  self)
        return pend

    def _expand_rows(self, batch, rowv, pw, a, b):
        """The arguments of ONE per-row fan-out call (`_cm_fanout_call`
        / `_gcm_fanout_call`) for rows `a:b` of the tick's expansion
        (`rowv`: its per-row source packet, receiver, length, payload
        offset, ssrc and index): rows padded to their class, per-row
        IVs, and the packet bytes gathered once, straight into a
        staging plane (core/staging.py) of width `pw` with room behind
        them for the rest."""
        # class-pad rows AND width: under churn the receiver count
        # changes every tick, so raw (packets x receivers) shapes
        # would retrace the fan-out jit unboundedly — bucketing
        # keeps the compiled-shape space at LENGTH x ROW classes
        rr_idx = _cycle_rows(b - a) if self._pads_rows else None
        at = slice(a, b) if rr_idx is None else a + rr_idx
        src, recv, length, payload_off, ssrc, idx = (v[at] for v in rowv)
        cw = min(pw, batch.capacity)
        plane = staging.alloc(len(recv), pw)
        plane[:, :cw] = batch.data[src, :cw]
        if self._gcm:
            return (recv, plane, length, payload_off,
                    gcm_kernel.srtp_gcm_iv(self._salt[recv], ssrc, idx))
        # per-row IV from the receiver's salt + sender's ssrc/index
        iv = self._salt[recv].copy()
        for k in range(4):
            iv[:, 4 + k] ^= ((ssrc >> (8 * (3 - k))) & 0xFF
                             ).astype(np.uint8)
        for k in range(6):
            iv[:, 8 + k] ^= ((idx >> (8 * (5 - k))) & 0xFF
                             ).astype(np.uint8)
        return recv, plane, length, payload_off, iv, idx

    def _cm_fanout_call(self, recv, plane, length, payload_off, iv, idx
                        ) -> staging.Launch:
        """AES-CM fan-out device call — the mesh translator
        (mesh/translator.py) overrides exactly this seam, sharding the
        output rows by owning receiver chip; everything above (routing,
        expansion, IVs) is shared verbatim.

        `plane` is `staging.alloc(rows, width)` with the packet bytes
        in its first `width` columns; receiver, length, payload offset,
        ROC (`idx >> 16` mod 2**32) and IV are packed behind them here,
        so ONE array goes to the device and one plane comes back.
        Returns the `staging.Launch` in flight, whose `fetch()` gives
        host arrays (wire bytes `[rows, width]`, wire lengths)."""
        tab_rk, tab_mid = self._device()
        staging.pack(plane, (recv, length, payload_off,
                             (idx >> 16) & 0xFFFFFFFF), iv)
        out = _fanout_protect(
            tab_rk, tab_mid, staging.put(plane),
            self.policy.auth_tag_len, self.policy.cipher != Cipher.NULL)
        return staging.Launch((out,), _split_fanout, h2d_arrays=1,
                              h2d_bytes=plane.nbytes)

    # (see PendingTranslate at module scope)

    def _gcm_legs(self, batch, rows, recvs, hdr) -> Optional[int]:
        """The uniform payload offset where this tick takes the
        leg-major AEAD form, else None (the per-row form: every tick of
        a bridge of small conferences, whose senders' lists all
        differ).  Leg-major: every routed sender shares one receiver
        list, headers are uniform and `_gcm_leg_major` says so of the
        (legs, packets) shape."""
        off0 = np.asarray(hdr.payload_off)[rows]
        # the offset bound mirrors _uniform_off: a forged ext_words field
        # can claim a header larger than the packet; such batches take
        # the general path, which clamps per row (the packets then die
        # at the receiving legs, not in our trace).  The mesh translator
        # overrides the `_gcm_uniform_fanout_call` seam below with the
        # legs partitioned over chips — parity-tested both ways.
        uniform = (_gcm_leg_major(len(recvs[0]), len(recvs)) and
                   all(len(r) == len(recvs[0]) and np.array_equal(
                       r, recvs[0]) for r in recvs[1:])
                   and np.all(off0 == off0[0])
                   and 0 <= int(off0[0]) < batch.capacity)
        return int(off0[0]) if uniform else None

    def _translate_gcm_legs(self, batch, rows, rr, hdr, idx, off0: int):
        """The leg-major AEAD fan-out of a whole tick: the matrix seals
        via `gcm_protect_fanout` — each leg's 16 KiB GHASH matrix is
        read once per leg, not once per output row.  ONE launch
        whatever the rows (its grid is legs x packets, both padded to
        their classes; it is not cut at `launch_rows`).  Returns (the
        `staging.Launch` in flight, (packets, legs) of the grid).
        Reference: RTPTranslatorImpl's cipher-agnostic per-leg
        transform (SURVEY §3.4)."""
        tracer = self.tracer
        with span_of(tracer, "expand") as sp:
            p_rows = np.asarray(rows, dtype=np.int64)
            pidx = np.asarray(idx).reshape(len(rows), len(rr))[:, 0]
            # class-pad BOTH grouped axes (legs and packets,
            # cycled) plus the data width: churn varies the leg
            # count every tick, and raw (G, P) shapes would retrace
            # unboundedly
            g_real, p_real = len(rr), len(p_rows)
            g_idx = _cycle_rows(g_real)
            rr_p = rr[g_idx] if g_idx is not None else rr
            p_idx = _cycle_rows(p_real)
            if p_idx is None:
                p_idx = np.arange(p_real)
            pr = p_rows[p_idx]
            plen = np.asarray(batch.length, dtype=np.int32)[pr]
            # width clips to the largest packet's class (see the
            # per-row path)
            pw = _round_width(int(np.max(plen, initial=12))
                              + self.policy.auth_tag_len)
            cw = min(pw, batch.capacity)
            pdata = np.zeros((len(pr), pw), dtype=np.uint8)
            pdata[:, :cw] = batch.data[pr][:, :cw]
            pssrc = hdr.ssrc[pr]
            pidx = pidx[p_idx]
            # iv [G, P, 12]: leg salt x sender ssrc/index
            iv = gcm_kernel.srtp_gcm_iv(
                np.broadcast_to(self._salt[rr_p][:, None, :12],
                                (len(rr_p), len(pr), 12)),
                pssrc[None, :], pidx[None, :])
            sp.note(rows=g_real * p_real,
                    rows_padded=len(rr_p) * len(pr), width=pw,
                    launches=1, legs_max=g_real, class_cut=0)
        with staging.dispatch(tracer, "fanout") as sp:
            # the output is leg-major [G, P, W] at the class-PADDED
            # shape; cropping to the raw (P, G) and the flip to
            # packet-major rows (p0r0, p0r1, ...) matching
            # `src`/`recv` are numpy work at result() time
            launch = self._gcm_uniform_fanout_call(
                rr_p, pdata, plen, iv, off0)
            sp.note(h2d_arrays=launch.h2d_arrays,
                    h2d_bytes=launch.h2d_bytes, **launch.counts)
        return launch, (p_real, g_real)

    def _gcm_uniform_fanout_call(self, rr, pdata, plen, iv, aad_const
                                 ) -> staging.Launch:
        """Leg-major fan-out device call: P packets sealed for G legs,
        one GHASH matrix read per LEG — the mesh translator overrides
        this seam with the legs partitioned over chips.  Returns the
        `staging.Launch` in flight, whose `fetch()` gives host
        (leg-major out [G, P, W], out_len [P])."""
        tab_rk, tab_gm = self._device()
        dev, n, nbytes = staging.put_each(
            (np.asarray(rr, dtype=np.int32), pdata,
             np.asarray(plen, dtype=np.int32), iv))
        return staging.Launch(
            _fanout_protect_gcm_legs(tab_rk, tab_gm, *dev,
                                     aad_const=aad_const),
            h2d_arrays=n, h2d_bytes=nbytes,
            counts={"gm_gather_bytes": len(rr) * GM_BYTES, "grouped": 1})

    def _gcm_fanout_call(self, recv, plane, length, payload_off, iv12
                         ) -> staging.Launch:
        """Per-row AEAD fan-out device call — the mesh translator
        overrides exactly this seam (leg-sharded, chip-local matrix
        gathers).

        As `_cm_fanout_call`: `plane` is `staging.alloc(rows, width)`
        with the packet bytes in its first `width` columns; receiver,
        length, payload offset and the 12-byte IV are packed behind
        them here, so ONE array goes to the device and one plane comes
        back.  Returns the `staging.Launch` in flight, whose `fetch()`
        gives host (wire bytes `[rows, width]`, wire lengths)."""
        tab_rk, tab_gm = self._device()
        staging.pack(plane, (recv, length, payload_off), iv12)
        out = _fanout_protect_gcm(tab_rk, tab_gm, staging.put(plane))
        return staging.Launch(
            (out,), _split_fanout, h2d_arrays=1, h2d_bytes=plane.nbytes,
            counts={"gm_gather_bytes": len(recv) * GM_BYTES,
                    "grouped": 0})


class PendingTranslate:
    """An in-flight `translate_async` fan-out: the tick's launches, in
    row order.

    Device work is enqueued; `each()` materializes a launch at a time
    (blocking transfer) and `result()` the whole tick, once, cached.
    Mirrors `context.PendingProtect`, the same double-buffering seam,
    for the SFU's per-leg re-encrypt launches.  The SFU keeps it across
    the tick boundary: dispatched by tick N, collected by tick N+1,
    with the copy back asked for at dispatch (`copy_back_async`), so a
    collection finds its launch done, or nearly.  Each launch's
    `fanout_wait` says what it found: `collected` 1, `ready` 1 / 0
    (every array of the launch was ready when the collection reached
    it) and `hidden_us`, the tick thread's time from the launch's jit
    call's return (`Launch.dispatched_at`: the end of its
    `fanout_dispatch`, to microseconds) to the start of this wait: the
    host work the launch ran under.
    """

    def __init__(self, parts, batch: PacketBatch, index, translator):
        # (launch, pg, recv, nth) a device call in flight: `fetch()` ->
        # (rows, lengths); `pg` = (p_real, g_real) when the launch is
        # the leg-major fan-out's padded grid [G_pad, P_pad, W], None
        # for flat rows; `recv` the receiver leg of each REAL row;
        # `nth` = {"launch": k} where the tick has several, else {}
        self._parts = list(parts)
        self.launches = len(self._parts)
        #: what this fans out: `translator.translate(batch, index)`
        #: collects this pending while it is the one in flight
        self.batch, self.index = batch, index
        self._translator = translator
        self._done: "List[Tuple[PacketBatch, np.ndarray]]" = []

    def copy_back_async(self) -> "PendingTranslate":
        """Ask for every launch's copy back now
        (`staging.Launch.copy_back_async`): each starts when its
        program ends, whatever the tick thread is doing then."""
        for launch, _pg, _recv, _nth in self._parts:
            launch.copy_back_async()
        return self

    def each(self):
        """(wire_batch, receiver_ids) a launch, in row order: each
        waits for its own launch alone, so a caller can send launch 1
        while launch 2 is on the device.  Nothing for a tick that
        routed no packet."""
        yield from self._done
        while self._parts:
            launch, pg, recv, nth = self._parts.pop(0)
            self._done.append((self._materialize(launch, pg, recv, nth),
                               recv))
            yield self._done[-1]
        if self._translator._in_flight is self:
            self._translator._in_flight = None    # collected

    def result(self) -> Tuple[PacketBatch, np.ndarray]:
        """The whole tick as one batch (one launch: that launch's
        plane as it came back; more: their rows in one copy)."""
        got = list(self.each())
        if not got:
            return (PacketBatch.empty(0, self.batch.capacity),
                    np.zeros(0, np.int64))
        if len(got) > 1:
            self._done = got = [(
                PacketBatch(np.concatenate([w.data for w, _r in got]),
                            np.concatenate([w.length for w, _r in got]),
                            np.concatenate([w.stream for w, _r in got])),
                np.concatenate([r for _w, r in got]))]
        return got[0]

    def _materialize(self, launch, pg, recv, nth) -> PacketBatch:
        """Wait for one launch (`fanout_wait`, the `device_compute`
        phase), then copy its rows back (`fanout_d2h`,
        `d2h_transfer`)."""
        tr = self._translator
        hidden = time.perf_counter() - launch.dispatched_at
        with span_of(tr.tracer, "fanout_wait", **nth) as sp:
            ready = int(launch.is_ready())
            sp.note(collected=1, ready=ready, hidden_us=int(1e6 * hidden))
            launch.block_until_ready()
        tr.fanout_collects += 1
        tr.fanout_collects_ready += ready
        with span_of(tr.tracer, "fanout_d2h", **nth) as sp:
            arr, lens = launch.fetch()
            lens = np.asarray(lens, dtype=np.int32)
            sp.note(d2h_arrays=launch.d2h_arrays,
                    d2h_bytes=launch.d2h_bytes, **launch.d2h_counts)
            if pg is not None:
                # crop the padded leg-major (G, P) grid to the real
                # counts and flatten packet-major — numpy on the
                # materialized buffer, so no per-raw-shape device
                # programs
                p, g = pg
                arr = arr[:g, :p].transpose(1, 0, 2).reshape(
                    p * g, arr.shape[-1])
                lens = np.repeat(lens[:p], g)
            else:
                # drop the class-padding rows (cycled copies appended
                # by translate_async to keep the fan-out shapes on the
                # FANOUT_ROW_CLASSES grid)
                n = len(recv)
                arr, lens = arr[:n], lens[:n]
            return PacketBatch(arr, lens, recv.astype(np.int32))
