"""ShardedRtpTranslator — the SFU fan-out primitive on a device mesh.

The decrypt-once / re-encrypt-N fan-out (BASELINE config #5, reference
`RTPTranslatorImpl`, SURVEY §3.4) is embarrassingly parallel over the
RECEIVER axis: each output row's key material belongs to exactly one
receiver leg, so partitioning legs across chips makes every key gather
chip-local — zero collectives, the same stream-data-parallel doctrine
as `ShardedSrtpTable` (the packets each chip needs are routed to it by
the host plan, which already expands the (packet × receiver) matrix).

The routing/expansion/IV host plane is `RtpTranslator`'s, unchanged;
only the protect launch seams are overridden.  GCM fan-outs shard BOTH
ways: the general per-row form (each output row's key schedule + GHASH
matrix gather is chip-local), and the full-mesh per-LEG-matrix fast
path, which shards over the LEG axis (`_gcm_uniform_fanout_call` — the
product form of mesh/sharded.py's `sharded_gcm_fanout`); parity tests
pin both against the single-chip translator.
"""

from __future__ import annotations

import functools

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from libjitsi_tpu.core import staging
from libjitsi_tpu.mesh.compat import shard_map

from libjitsi_tpu.mesh.table import ShardedRowsMixin, _named
from libjitsi_tpu.sfu.translator import RtpTranslator, _split_fanout
from libjitsi_tpu.transform.srtp import kernel
from libjitsi_tpu.transform.srtp.policy import Cipher, SrtpProfile


class ShardedRtpTranslator(ShardedRowsMixin, RtpTranslator):
    """`RtpTranslator` whose re-encrypt fan-out runs sharded by leg.

    `translate_async` keeps its overlap contract in mesh mode: the
    sharded seams return a `staging.Launch` whose outputs stay on the
    mesh in lane layout (the CM fan-out: one packed plane), so
    `PendingTranslate` holds device-resident lane buffers until it is
    collected: the SfuBridge's next tick, on a mesh as on one chip.
    """

    def __init__(self, capacity: int, mesh: Mesh,
                 profile: SrtpProfile =
                 SrtpProfile.AES_CM_128_HMAC_SHA1_80):
        if profile.policy.cipher not in (Cipher.AES_CM, Cipher.NULL,
                                         Cipher.AES_GCM):
            raise ValueError(
                f"ShardedRtpTranslator supports AES-CM/NULL/AES-GCM "
                f"profiles; {profile.value} stays single-chip for now")
        self._init_sharding(mesh, capacity)
        super().__init__(capacity, profile)

    def _sharded_tables(self, group: str = "rtp"):
        return self._rk, (self._gm if self._gcm else self._mid)

    def fanout_warmups(self, rows: int, payload_len: int = 160):
        """`RtpTranslator.fanout_warmups` on a throwaway translator of
        this class on this mesh: the thunks launch the shard_map
        programs this translator launches (shared a mesh), leave its
        placed tables alone and, run side by side off the tick thread,
        open no span of its tracer."""
        scratch = ShardedRtpTranslator(self.capacity, self.mesh,
                                       self.profile)
        scratch._max_legs = self._max_legs
        return RtpTranslator.fanout_warmups(scratch, rows, payload_len)

    def _cm_fanout_call(self, recv, plane, length, payload_off, iv, idx
                        ) -> staging.Launch:
        """The seam's contract is `RtpTranslator._cm_fanout_call`'s:
        `plane` holds the packet bytes in its leading columns, and
        chip-local receiver row, length, payload offset, ROC
        (`idx >> 16` mod 2**32) and IV are packed behind them; ONE lane
        plane goes to the mesh, a block a chip, and one comes back
        (`ShardedRowsMixin._packed_call`).  Returns the
        `staging.Launch` in flight, whose `fetch()` gives host (wire
        bytes, wire lengths)."""
        return self._packed_call(
            self._fanout_fn(), plane, recv,
            (length, payload_off, (np.asarray(idx) >> 16) & 0xFFFFFFFF),
            iv, _split_fanout)

    def _gcm_fanout_call(self, recv, plane, length, payload_off, iv12
                         ) -> staging.Launch:
        """The GCM twin keeps an array an argument: the packet bytes
        are `plane`'s leading columns and nothing is packed; five lane
        arrays are routed to their owning chips, counted as they cross,
        and the launch holds the two outputs in lane layout until
        `fetch` scatters them back (`_mesh_launch`)."""
        data = plane[:, :plane.shape[-1] - staging.TAIL]
        outs, plan, n, nbytes = self._sharded_call(
            self._gcm_fanout_fn(), self._sharded_device(), recv,
            [data, np.asarray(length, dtype=np.int32), payload_off,
             iv12])
        return self._mesh_launch(outs, plan, n, nbytes, (None, np.int32))

    def _gcm_uniform_fanout_call(self, rr, pdata, plen, iv, aad_const
                                 ) -> staging.Launch:
        """Leg-partitioned full-mesh AEAD fan-out from the DEVICE-
        RESIDENT row-partitioned tables: legs route to their owning
        chips via the same owner plan as every sharded seam — no host
        re-gather / re-upload of the per-leg 16 KiB GHASH matrices
        (advisor r5: the old form shipped ~16 KiB x legs across the
        link every call) — the P packets broadcast, and each chip
        seals the same packets for ITS legs with zero collectives
        (the product form of mesh/sharded.py's sharded_gcm_fanout)."""
        plen32 = np.asarray(plen, dtype=np.int32)
        fn = self._gcm_uniform_fn(aad_const)
        (out,) = self._sharded_launch(
            fn, self._sharded_device(), rr, [np.asarray(iv)],
            extra_args=(np.asarray(pdata), plen32))
        # leg-major [G, P, W]; the output length is structural (AEAD
        # appends a 16B tag), so no second device output to scatter
        return staging.Launch(
            (out, plen32 + 16), h2d_arrays=4,
            h2d_bytes=4 * len(rr) + sum(
                int(np.asarray(a).nbytes) for a in (iv, pdata, plen32)))

    def _gcm_uniform_fn(self, off_const):
        key = ("gcm_uniform_fanout", off_const)
        fn = self._sh_fns.get(key)
        if fn is not None:
            return fn
        from libjitsi_tpu.kernels import gcm as gcm_kernel

        def _run(tab_rk, tab_gm, local, iv, data, length):
            out, _ = gcm_kernel.gcm_protect_fanout(
                data, length, tab_rk[local[0]], tab_gm[local[0]],
                iv[0], aad_const=off_const)
            return (out[None],)

        row3 = P(self._axes, None, None)
        lanes = P(self._axes, None)
        fn = jax.jit(shard_map(
            _named(_run, "mesh_fanout_protect_gcm_legs"), mesh=self.mesh,
            in_specs=(row3, row3, lanes,
                      P(self._axes, None, None, None),
                      P(None, None), P(None)),
            out_specs=(P(self._axes, None, None, None),),
            check_vma=False))
        # setdefault: concurrent warm-ups of one key, and every
        # translator of the mesh, share ONE jit
        return self._sh_fns.setdefault(key, fn)

    def _gcm_fanout_fn(self):
        """The per-row GCM fan-out program of this mesh: one a shape,
        the payload offset a lane array (`gcm_protect_rows`)."""
        key = ("gcm_fanout",)
        fn = self._sh_fns.get(key)
        if fn is not None:
            return fn
        from libjitsi_tpu.kernels import gcm as gcm_kernel

        def _run(tab_rk, tab_gm, local, data, length, off, iv12):
            out = gcm_kernel.gcm_protect_rows(
                data[0], length[0], off[0], tab_rk[local[0]],
                tab_gm[local[0]], iv12[0])
            return tuple(o[None] for o in out)

        row3 = P(self._axes, None, None)
        lanes = P(self._axes, None)
        fn = jax.jit(shard_map(
            _named(_run, "mesh_fanout_protect_gcm"), mesh=self.mesh,
            in_specs=(row3, row3, lanes, row3, lanes, lanes, row3),
            out_specs=(row3, lanes), check_vma=False))
        # setdefault: concurrent warm-ups of one key, and every
        # translator of the mesh, share ONE jit
        return self._sh_fns.setdefault(key, fn)

    def _fanout_fn(self):
        """The packed CM fan-out program of this mesh: one a shape, the
        payload offset a word of the plane (`srtp_protect_rows`)."""
        tag_len = self.policy.auth_tag_len
        encrypt = self.policy.cipher != Cipher.NULL
        return self._packed_fn(
            ("fanout", tag_len, encrypt), "mesh_fanout_protect",
            functools.partial(kernel.srtp_protect_rows, tag_len=tag_len,
                              encrypt=encrypt))
