"""Conference audio mixer — N-way PCM mix-minus as one batched device op.

The reference's `org.jitsi.impl.neomedia.conference.AudioMixer` (with
`AudioMixerPushBufferStream` pulling PCM from every input stream and one
`AudioMixingPushBufferStream` per output) computes, per participant i,
``sum_{j != i} pcm_j`` with int-range clipping — a pull-graph of per-stream
Java objects.  On TPU this inverts into dense math over an ``[N, F]`` frame
matrix:

    total   = sum_j pcm_j                       (one reduction)
    out_i   = clip(total - pcm_i)               (broadcast subtract-self)
    level_i = RFC 6465 dBov from mean square    (free by-product)

which is exactly the "compute total sum then subtract self" trick the
reference uses to avoid the O(N^2) naive mix — here it is additionally one
fused XLA program over the whole conference, and the reduction becomes a
`psum` over the participant axis when the conference is sharded across
chips (see libjitsi_tpu.mesh).

Audio levels (RFC 6465, used by the CSRC audio-level header extension and
the active-speaker detector — reference:
org.jitsi.impl.neomedia.audiolevel.AudioLevelCalculator) are 0..127 dBov
where 0 is overload and 127 is silence.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

I16_MIN = -32768
I16_MAX = 32767


def audio_levels(pcm, active=None):
    """RFC 6465 audio level per participant: uint8 [N] in 0..127 dBov.

    pcm: int16/int32 [N, F].  Silence (all-zero frame or inactive row)
    reports 127.  0 dBov corresponds to a full-scale square wave.
    """
    x = pcm.astype(jnp.float32) / 32768.0
    ms = jnp.mean(x * x, axis=-1)
    db = 10.0 * jnp.log10(jnp.maximum(ms, 1e-12))  # dBov, <= 0
    level = jnp.clip(jnp.round(-db), 0, 127).astype(jnp.uint8)
    level = jnp.where(ms <= 1e-12, jnp.uint8(127), level)
    if active is not None:
        level = jnp.where(active, level, jnp.uint8(127))
    return level


def mix_minus(pcm, active=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mix-minus over one frame: (out int16 [N, F], levels uint8 [N]).

    out_i = saturate(sum_{j active, j != i} pcm_j); inactive rows receive
    the full mix (they contribute nothing, so total - 0 = total), matching
    the reference where a receive-only participant hears everyone.
    """
    # the C=1 case of mix_minus_many — ONE source of truth for the mix
    # math so the single-conference and whole-bridge paths cannot diverge
    out, levels = mix_minus_many(
        jnp.asarray(pcm)[None],
        None if active is None else jnp.asarray(active)[None])
    return out[0], levels[0]


@jax.jit
def _mix_jit(pcm, active):
    return mix_minus(pcm, active)


def mix_minus_many(pcm, active=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mix-minus over MANY conferences in one launch.

    pcm: int16 [C, N, F] — C conferences of up to N participants;
    active: bool [C, N].  Returns (out int16 [C, N, F], levels uint8
    [C, N]).  A bridge hosts hundreds of conferences but a single-
    conference launch is dispatch-bound (~40 µs of overhead for ~10 µs
    of math at N=256), so the conference axis is batched the same way
    the SRTP path batches streams: one device program per tick for the
    whole bridge.  The reference's per-AudioMixer thread model has no
    analog for this — it is the TPU-first inversion of §2.4.
    """
    pcm = jnp.asarray(pcm, dtype=jnp.int32)
    with jax.named_scope("mix"):
        if active is None:
            contrib = pcm
        else:
            contrib = jnp.where(active[:, :, None], pcm, 0)
        total = jnp.sum(contrib, axis=1, keepdims=True)     # [C, 1, F]
        out = jnp.clip(total - contrib, I16_MIN,
                       I16_MAX).astype(jnp.int16)
    return out, audio_levels(pcm, active)


@jax.jit
def _mix_many_jit(pcm, active):
    return mix_minus_many(pcm, active)


def _mix_pallas(pcm, active):
    # interpret mode exists for the CPU tests only (bit-identical); on
    # an accelerator the kernel compiles for real or fails loudly
    from libjitsi_tpu.kernels.pallas_ops import mix_minus_pallas
    interpret = jax.default_backend() == "cpu"
    return mix_minus_pallas(pcm, active, interpret=interpret)


# provider registry (reference pattern: crypto.Aes benchmarks providers
# and installs the fastest; here per shape signature on first use)
from libjitsi_tpu.kernels import registry as _registry  # noqa: E402

_registry.register("mix_minus", "xla", _mix_jit)
_registry.register("mix_minus", "pallas", _mix_pallas)


class MixerBridge:
    """Whole-bridge mixing: C conferences ticked as one device launch.

    The multi-conference analog of AudioMixer (which the reference
    instantiates once per conference, each with its own pull threads):
    deposit frames with ``push(cid, sid, pcm)``, call ``tick()`` once
    per frame period, read back each conference's mix-minus rows and
    RFC 6465 levels.  One launch for the whole bridge amortizes the
    ~40 µs dispatch overhead that dominates a single small conference.
    """

    def __init__(self, conferences: int = 64, capacity: int = 64,
                 frame_samples: int = 960):
        self.conferences = conferences
        self.capacity = capacity
        self.frame_samples = frame_samples
        self.active = np.zeros((conferences, capacity), dtype=bool)
        self._frame = np.zeros((conferences, capacity, frame_samples),
                               dtype=np.int16)
        self._in_use = np.zeros(conferences, dtype=bool)
        # compile at setup (see AudioMixer.__init__)
        jax.block_until_ready(_mix_many_jit(
            jnp.asarray(self._frame), jnp.asarray(self.active)))

    def alloc_conference(self) -> int:
        free = np.nonzero(~self._in_use)[0]
        if not len(free):
            raise RuntimeError(f"all {self.conferences} conference rows "
                               "in use")
        cid = int(free[0])
        self._in_use[cid] = True
        return cid

    def release_conference(self, cid: int) -> None:
        self._check(cid)     # stale/negative cid would clear another row
        self._in_use[cid] = False
        self.active[cid] = False
        self._frame[cid] = 0

    def _check(self, cid: int, sid: int = 0) -> None:
        # negative indices would silently wrap to another conference's
        # row; stale cids (released, possibly reallocated) would leak
        # audio across conferences — both must fail loudly
        if not (0 <= cid < self.conferences) or not self._in_use[cid]:
            raise KeyError(f"conference {cid} not allocated")
        if not (0 <= sid < self.capacity):
            raise IndexError(f"participant {sid} out of range")

    def add_participant(self, cid: int, sid: int) -> None:
        self._check(cid, sid)
        self.active[cid, sid] = True
        self._frame[cid, sid] = 0

    def remove_participant(self, cid: int, sid: int) -> None:
        self._check(cid, sid)
        self.active[cid, sid] = False
        self._frame[cid, sid] = 0

    def push(self, cid: int, sid: int, pcm: np.ndarray) -> None:
        self._check(cid, sid)
        f = np.asarray(pcm, dtype=np.int16)
        if f.shape != (self.frame_samples,):
            raise ValueError(
                f"frame must be [{self.frame_samples}] int16, got {f.shape}")
        self._frame[cid, sid] = f

    def tick(self) -> Tuple[np.ndarray, np.ndarray]:
        """One frame period for every conference: (out int16 [C, N, F],
        levels uint8 [C, N]); deposited frames are consumed."""
        out, levels = _mix_many_jit(jnp.asarray(self._frame),
                                    jnp.asarray(self.active))
        # materialize BEFORE zeroing (see AudioMixer.mix)
        out_np, levels_np = np.asarray(out), np.asarray(levels)
        self._frame[:] = 0
        return out_np, levels_np


class AudioMixer:
    """Host-facing mixer over a fixed participant capacity.

    The reference exposes the mix as a capture `MediaDevice`
    (`AudioMixerMediaDevice`) that each `MediaStream` pulls from; here a
    conference is a row range: deposit each participant's decoded frame
    with `push()`, call `mix()` once per frame tick, read back per-
    participant output and levels.  48 kHz mono int16 is the normalized
    interchange format (the reference normalizes formats in
    `AudioMixer.getOutFormatFromInDataSources`; our io/codec layer
    resamples to 48k before deposit).
    """

    def __init__(self, capacity: int = 256, frame_samples: int = 960,
                 mix_fn=None):
        # 960 samples = 20 ms @ 48 kHz, the dominant Opus/RTP ptime.
        # mix_fn overrides the provider registry with a caller-built
        # launcher — the mesh bridge passes sharded_mix_minus(mesh) so
        # the participant axis psums over ICI (libjitsi_tpu.mesh).
        self.capacity = capacity
        self.frame_samples = frame_samples
        self.active = np.zeros(capacity, dtype=bool)
        self._frame = np.zeros((capacity, frame_samples), dtype=np.int16)
        self._mix_fn = mix_fn
        # compile + provider-benchmark NOW, at setup time — a 20 ms mix
        # tick must never absorb jit compiles or the registry's timing
        # runs (reference analog: crypto.Aes benches providers at startup)
        if mix_fn is None:
            _registry.warmup("mix_minus", jnp.asarray(self._frame),
                             jnp.asarray(self.active))
        else:
            jax.block_until_ready(mix_fn(jnp.asarray(self._frame),
                                         jnp.asarray(self.active)))

    def add_participant(self, sid: int) -> None:
        self.active[sid] = True
        self._frame[sid] = 0

    def remove_participant(self, sid: int) -> None:
        self.active[sid] = False
        self._frame[sid] = 0

    def push(self, sid: int, pcm: np.ndarray) -> None:
        """Deposit one 20 ms frame for participant `sid` (int16 [F])."""
        f = np.asarray(pcm, dtype=np.int16)
        if f.shape != (self.frame_samples,):
            raise ValueError(
                f"frame must be [{self.frame_samples}] int16, got {f.shape}")
        self._frame[sid] = f

    def push_batch(self, sids: np.ndarray, frames: np.ndarray) -> None:
        """Deposit many participants' frames at once (int16 [K, F]) —
        the dense receive plane's deposit path (one array write)."""
        frames = np.asarray(frames, dtype=np.int16)
        if frames.ndim != 2 or frames.shape[1] != self.frame_samples:
            raise ValueError(
                f"frames must be [K, {self.frame_samples}] int16, "
                f"got {frames.shape}")
        self._frame[np.asarray(sids, dtype=np.int64)] = frames

    def mix(self) -> Tuple[np.ndarray, np.ndarray]:
        """Run one frame tick: returns (out int16 [N, F], levels uint8 [N]).

        Frames are consumed: participants that miss the next tick
        contribute silence (the reference's pull model blocks briefly then
        pads silence; a server mixer must never block on a slow sender).
        """
        if self._mix_fn is not None:
            out, levels = self._mix_fn(jnp.asarray(self._frame),
                                       jnp.asarray(self.active))
        else:
            out, levels = _registry.call("mix_minus",
                                         jnp.asarray(self._frame),
                                         jnp.asarray(self.active))
        # materialize BEFORE zeroing: on the CPU backend jnp.asarray can
        # alias the host buffer and dispatch is async — zeroing first
        # races the device read (seen as a rare wrong-mix flake)
        out_np, levels_np = np.asarray(out), np.asarray(levels)
        self._frame[:] = 0
        return out_np, levels_np
