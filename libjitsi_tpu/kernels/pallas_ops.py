"""Pallas TPU kernels for the conference hot ops.

BASELINE.json's north star names Pallas for the per-packet/PCM hot math
("...AudioMixer's N-participant PCM sum become Pallas kernels...").  This
module provides the Pallas implementations; `kernels.registry` pairs each
with its XLA twin and — like the reference's
`org.jitsi.impl.neomedia.transform.srtp.crypto.Aes`, which benchmarks
SunJCE/BouncyCastle/OpenSSL at startup and keeps the fastest — selects
per op by measurement, not by assumption: the registry keeps whichever
wins on the deployment's hardware (`chip_smoke.py` prints the v5e's
pick and both timings).

Kernel design notes
- One fused VMEM pass per conference frame: the [N, F] PCM block is read
  once; total-sum, mix-minus, clipping and the RFC 6465 level reduction
  all happen before anything returns to HBM.  The XLA path materializes
  the same math as two programs (mix and levels) when called separately.
- No gathers: Mosaic on this toolchain rejects table gathers (the AES
  S-box experiment fails to lower), so only gather-free ops live here.
- Outputs are int32 (int16/uint8 tiles need (16,128)/(32,128) sublane
  alignment; the cheap narrowing cast happens outside the kernel).
- Everything is interpret-mode testable on CPU (tests force
  `interpret=True`), matching the survey's test strategy (§5: "interpret
  -mode Pallas runs in CI").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

I16_MIN = -32768
I16_MAX = 32767


def _mix_kernel(pcm_ref, active_ref, out_ref, lvl_ref):
    """Fused mix-minus + RFC 6465 levels over one [N, F] frame block."""
    pcm = pcm_ref[:].astype(jnp.int32)
    active = active_ref[:].astype(jnp.int32)  # [N, 1] 0/1
    contrib = pcm * active
    total = jnp.sum(contrib, axis=0, keepdims=True)       # [1, F]
    out_ref[:] = jnp.clip(total - contrib, I16_MIN, I16_MAX)
    x = pcm.astype(jnp.float32) * (1.0 / 32768.0)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)          # [N, 1]
    db = 10.0 * jnp.log10(jnp.maximum(ms, 1e-12))
    lvl = jnp.clip(jnp.round(-db), 0, 127).astype(jnp.int32)
    silent = jnp.logical_or(ms <= 1e-12, active == 0)
    lvl_ref[:] = jnp.where(silent, jnp.int32(127), lvl)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mix_minus_pallas(pcm, active, interpret: bool = False):
    """Pallas twin of `conference.mixer.mix_minus`.

    pcm int16 [N, F], active bool [N] -> (out int16 [N, F], levels uint8
    [N]).  Bit-identical to the XLA path (same clipping, same dBov
    rounding, inactive/silent rows report 127).
    """
    n, f = pcm.shape
    act = jnp.asarray(active, dtype=jnp.int32).reshape(n, 1)
    out, lvl = pl.pallas_call(
        _mix_kernel,
        out_shape=(jax.ShapeDtypeStruct((n, f), jnp.int32),
                   jax.ShapeDtypeStruct((n, 1), jnp.int32)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM)),
        interpret=interpret,
    )(jnp.asarray(pcm), act)
    return out.astype(jnp.int16), lvl.reshape(n).astype(jnp.uint8)
