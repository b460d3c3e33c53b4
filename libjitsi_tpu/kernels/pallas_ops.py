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
- A grid over the participant axis, two passes: the first walks the
  [N, F] PCM in row tiles, accumulates the [1, F] total in a resident
  output block and writes each tile's RFC 6465 levels; the second walks
  the same tiles and writes clip(total - contrib).  A tile is sized to
  VMEM (`_tile_rows`), so N is bounded by HBM, not by VMEM: one
  whole-array VMEM block compiled for the v5e up to [2048, 960] and was
  refused (vmem exhausted) at [4096, 960], under the capacity of a
  bridge.  N <= one tile is the single-block case (grid of 1).
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


#: VMEM budget of one int32 [rows, F] tile; a grid step holds the int16
#: input tile, the int32 output tile (both double-buffered) and a few
#: int32/f32 temporaries of this size inside the default scoped limit
_TILE_BYTES = 2 << 20
_TILE_ROWS_MAX = 512


def _tile_rows(n: int, f: int) -> int:
    """Rows per grid step: a multiple of 16 (the int16 sublane tile),
    or all of N when that is no more."""
    rows = max(16, min(_TILE_ROWS_MAX, _TILE_BYTES // (4 * f) // 16 * 16))
    return n if n <= rows else rows


def _sum_levels_kernel(pcm_ref, active_ref, total_ref, lvl_ref):
    """Pass 1 over one row tile: add the tile's contribution to the
    resident [1, F] total, and write the tile's RFC 6465 levels."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        total_ref[...] = jnp.zeros_like(total_ref)

    pcm = pcm_ref[...].astype(jnp.int32)
    active = active_ref[...]                              # [T, 1] 0/1
    total_ref[...] += jnp.sum(pcm * active, axis=0, keepdims=True)
    x = pcm.astype(jnp.float32) * (1.0 / 32768.0)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)          # [T, 1]
    db = 10.0 * jnp.log10(jnp.maximum(ms, 1e-12))
    lvl = jnp.clip(jnp.round(-db), 0, 127).astype(jnp.int32)
    silent = jnp.logical_or(ms <= 1e-12, active == 0)
    lvl_ref[...] = jnp.where(silent, jnp.int32(127), lvl)


def _minus_kernel(pcm_ref, active_ref, total_ref, out_ref):
    """Pass 2 over one row tile: everyone else's sum, clipped."""
    contrib = pcm_ref[...].astype(jnp.int32) * active_ref[...]
    out_ref[...] = jnp.clip(total_ref[...] - contrib, I16_MIN, I16_MAX)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mix_minus_pallas(pcm, active, interpret: bool = False):
    """Pallas twin of `conference.mixer.mix_minus`.

    pcm int16 [N, F], active bool [N] -> (out int16 [N, F], levels uint8
    [N]).  Bit-identical to the XLA path (same clipping, same dBov
    rounding, inactive/silent rows report 127).
    """
    n, f = pcm.shape
    t = _tile_rows(n, f)
    steps = pl.cdiv(n, t)
    pad = steps * t - n           # inactive zero rows: add 0 to the sum
    pcm = jnp.pad(jnp.asarray(pcm), ((0, pad), (0, 0)))
    act = jnp.pad(jnp.asarray(active, dtype=jnp.int32).reshape(n, 1),
                  ((0, pad), (0, 0)))
    rows = pl.BlockSpec((t, f), lambda i: (i, 0))
    col = pl.BlockSpec((t, 1), lambda i: (i, 0))
    whole = pl.BlockSpec((1, f), lambda i: (0, 0))
    with jax.named_scope("mix"):
        total, lvl = pl.pallas_call(
            _sum_levels_kernel,
            grid=(steps,),
            out_shape=(jax.ShapeDtypeStruct((1, f), jnp.int32),
                       jax.ShapeDtypeStruct((n + pad, 1), jnp.int32)),
            in_specs=[rows, col],
            out_specs=(whole, col),
            # the total's block is revisited by every step: sequential
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(pcm, act)
        out = pl.pallas_call(
            _minus_kernel,
            grid=(steps,),
            out_shape=jax.ShapeDtypeStruct((n + pad, f), jnp.int32),
            in_specs=[rows, col, whole],
            out_specs=rows,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=interpret,
        )(pcm, act, total)
    return out[:n].astype(jnp.int16), lvl[:n, 0].astype(jnp.uint8)
