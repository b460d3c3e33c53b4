"""GHASH (GCM's GF(2^128) universal hash) as batched MXU bit-matrix math.

No CLMUL instruction exists on TPU; the usual software fallbacks are
bit-serial loops or 4-bit Shoup tables (gather-heavy).  The TPU-native
observation: multiplication by the *fixed* hash key H is GF(2)-linear,
so the whole Horner step ``Y <- (Y xor X) * H`` is one 128x128 Boolean
matrix applied to a 128-bit vector — i.e. an int8 matmul (mod 2) that
maps straight onto the MXU, batched over packets.  The matrix M_H
(including polynomial reduction) is precomputed on host per session key
(H = AES_K(0^128)), exactly the kind of per-stream constant the SRTP
tables already gather per row.

Bit order follows NIST SP 800-38D: bit 0 = MSB of byte 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_R = 0xE1 << 120  # reduction polynomial bits (11100001 || 0^120)

#: bytes of one key's GHASH matrix as the tables hold it (int8 [128, 128])
GM_BYTES = 128 * 128


def gf_mult(x: int, y: int) -> int:
    """SP 800-38D §6.3 multiplication on 128-bit ints (b0 = MSB)."""
    z = 0
    v = y
    for i in range(128):
        if (x >> (127 - i)) & 1:
            z ^= v
        lsb = v & 1
        v >>= 1
        if lsb:
            v ^= _R
    return z


def ghash_matrix(h_block: bytes) -> np.ndarray:
    """[128, 128] uint8 matrix M with (M @ bits(X)) % 2 == bits(X * H).

    h_block: the 16-byte hash subkey H = AES_K(0^128).
    """
    h = int.from_bytes(h_block, "big")
    m = np.zeros((128, 128), dtype=np.uint8)
    for j in range(128):
        col = gf_mult(1 << (127 - j), h)
        for i in range(128):
            m[i, j] = (col >> (127 - i)) & 1
    return m


def ghash_matrix_batch(h_blocks: np.ndarray) -> np.ndarray:
    """Vectorized `ghash_matrix`: [S, 16] uint8 H blocks -> [S, 128, 128].

    Column j of M_H is H * x^j in GF(2^128); successive columns follow by
    one right-shift + conditional reduction, so the whole matrix builds in
    128 vector steps across all S streams (vs the scalar version's
    128x128 Python loop per stream — the GCM install-plane bottleneck).
    """
    hb = np.atleast_2d(np.asarray(h_blocks, dtype=np.uint8))
    s = hb.shape[0]
    # [S, 128] bit vectors, bit 0 = MSB of byte 0 (SP 800-38D order)
    col = np.unpackbits(hb, axis=1)
    rbits = np.unpackbits(
        np.frombuffer(_R.to_bytes(16, "big"), dtype=np.uint8))
    m = np.zeros((s, 128, 128), dtype=np.uint8)
    for j in range(128):
        m[:, :, j] = col
        lsb = col[:, 127:128]                  # coefficient of x^127
        col = np.concatenate(
            [np.zeros((s, 1), dtype=np.uint8), col[:, :-1]], axis=1)
        col = col ^ (lsb * rbits[None, :])
    return m


def ghash_ref(h_block: bytes, data: bytes) -> bytes:
    """Host reference GHASH over a whole (block-aligned) byte string."""
    if len(data) % 16:
        raise ValueError("ghash input must be block-aligned")
    h = int.from_bytes(h_block, "big")
    y = 0
    for i in range(0, len(data), 16):
        y = gf_mult(y ^ int.from_bytes(data[i:i + 16], "big"), h)
    return y.to_bytes(16, "big")


# ------------------------------------------------------------------ device

def _bytes_to_bits(blk):
    """uint8 [B, 16] -> int8 bits [B, 128] (bit 0 = MSB of byte 0)."""
    shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
    bits = (blk[:, :, None] >> shifts[None, None, :]) & 1
    return bits.reshape(blk.shape[0], 128).astype(jnp.int8)


def _bits_to_bytes(bits):
    w = (jnp.uint8(1) << jnp.arange(7, -1, -1, dtype=jnp.uint8))
    b = bits.reshape(bits.shape[0], 16, 8).astype(jnp.uint8) * w[None, None, :]
    return jnp.sum(b, axis=2).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("nblk_max",))
def ghash(matrices, data, nblocks, nblk_max: int):
    """Batched GHASH.

    matrices: int8 [B, 128, 128] per-row M_H (gathered per stream);
    data: uint8 [B, nblk_max*16] block-aligned, zero-padded;
    nblocks: int32 [B] actual block count per row.
    Returns uint8 [B, 16] digests.

    The Horner loop is sequential in blocks (data dependence) but each
    step is one batched MXU matmul over the whole packet batch; rows
    shorter than the running block index take identity steps.
    """
    b = data.shape[0]
    y = jnp.zeros((b, 128), dtype=jnp.int8)

    def body(i, y):
        blk = jax.lax.dynamic_slice_in_dim(data, i * 16, 16, axis=1)
        x = _bytes_to_bits(blk)
        t = jnp.bitwise_xor(y, x)
        prod = jnp.einsum("bij,bj->bi", matrices, t,
                          preferred_element_type=jnp.int32)
        y2 = (prod & 1).astype(jnp.int8)
        active = (i < nblocks)[:, None]
        return jnp.where(active, y2, y)

    y = jax.lax.fori_loop(0, nblk_max, body, y)
    return _bits_to_bytes(y)


def ghash_grouped(matrices, data, nblocks, nblk_max: int):
    """Grouped GHASH: G legs x P rows sharing one M_H per leg.

    matrices: int8 [G, 128, 128]; data: uint8 [G, P, nblk_max*16];
    nblocks: int32 [G, P].  Returns uint8 [G, P, 16].

    The per-row form (`ghash`) gathers a 16 KiB matrix PER ROW — for an
    SFU fan-out of P packets x G receivers that is P x G x 16 KiB of HBM
    traffic for key material alone, and it capped the GCM launch size.
    Here each leg's matrix is read once and applied to all its rows as
    one [128,128] x [128, P] MXU matmul per Horner step.
    """
    g, p, _ = data.shape
    y = jnp.zeros((g, p, 128), dtype=jnp.int8)

    def body(i, y):
        blk = jax.lax.dynamic_slice_in_dim(data, i * 16, 16, axis=2)
        x = _bytes_to_bits(blk.reshape(g * p, 16)).reshape(g, p, 128)
        t = jnp.bitwise_xor(y, x)
        prod = jnp.einsum("gij,gpj->gpi", matrices, t,
                          preferred_element_type=jnp.int32)
        y2 = (prod & 1).astype(jnp.int8)
        active = (i < nblocks)[..., None]
        return jnp.where(active, y2, y)

    y = jax.lax.fori_loop(0, nblk_max, body, y)
    return _bits_to_bytes(y.reshape(g * p, 128)).reshape(g, p, 16)


# ------------------------------------------------- packed (VPU) variant

def _pack_bits(bits):
    """0/1 int [..., 128] -> uint32 words [..., 4]; bit j lands at bit
    (31 - j%32) of word j//32, matching `_bytes_to_words` below so the
    AND/popcount parity below is order-consistent."""
    w = bits.astype(jnp.uint32).reshape(*bits.shape[:-1], 4, 32)
    shifts = jnp.arange(31, -1, -1, dtype=jnp.uint32)
    return jnp.sum(w << shifts, axis=-1, dtype=jnp.uint32)


def _bytes_to_words(blk):
    """uint8 [..., 16] -> uint32 [..., 4] big-endian words (MSB of byte
    4k at bit 31 of word k — the same 128-bit order `_bytes_to_bits`
    flattens to)."""
    b = blk.astype(jnp.uint32).reshape(*blk.shape[:-1], 4, 4)
    shifts = jnp.array([24, 16, 8, 0], dtype=jnp.uint32)
    return jnp.sum(b << shifts, axis=-1, dtype=jnp.uint32)


def _words_to_bytes(wds):
    shifts = jnp.array([24, 16, 8, 0], dtype=jnp.uint32)
    b = (wds[..., :, None] >> shifts) & 0xFF
    return b.reshape(*wds.shape[:-1], 16).astype(jnp.uint8)


def ghash_grouped_packed(matrices, data, nblocks, nblk_max: int):
    """`ghash_grouped` with the GF(2) matvec as packed-word AND +
    popcount parity instead of an int8 matmul.

    Same signature, bit-identical digests.  The einsum form burns one
    MXU MAC per matrix BIT — ideal where the MXU is otherwise idle,
    32x pure waste on backends whose vector unit has native
    population_count (XLA:CPU).  Here each Horner step ANDs the 128
    packed matrix rows [G, 128, 4]x[G, P, 4] and reduces with
    popcount, so the work per step is 128 uint32 lanes instead of
    128x128 int8 MACs.  Neither form is hardcoded anywhere: both are
    registered as providers on the GCM ops and the kernel registry's
    benchmark-and-pick keeps whichever measures faster per backend.
    """
    g, p, _ = data.shape
    mp = _pack_bits(matrices)                       # [G, 128, 4]
    y = jnp.zeros((g, p, 4), dtype=jnp.uint32)

    def body(i, y):
        blk = jax.lax.dynamic_slice_in_dim(data, i * 16, 16, axis=2)
        t = jnp.bitwise_xor(y, _bytes_to_words(blk))
        hits = jax.lax.population_count(
            mp[:, None, :, :] & t[:, :, None, :])   # [G, P, 128, 4]
        bits = jnp.sum(hits, axis=-1, dtype=jnp.uint32) & 1
        y2 = _pack_bits(bits)
        active = (i < nblocks)[..., None]
        return jnp.where(active, y2, y)

    y = jax.lax.fori_loop(0, nblk_max, body, y)
    return _words_to_bytes(y)
