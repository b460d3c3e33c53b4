"""Batched AES-GCM AEAD (SP 800-38D) for SRTP/SRTCP (RFC 7714).

Layout convention matches the SRTP packet: ``data[:aad_len]`` is the
AAD (the RTP/RTCP header) and ``data[aad_len:length]`` the plaintext /
ciphertext — encryption happens in place, the 16-byte tag is appended.
CTR rides the existing AES kernel (J0 = IV||0x00000001; within one
packet the 32-bit counter cannot wrap, so the full-128-bit increment is
equivalent); the tag rides the GHASH MXU matmul kernel with the
per-row index arithmetic building each row's ``AAD||0* || C||0* ||
len(A)||len(C)`` block stream without host round trips.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from libjitsi_tpu.core.packet import payload_blocks
from libjitsi_tpu.kernels.scatter import gather_span as _gather_span
from libjitsi_tpu.kernels.scatter import scatter_bytes, shift_right
from libjitsi_tpu.kernels.aes import (aes_encrypt, ctr_crypt_offset,
                                      ctr_crypt_rows,
                                      ctr_crypt_uniform)
from libjitsi_tpu.kernels.ghash import ghash

TAG_LEN = 16


def _ceil16(x):
    return (x + 15) & ~15


def _ghash_width(capacity: int) -> int:
    """Tight bound on the GHASH input row: padded-AAD + padded-CT +
    length block.  ceil16(a) + ceil16(c) <= ceil16(a + c) + 16 for any
    split, and a + c <= capacity, so ceil16(cap) + 16 covers the data
    and +16 the length block.  (The old 2*cap+16 bound nearly doubled
    the Horner matmul rounds every GCM path pays.)"""
    return _ceil16(capacity) + 32


def _length_block(cols, ap, cp, abits, cbits):
    """be64(aad_bits) || be64(ct_bits) bytes at columns [ap+cp, ap+cp+16).

    Shared by both GHASH-input builders — the two paths MUST stay
    bit-identical or the uniform fast path's tags stop verifying against
    the general path's.  Bit counts fit in 32 bits (capacity << 2^29),
    so bytes 0..3 of each u64 are zero and the math stays in int32.
    """
    p = cols - (ap + cp)
    shift_a = jnp.clip(8 * (7 - p), 0, 24)
    shift_c = jnp.clip(8 * (15 - p), 0, 24)
    byte = jnp.where(
        (p >= 4) & (p < 8), (abits >> shift_a) & 0xFF,
        jnp.where((p >= 12) & (p < 16), (cbits >> shift_c) & 0xFF, 0)
    ).astype(jnp.uint8)
    return byte, p


def _build_ghash_input(data, aad_len, ct_len, width: int,
                       shifted: bool = False):
    """[B, W] packet bytes -> [B, width] GHASH block stream + counts.

    Row layout: AAD (0-padded to 16) || ciphertext (0-padded) ||
    be64(aad_bits) || be64(ct_bits).

    `shifted`: the same bytes without the [B, width] gather: the AAD
    stays where it is and the ciphertext moves right by ceil16(aad) -
    aad < 16 columns, a four-select ladder (`scatter.shift_right`); the
    edge padding reads what the gather's clip reads past the packet.
    """
    bsz, cap = data.shape
    a = aad_len.astype(jnp.int32)
    c = ct_len.astype(jnp.int32)
    ap = (a + 15) & ~15
    cp = (c + 15) & ~15
    cols = jnp.arange(width, dtype=jnp.int32)[None, :]

    in_aad = cols < a[:, None]
    k = cols - ap[:, None]
    in_ct = (k >= 0) & (k < c[:, None])
    if shifted:
        wide = jnp.pad(data, ((0, 0), (0, width - cap)), mode="edge")
        gathered = jnp.where(in_aad, wide, shift_right(wide, ap - a, 16))
    else:
        src = jnp.where(in_aad, cols, jnp.where(in_ct, a[:, None] + k, 0))
        gathered = jnp.take_along_axis(
            data, jnp.clip(src, 0, cap - 1), axis=1)

    len_byte, p = _length_block(cols, ap[:, None], cp[:, None],
                                (a * 8)[:, None], (c * 8)[:, None])

    out = jnp.where(in_aad | in_ct, gathered, 0).astype(jnp.uint8)
    out = jnp.where((p >= 0) & (p < 16), len_byte, out)
    nblocks = (ap + cp + 16) // 16
    return out, nblocks


def _build_ghash_input_uniform(data, aad: int, ct_len, width: int):
    """Uniform-AAD twin of `_build_ghash_input`: with every row's AAD the
    same static size (SRTP: the 12-byte RTP header / 8-byte RTCP prefix),
    the AAD->padded-AAD and ciphertext shifts are static pad/slice ops —
    no [B, width] gather (the gather dominates the general path's cost on
    TPU, like the CTR alignment gather did)."""
    bsz, cap = data.shape
    c = ct_len.astype(jnp.int32)
    ap = _ceil16(aad)
    cp = (c + 15) & ~15
    cols = jnp.arange(width, dtype=jnp.int32)[None, :]

    # AAD bytes land at columns [0, aad); ct bytes at [ap, ap + c)
    aad_part = jnp.pad(data[:, :aad], ((0, 0), (0, width - aad)))
    ct_src = jnp.pad(data[:, aad:], ((0, 0), (0, max(0, width - (cap - aad)))))
    ct_part = jnp.pad(ct_src, ((0, 0), (ap, 0)))[:, :width]
    k = cols - ap
    in_aad = cols < aad
    in_ct = (k >= 0) & (k < c[:, None])
    out = jnp.where(in_aad, aad_part,
                    jnp.where(in_ct, ct_part, 0)).astype(jnp.uint8)

    len_byte, p = _length_block(cols, ap, cp[:, None],
                                jnp.full_like(c, aad * 8)[:, None],
                                (c * 8)[:, None])
    out = jnp.where((p >= 0) & (p < 16), len_byte, out)
    nblocks = (ap + cp + 16) // 16
    return out, nblocks


def _j0(iv12):
    """[B, 12] -> [B, 16] J0 = IV || 0x00000001."""
    b = iv12.shape[0]
    tail = jnp.tile(jnp.array([0, 0, 0, 1], dtype=jnp.uint8), (b, 1))
    return jnp.concatenate([iv12.astype(jnp.uint8), tail], axis=1)


def _inc32(block):
    """Increment the last 32 bits (big-endian) of [B, 16] blocks."""
    hi = block[:, :12]
    lo = block[:, 12:].astype(jnp.uint32)
    val = (lo[:, 0] << 24) | (lo[:, 1] << 16) | (lo[:, 2] << 8) | lo[:, 3]
    val = val + 1  # uint32 wraps naturally
    shifts = jnp.array([24, 16, 8, 0], dtype=jnp.uint32)
    lo2 = ((val[:, None] >> shifts[None, :]) & 0xFF).astype(jnp.uint8)
    return jnp.concatenate([hi, lo2], axis=1)


def _scatter_tag(data, pos, tag):
    # gather-free (kernels/scatter.py has the perf story)
    with jax.named_scope("scatter_tag"):
        return scatter_bytes(data, pos, tag, TAG_LEN)


def _tag(round_keys, gmat, data, aad_len, ct_len, j0, width: int,
         aad_const=None, shifted: bool = False):
    with jax.named_scope("tag"):
        if aad_const is not None:
            gin, nblk = _build_ghash_input_uniform(data, aad_const,
                                                   ct_len, width)
        else:
            gin, nblk = _build_ghash_input(data, aad_len, ct_len, width,
                                           shifted)
        with jax.named_scope("ghash"):
            s = ghash(gmat, gin, nblk, width // 16)
        ek_j0 = aes_encrypt(round_keys, j0)
        return jnp.bitwise_xor(s, ek_j0)


@functools.partial(jax.jit, static_argnames=("aad_const",))
def gcm_protect(data, length, aad_len, round_keys, gmat, iv12,
                aad_const=None):
    """Batched seal: encrypt data[aad:length] in place, append 16B tag.

    data [B, W] uint8; length/aad_len [B] int32; round_keys [B, R, 16];
    gmat [B, 128, 128] int8 (per-stream GHASH matrix); iv12 [B, 12].
    Returns (data', length + 16).
    """
    data = jnp.asarray(data, dtype=jnp.uint8)
    length = jnp.asarray(length, dtype=jnp.int32)
    aad_len = jnp.asarray(aad_len, dtype=jnp.int32)
    j0 = _j0(jnp.asarray(iv12))
    ctr0 = _inc32(j0)
    ct_len = length - aad_len
    if aad_const is not None:
        enc = ctr_crypt_uniform(round_keys, ctr0, data, aad_const, ct_len)
    else:
        enc = ctr_crypt_offset(round_keys, ctr0, data, aad_len, ct_len)
    width = _ghash_width(data.shape[1])
    tag = _tag(round_keys, gmat, enc, aad_len, ct_len, j0, width,
               aad_const)
    out = _scatter_tag(enc, length, tag)
    return out, length + TAG_LEN


@jax.jit
def gcm_protect_rows(data, length, aad_len, round_keys, gmat, iv12):
    """`gcm_protect` whose AAD length is DATA: arguments and bytes as
    its `aad_const=None` form, with neither of that form's gathers
    (`ctr_crypt_rows`, `_build_ghash_input`'s `shifted`), so there is
    one program a shape whatever header lengths a batch carries, at the
    static form's cost.  The SFU's per-row fan-out form.  Rows are RTP
    packets with room for their tag: `aad_len` >= 12 and `length` + 16
    <= W bound the keystream (`payload_blocks`)."""
    data = jnp.asarray(data, dtype=jnp.uint8)
    length = jnp.asarray(length, dtype=jnp.int32)
    aad_len = jnp.asarray(aad_len, dtype=jnp.int32)
    j0 = _j0(jnp.asarray(iv12))
    ct_len = length - aad_len
    enc = ctr_crypt_rows(round_keys, _inc32(j0), data, aad_len, ct_len,
                         nblocks=payload_blocks(data.shape[1], TAG_LEN))
    tag = _tag(round_keys, gmat, enc, aad_len, ct_len, j0,
               _ghash_width(data.shape[1]), shifted=True)
    return _scatter_tag(enc, length, tag), length + TAG_LEN


@functools.partial(jax.jit, static_argnames=("aad_const",))
def gcm_unprotect(data, length, aad_len, round_keys, gmat, iv12,
                  aad_const=None):
    """Batched open: verify tag, decrypt in place.

    Returns (data', length - 16, auth_ok).  Decrypt always runs
    (branch-free); callers mask failed rows.
    """
    data = jnp.asarray(data, dtype=jnp.uint8)
    length = jnp.asarray(length, dtype=jnp.int32)
    aad_len = jnp.asarray(aad_len, dtype=jnp.int32)
    mlen = length - TAG_LEN
    ct_len = mlen - aad_len
    j0 = _j0(jnp.asarray(iv12))
    width = _ghash_width(data.shape[1])
    want = _tag(round_keys, gmat, data, aad_len, ct_len, j0, width,
                aad_const)
    stored = _gather_span(data, mlen, TAG_LEN)
    auth_ok = jnp.all(stored == want, axis=1)
    ctr0 = _inc32(j0)
    if aad_const is not None:
        dec = ctr_crypt_uniform(round_keys, ctr0, data, aad_const, ct_len)
    else:
        dec = ctr_crypt_offset(round_keys, ctr0, data, aad_len, ct_len)
    return dec, mlen, auth_ok


def _grouped_tag(round_keys, gmat_g, enc, aad_len, ct_len, j0,
                 grid_rows, inv_pos, width: int, aad_const):
    """Per-stream-grouped tag for a mixed-stream batch.

    The per-row `_tag` gathers a 16 KiB GHASH matrix per packet — at
    batch 65536 that is 1 GiB of HBM traffic for key material, which
    capped the GCM launch size.  Here the host pre-groups
    rows by stream into a [G, P] grid (`grid_rows`: row index or -1
    padding) so each stream's matrix is read ONCE and applied to all its
    rows as one MXU matmul per Horner step (`ghash_grouped`), then the
    digests scatter back to batch order via `inv_pos`.
    """
    from libjitsi_tpu.kernels.ghash import ghash_grouped

    with jax.named_scope("tag"):
        if aad_const is not None:
            gin, nblk = _build_ghash_input_uniform(enc, aad_const, ct_len,
                                                   width)
        else:
            gin, nblk = _build_ghash_input(enc, aad_len, ct_len, width)
        g, p = grid_rows.shape
        safe = jnp.clip(grid_rows.reshape(-1), 0, enc.shape[0] - 1)
        gin_g = gin[safe].reshape(g, p, width)
        nblk_g = jnp.where(grid_rows >= 0, nblk[safe].reshape(g, p), 0)
        with jax.named_scope("ghash"):
            s = ghash_grouped(gmat_g, gin_g, nblk_g, width // 16)
        s_rows = s.reshape(g * p, 16)[inv_pos]
        ek_j0 = aes_encrypt(round_keys, j0)
        return jnp.bitwise_xor(s_rows, ek_j0)


@functools.partial(jax.jit, static_argnames=("aad_const",))
def gcm_protect_grouped(data, length, aad_len, round_keys, gmat_g, iv12,
                        grid_rows, inv_pos, aad_const=None):
    """`gcm_protect` with stream-grouped GHASH: round_keys [B, R, 16]
    stay per-row (cheap), gmat_g [G, 128, 128] is per GROUP."""
    data = jnp.asarray(data, dtype=jnp.uint8)
    length = jnp.asarray(length, dtype=jnp.int32)
    aad_len = jnp.asarray(aad_len, dtype=jnp.int32)
    j0 = _j0(jnp.asarray(iv12))
    ctr0 = _inc32(j0)
    ct_len = length - aad_len
    if aad_const is not None:
        enc = ctr_crypt_uniform(round_keys, ctr0, data, aad_const, ct_len)
    else:
        enc = ctr_crypt_offset(round_keys, ctr0, data, aad_len, ct_len)
    width = _ghash_width(data.shape[1])
    tag = _grouped_tag(round_keys, gmat_g, enc, aad_len, ct_len, j0,
                       grid_rows, inv_pos, width, aad_const)
    out = _scatter_tag(enc, length, tag)
    return out, length + TAG_LEN


@functools.partial(jax.jit, static_argnames=("aad_const",))
def gcm_unprotect_grouped(data, length, aad_len, round_keys, gmat_g,
                          iv12, grid_rows, inv_pos, aad_const=None):
    """`gcm_unprotect` with stream-grouped GHASH."""
    data = jnp.asarray(data, dtype=jnp.uint8)
    length = jnp.asarray(length, dtype=jnp.int32)
    aad_len = jnp.asarray(aad_len, dtype=jnp.int32)
    mlen = length - TAG_LEN
    ct_len = mlen - aad_len
    j0 = _j0(jnp.asarray(iv12))
    width = _ghash_width(data.shape[1])
    want = _grouped_tag(round_keys, gmat_g, data, aad_len, ct_len, j0,
                        grid_rows, inv_pos, width, aad_const)
    stored = _gather_span(data, mlen, TAG_LEN)
    auth_ok = jnp.all(stored == want, axis=1)
    ctr0 = _inc32(j0)
    if aad_const is not None:
        dec = ctr_crypt_uniform(round_keys, ctr0, data, aad_const, ct_len)
    else:
        dec = ctr_crypt_offset(round_keys, ctr0, data, aad_len, ct_len)
    return dec, mlen, auth_ok


# --- keystream-cache fast path ---------------------------------------------
#
# SRTP-GCM's per-packet AES work is fully determined by (session key,
# ssrc, packet index): the CTR keystream and the E(K, J0) tag mask can
# be computed before the packet exists.  The cached kernels below take
# that material pre-gathered per row (transform/srtp/keystream.py owns
# the window bookkeeping) and run only the irreducibly online half —
# the payload XOR and the ciphertext-dependent GHASH.  No round keys
# cross the jit boundary at all on this path.

def _cached_width(cap: int, aad_const: int, ks_bytes: int) -> int:
    """GHASH width for the cached path: the cache's hit test guarantees
    ct_len <= ks_bytes, so the Horner round count is bounded by the
    keystream window's byte depth, not the packet buffer's padded
    capacity — at the default 256-byte window that is ~18 rounds
    instead of ~96 for a 1504-byte buffer."""
    return min(_ghash_width(cap), _ceil16(aad_const) + _ceil16(ks_bytes) + 16)


def _xor_cached(data, ks, offset: int, ct_len):
    """XOR a cached keystream row into [offset, offset+ct_len) with the
    same static pad-shift as `_xor_window_uniform`.  `ks` is [B, KS]
    with KS possibly smaller than the packet width — the cache's hit
    test guarantees ct_len <= KS per row, so the right zero-pad is
    never reached by an inside column."""
    width = data.shape[1]
    ks = jnp.asarray(ks, dtype=jnp.uint8)
    pad = max(0, width - offset - ks.shape[1])
    ks_aligned = jnp.pad(ks, ((0, 0), (offset, pad)))[:, :width]
    col = jnp.arange(width, dtype=jnp.int32)[None, :]
    ln = jnp.asarray(ct_len, dtype=jnp.int32)[:, None]
    inside = (col >= offset) & (col < offset + ln)
    return jnp.where(inside, data ^ ks_aligned, data)


@functools.partial(jax.jit, static_argnames=("aad_const",))
def gcm_protect_cached(data, length, ks, ek_j0, gmat, aad_const: int):
    """`gcm_protect` with the AES plane precomputed: ks [B, KS] uint8 is
    the CTR keystream starting at inc32(J0); ek_j0 [B, 16] the cached
    E(K, J0) tag masks; gmat [B, 128, 128] per-row GHASH matrices.
    Only the uniform-AAD shape exists — the cache serves all-or-nothing
    batches whose headers agree on one payload offset.  Bit-exact with
    `gcm_protect` by construction: the GHASH-input builder and tag
    scatter are the same code, and CTR keystream ⊕ data is the same
    bytes regardless of when the keystream was generated."""
    data = jnp.asarray(data, dtype=jnp.uint8)
    length = jnp.asarray(length, dtype=jnp.int32)
    ct_len = length - aad_const
    enc = _xor_cached(data, ks, aad_const, ct_len)
    width = _cached_width(data.shape[1], aad_const, ks.shape[1])
    gin, nblk = _build_ghash_input_uniform(enc, aad_const, ct_len, width)
    s = ghash(gmat, gin, nblk, width // 16)
    tag = jnp.bitwise_xor(s, jnp.asarray(ek_j0, dtype=jnp.uint8))
    out = _scatter_tag(enc, length, tag)
    return out, length + TAG_LEN


@functools.partial(jax.jit, static_argnames=("aad_const",))
def gcm_unprotect_cached(data, length, ks, ek_j0, gmat, aad_const: int):
    """`gcm_unprotect` on cached keystream/tag-mask rows.  Returns
    (data', length - 16, auth_ok); decrypt always runs (branch-free)."""
    data = jnp.asarray(data, dtype=jnp.uint8)
    length = jnp.asarray(length, dtype=jnp.int32)
    mlen = length - TAG_LEN
    ct_len = mlen - aad_const
    width = _cached_width(data.shape[1], aad_const, ks.shape[1])
    gin, nblk = _build_ghash_input_uniform(data, aad_const, ct_len, width)
    s = ghash(gmat, gin, nblk, width // 16)
    want = jnp.bitwise_xor(s, jnp.asarray(ek_j0, dtype=jnp.uint8))
    stored = _gather_span(data, mlen, TAG_LEN)
    auth_ok = jnp.all(stored == want, axis=1)
    dec = _xor_cached(data, ks, aad_const, ct_len)
    return dec, mlen, auth_ok


def _cached_grouped_digest(gmat_g, enc, ct_len, grid_rows, inv_pos,
                           width: int, aad_const: int, packed: bool):
    """Grouped-GHASH digest for the cached path (same grid/inverse
    plumbing as `_grouped_tag`, minus the AES tag-mask encrypt).
    `packed` selects the AND/popcount GF(2) matvec over the int8 MXU
    matmul — both are registered as providers and the registry's
    benchmark-and-pick keeps the faster one per backend."""
    from libjitsi_tpu.kernels.ghash import (ghash_grouped,
                                            ghash_grouped_packed)

    gin, nblk = _build_ghash_input_uniform(enc, aad_const, ct_len, width)
    g, p = grid_rows.shape
    safe = jnp.clip(grid_rows.reshape(-1), 0, enc.shape[0] - 1)
    gin_g = gin[safe].reshape(g, p, width)
    nblk_g = jnp.where(grid_rows >= 0, nblk[safe].reshape(g, p), 0)
    fn = ghash_grouped_packed if packed else ghash_grouped
    with jax.named_scope("ghash"):
        s = fn(gmat_g, gin_g, nblk_g, width // 16)
    return s.reshape(g * p, 16)[inv_pos]


@functools.partial(jax.jit, static_argnames=("aad_const", "packed"))
def gcm_protect_cached_grouped(data, length, ks, ek_j0, gmat_g,
                               grid_rows, inv_pos, aad_const: int,
                               packed: bool = False):
    """`gcm_protect_cached` with stream-grouped GHASH (gmat_g is per
    GROUP, read once per stream instead of once per row)."""
    data = jnp.asarray(data, dtype=jnp.uint8)
    length = jnp.asarray(length, dtype=jnp.int32)
    ct_len = length - aad_const
    enc = _xor_cached(data, ks, aad_const, ct_len)
    width = _cached_width(data.shape[1], aad_const, ks.shape[1])
    s_rows = _cached_grouped_digest(gmat_g, enc, ct_len, grid_rows,
                                    inv_pos, width, aad_const, packed)
    tag = jnp.bitwise_xor(s_rows, jnp.asarray(ek_j0, dtype=jnp.uint8))
    out = _scatter_tag(enc, length, tag)
    return out, length + TAG_LEN


@functools.partial(jax.jit, static_argnames=("aad_const", "packed"))
def gcm_unprotect_cached_grouped(data, length, ks, ek_j0, gmat_g,
                                 grid_rows, inv_pos, aad_const: int,
                                 packed: bool = False):
    """`gcm_unprotect_cached` with stream-grouped GHASH."""
    data = jnp.asarray(data, dtype=jnp.uint8)
    length = jnp.asarray(length, dtype=jnp.int32)
    mlen = length - TAG_LEN
    ct_len = mlen - aad_const
    width = _cached_width(data.shape[1], aad_const, ks.shape[1])
    s_rows = _cached_grouped_digest(gmat_g, data, ct_len, grid_rows,
                                    inv_pos, width, aad_const, packed)
    want = jnp.bitwise_xor(s_rows, jnp.asarray(ek_j0, dtype=jnp.uint8))
    stored = _gather_span(data, mlen, TAG_LEN)
    auth_ok = jnp.all(stored == want, axis=1)
    dec = _xor_cached(data, ks, aad_const, ct_len)
    return dec, mlen, auth_ok


@functools.partial(jax.jit, static_argnames=("aad_const",))
def gcm_protect_fanout(data, length, round_keys, gmat, iv12,
                       aad_const: int = 12):
    """Full-mesh SFU seal: P packets x G receiver legs in one launch.

    data [P, W] uint8 — the SAME decrypted packets go to every leg;
    length [P] int32; round_keys [G, R, 16]; gmat [G, 128, 128] int8
    (one GHASH matrix per LEG, read once per leg via `ghash_grouped`
    instead of once per output row); iv12 [G, P, 12] (leg salt x sender
    ssrc/index).  Returns (out [G, P, W], out_len [P] + 16).
    """
    from libjitsi_tpu.kernels.ghash import ghash_grouped

    data = jnp.asarray(data, dtype=jnp.uint8)
    length = jnp.asarray(length, dtype=jnp.int32)
    g = round_keys.shape[0]
    p, w = data.shape
    rows = g * p
    data_gp = jnp.broadcast_to(data[None], (g, p, w)).reshape(rows, w)
    rk_rows = jnp.repeat(jnp.asarray(round_keys), p, axis=0)
    j0 = _j0(jnp.asarray(iv12).reshape(rows, 12))
    ctr0 = _inc32(j0)
    length_r = jnp.tile(length, g)
    ct_len = length_r - aad_const
    enc = ctr_crypt_uniform(rk_rows, ctr0, data_gp, aad_const, ct_len)
    width = _ghash_width(w)
    with jax.named_scope("tag"):
        gin, nblk = _build_ghash_input_uniform(enc, aad_const, ct_len,
                                               width)
        with jax.named_scope("ghash"):
            s = ghash_grouped(jnp.asarray(gmat), gin.reshape(g, p, width),
                              nblk.reshape(g, p), width // 16)
        ek_j0 = aes_encrypt(rk_rows, j0)
        tag = jnp.bitwise_xor(s.reshape(rows, 16), ek_j0)
    out = _scatter_tag(enc, length_r, tag)
    return out.reshape(g, p, w), length + TAG_LEN


def srtp_gcm_iv(salt12: np.ndarray, ssrc: np.ndarray,
                index: np.ndarray) -> np.ndarray:
    """RFC 7714 §8.1 SRTP IV: (00 00 || SSRC || ROC || SEQ) XOR salt.

    Host-side, broadcast-capable: `salt12` [..., 12] uint8 is copied and
    XORed with `ssrc` (4 bytes at offsets 2..5) and the 48-bit `index`
    (offsets 6..11).  Single IV-construction source for the stream table
    and the SFU translator — nonce layout must never diverge.
    """
    iv = np.array(salt12[..., :12], dtype=np.uint8, copy=True)
    ssrc = np.asarray(ssrc, dtype=np.int64)
    index = np.asarray(index, dtype=np.int64)
    for k in range(4):
        iv[..., 2 + k] ^= ((ssrc >> (8 * (3 - k))) & 0xFF).astype(np.uint8)
    for k in range(6):
        iv[..., 6 + k] ^= ((index >> (8 * (5 - k))) & 0xFF).astype(np.uint8)
    return iv
