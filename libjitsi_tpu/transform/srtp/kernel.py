"""Batched SRTP/SRTCP protect/unprotect device kernels (JAX).

The per-packet crypto of the reference's
`org.jitsi.impl.neomedia.transform.srtp.{SRTPCryptoContext,SRTCPCryptoContext}`
(AES-CM keystream XOR over the payload + HMAC-SHA1 tag over the
authenticated portion || ROC) inverted into one batched device computation:
every argument is a per-row array, per-stream key material arrives as
row-gathered dense tensors, and the whole batch is one fused XLA program.

Host (context.py) is responsible for: index/ROC estimation, replay windows,
IV construction — the sequential, branchy, tiny-state machine.  Device does
all the byte crunching.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from libjitsi_tpu.core.packet import payload_blocks
from libjitsi_tpu.kernels.aes import (ctr_crypt_offset, ctr_crypt_rows,
                                      ctr_crypt_uniform, f8_crypt_offset,
                                      f8_crypt_uniform)
from libjitsi_tpu.kernels.scatter import gather_span as _gather_span
from libjitsi_tpu.kernels.scatter import scatter_bytes
from libjitsi_tpu.kernels.sha1 import hmac_sha1


def gather_keys(rows, *tables):
    """Row-gather per-stream key tensors (`None` passes through), under
    the device scope `key_gather`: in a device trace the gathers of a
    program can be told from its keystream and its HMAC."""
    with jax.named_scope("key_gather"):
        return tuple(None if t is None else t[rows] for t in tables)


def _scatter_word(data, pos, word):
    """Write 4 bytes `word` [B, 4] at per-row byte offset `pos` [B]
    (gather-free — kernels/scatter.py has the perf story)."""
    return scatter_bytes(data, pos, word, 4)


def _scatter_tag(data, pos, tag, tag_len: int):
    """Write tag[:, :tag_len] at per-row byte offset `pos`."""
    with jax.named_scope("scatter_tag"):
        return scatter_bytes(data, pos, tag, tag_len)


def _auth_tags(data, mlen, extra_word, midstates):
    """HMAC-SHA1 over data[:mlen] || extra_word (4 bytes), per row.

    `_pad_and_blockify` masks bytes at/after the length argument, so stale
    bytes past `mlen` in `data` never leak into the MAC.
    """
    with jax.named_scope("auth"):
        buf = _scatter_word(data, mlen, extra_word)
        return hmac_sha1(midstates, buf, mlen + 4)


def _u32_bytes(x):
    """[B] int -> [B, 4] uint8 big-endian."""
    x = x.astype(jnp.uint32)
    shifts = jnp.array([24, 16, 8, 0], dtype=jnp.uint32)
    return ((x[:, None] >> shifts[None, :]) & 0xFF).astype(jnp.uint8)


@functools.partial(
    jax.jit, static_argnames=("tag_len", "encrypt", "payload_off_const"))
def srtp_protect(
    data,
    length,
    payload_off,
    round_keys,
    iv,
    midstates,
    roc,
    tag_len: int,
    encrypt: bool = True,
    payload_off_const=None,
    f8_round_keys=None,
):
    """Batched SRTP protect (reference: SRTPCryptoContext.transformPacket).

    data [B, W] uint8, length/payload_off [B] int32, round_keys [B, R, 16],
    iv [B, 16], midstates [B, 2, 5], roc [B] (guessed ROC v per packet).
    Returns (data', length') with payload encrypted in place and the
    HMAC-SHA1 tag (truncated to tag_len) appended; the MAC covers
    header||ciphertext||ROC per RFC 3711 §4.2.

    `f8_round_keys` [B, R, 16] switches the cipher from AES-CM to AES-f8
    (RFC 3711 §4.1.2, reference SRTPCipherF8): `iv` is then the f8 IV and
    the extra schedule is E(k_e XOR m)'s (None-ness is trace-static).
    """
    data = jnp.asarray(data, dtype=jnp.uint8)
    length = jnp.asarray(length, dtype=jnp.int32)
    payload_off = jnp.asarray(payload_off, dtype=jnp.int32)
    if encrypt:
        if payload_off_const is not None:
            if f8_round_keys is not None:
                data = f8_crypt_uniform(
                    round_keys, f8_round_keys, iv, data, payload_off_const,
                    length - payload_off_const)
            else:
                data = ctr_crypt_uniform(
                    round_keys, iv, data, payload_off_const,
                    length - payload_off_const)
        elif f8_round_keys is not None:
            data = f8_crypt_offset(round_keys, f8_round_keys, iv, data,
                                   payload_off, length - payload_off)
        else:
            data = ctr_crypt_offset(
                round_keys, iv, data, payload_off, length - payload_off
            )
    return _append_tag(data, length, roc, midstates, tag_len)


def _append_tag(data, length, roc, midstates, tag_len: int):
    """HMAC-SHA1 over data[:length] || ROC, truncated to `tag_len` and
    appended (RFC 3711 section 4.2); `tag_len` 0 appends nothing."""
    if tag_len:
        tags = _auth_tags(data, length, _u32_bytes(jnp.asarray(roc)), midstates)
        data = _scatter_tag(data, length, tags, tag_len)
        length = length + tag_len
    return data, length


@functools.partial(jax.jit, static_argnames=("tag_len", "encrypt"))
def srtp_protect_rows(data, length, payload_off, round_keys, iv, midstates,
                      roc, tag_len: int, encrypt: bool = True):
    """`srtp_protect` (AES-CM) whose payload offset is DATA: arguments
    and bytes as `srtp_protect`'s, but the keystream is aligned a row by
    `ctr_crypt_rows`' shift ladder, so there is one program a shape
    whatever header lengths a batch carries, at the static form's cost.
    The SFU fan-out's form (sfu/translator.py, mesh/translator.py).
    Rows are RTP packets with room for their tag: `payload_off` >= 12
    and `length + tag_len` <= W bound the keystream (`payload_blocks`)."""
    data = jnp.asarray(data, dtype=jnp.uint8)
    length = jnp.asarray(length, dtype=jnp.int32)
    payload_off = jnp.asarray(payload_off, dtype=jnp.int32)
    if encrypt:
        data = ctr_crypt_rows(
            round_keys, iv, data, payload_off, length - payload_off,
            nblocks=payload_blocks(data.shape[1], tag_len))
    return _append_tag(data, length, roc, midstates, tag_len)


@functools.partial(
    jax.jit, static_argnames=("tag_len", "encrypt", "payload_off_const"))
def srtp_unprotect(
    data,
    length,
    payload_off,
    round_keys,
    iv,
    midstates,
    roc,
    tag_len: int,
    encrypt: bool = True,
    payload_off_const=None,
    f8_round_keys=None,
):
    """Batched SRTP unprotect (reference: SRTPCryptoContext.reverseTransformPacket).

    Returns (data', length', auth_ok).  Decrypt always runs (rows that fail
    auth are masked by the caller — keeps the program branch-free); auth_ok
    is the constant-pattern tag comparison result per row.
    """
    data = jnp.asarray(data, dtype=jnp.uint8)
    length = jnp.asarray(length, dtype=jnp.int32)
    payload_off = jnp.asarray(payload_off, dtype=jnp.int32)
    mlen = length - tag_len
    if tag_len:
        tags = _auth_tags(data, mlen, _u32_bytes(jnp.asarray(roc)), midstates)
        stored = _gather_span(data, mlen, tag_len)
        auth_ok = jnp.all(stored == tags[:, :tag_len], axis=1)
    else:
        auth_ok = jnp.ones((data.shape[0],), dtype=bool)
    if encrypt:
        if payload_off_const is not None:
            if f8_round_keys is not None:
                out = f8_crypt_uniform(
                    round_keys, f8_round_keys, iv, data, payload_off_const,
                    mlen - payload_off_const)
            else:
                out = ctr_crypt_uniform(
                    round_keys, iv, data, payload_off_const,
                    mlen - payload_off_const)
        elif f8_round_keys is not None:
            out = f8_crypt_offset(round_keys, f8_round_keys, iv, data,
                                  payload_off, mlen - payload_off)
        else:
            out = ctr_crypt_offset(
                round_keys, iv, data, payload_off, mlen - payload_off)
    else:
        out = data
    return out, mlen, auth_ok


@functools.partial(jax.jit, static_argnames=("tag_len", "encrypt"))
def srtcp_protect(
    data, length, round_keys, iv, midstates, index_word, tag_len: int,
    encrypt: bool = True, f8_round_keys=None,
):
    """Batched SRTCP protect (reference: SRTCPCryptoContext.transformPacket).

    Encrypts everything after the 8-byte header (first RTCP header + sender
    SSRC stay clear per RFC 3711 §3.4), appends the E||SRTCP-index word
    (already OR-ed with the E bit by the caller) and the tag.
    """
    data = jnp.asarray(data, dtype=jnp.uint8)
    length = jnp.asarray(length, dtype=jnp.int32)
    if encrypt:
        if f8_round_keys is not None:
            data = f8_crypt_uniform(round_keys, f8_round_keys, iv, data, 8,
                                    length - 8)
        else:
            data = ctr_crypt_uniform(round_keys, iv, data, 8, length - 8)
    word = _u32_bytes(jnp.asarray(index_word))
    tags = _auth_tags(data, length, word, midstates)
    data = _scatter_word(data, length, word)
    length = length + 4
    if tag_len:
        data = _scatter_tag(data, length, tags, tag_len)
        length = length + tag_len
    return data, length


@functools.partial(jax.jit, static_argnames=("tag_len", "encrypt"))
def srtcp_unprotect(
    data, length, round_keys, iv, midstates, tag_len: int,
    encrypt: bool = True, f8_round_keys=None,
):
    """Batched SRTCP unprotect.  Returns (data', length', auth_ok, e_bit, index).

    The caller re-derives the IV from the parsed index; this kernel is called
    twice per batch in principle — in practice the host parses the trailer
    with NumPy first (cheap column reads) and calls this once with the right
    IVs; the index/E returned here are for verification.
    """
    data = jnp.asarray(data, dtype=jnp.uint8)
    length = jnp.asarray(length, dtype=jnp.int32)
    mlen = length - tag_len - 4  # bytes covered by encryption (packet proper)
    word = _gather_span(data, mlen, 4).astype(jnp.uint32)
    index_word = (word[:, 0] << 24) | (word[:, 1] << 16) | (word[:, 2] << 8) | word[:, 3]
    e_bit = index_word >> 31
    index = index_word & 0x7FFFFFFF
    if tag_len:
        with jax.named_scope("auth"):
            # MAC covers packet || index word
            tags = hmac_sha1(midstates, data, mlen + 4)
        stored = _gather_span(data, mlen + 4, tag_len)
        auth_ok = jnp.all(stored == tags[:, :tag_len], axis=1)
    else:
        auth_ok = jnp.ones((data.shape[0],), dtype=bool)
    if encrypt:
        if f8_round_keys is not None:
            out = f8_crypt_uniform(round_keys, f8_round_keys, iv, data, 8,
                                   mlen - 8)
        else:
            out = ctr_crypt_uniform(round_keys, iv, data, 8, mlen - 8)
        # rows with E=0 were sent unencrypted: pass through
        out = jnp.where((e_bit == 1)[:, None], out, data)
    else:
        out = data
    return out, mlen, auth_ok, e_bit, index
