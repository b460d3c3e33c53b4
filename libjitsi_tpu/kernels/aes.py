"""Batched AES-128/256 and AES-CTR as pure-JAX vectorized kernels.

This is the cipher half of the SRTP hot path.  The reference selects among
AES providers at startup (`org.jitsi.impl.neomedia.transform.srtp.crypto.Aes`
benchmarks SunJCE / BouncyCastle / OpenSSL-JNI and picks the fastest) and
runs AES-CM per packet.  Here the per-packet loop inverts into one batched
computation: `[B, 16]` counter blocks -> `[B, 16]` keystream blocks, uint8
vector math + one 256-entry S-box gather per round, with the batch axis
(packets x blocks) supplying the parallelism the MXU/VPU wants.

Design notes
- Key expansion is host-side NumPy (cold path, per-stream, tiny); the device
  consumes a dense `[B, rounds+1, 16]` round-key tensor gathered per packet
  row by stream id — this is how per-stream SRTP session keys batch.
- The round loop is unrolled at trace time (constant 10/14 trip count).
- S-box lookups here are `jnp.take` gathers on a 256-byte constant: the
  CPU's core and the tests' reference.  An accelerator runs the gather-free
  circuit of kernels/aes_bitsliced.py instead (`get_core`).
- State layout is the FIPS-197 flat byte order (index = row + 4*col), so
  blocks go in/out with no repacking.
- The S-box and round constants are *generated* from GF(2^8) arithmetic at
  import, not transcribed, eliminating table-typo risk.

KATs: FIPS-197 App. C, NIST SP 800-38A F.5 (CTR), plus differential tests
against the OpenSSL-backed `cryptography` package (tests/test_aes.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from libjitsi_tpu.kernels.scatter import shift_right


# ---------------------------------------------------------------------------
# GF(2^8) tables (host, generated once)
# ---------------------------------------------------------------------------

def _make_sbox() -> np.ndarray:
    # log/antilog over GF(2^8) with generator 0x03
    exp = np.zeros(256, dtype=np.int32)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        # x *= 3  (== xtime(x) ^ x)
        x = (((x << 1) ^ (0x1B if x & 0x80 else 0)) & 0xFF) ^ x
    sbox = np.zeros(256, dtype=np.uint8)
    for a in range(256):
        inv = 0 if a == 0 else exp[(255 - log[a]) % 255]
        s = inv
        for _ in range(4):
            inv = ((inv << 1) | (inv >> 7)) & 0xFF
            s ^= inv
        sbox[a] = s ^ 0x63
    return sbox


_SBOX = _make_sbox()

# ShiftRows as a static permutation of the flat (row + 4*col) state:
# out[r + 4c] = in[r + 4*((c + r) % 4)]
_SHIFT_IDX = np.array(
    [r + 4 * ((c + r) % 4) for c in range(4) for r in range(4)], dtype=np.int32
)


# ---------------------------------------------------------------------------
# Key expansion (host)
# ---------------------------------------------------------------------------

def expand_key(key) -> np.ndarray:
    """FIPS-197 key schedule.  key: 16 or 32 bytes -> [rounds+1, 16] uint8.

    Host-side, per stream (cold path).  Reference analog: the cipher init in
    SRTPCipherCTR / the JCE key schedule.
    """
    key = np.frombuffer(bytes(key), dtype=np.uint8) if isinstance(key, (bytes, bytearray)) else np.asarray(key, dtype=np.uint8)
    if len(key) not in (16, 32):
        raise ValueError("AES key must be 16 or 32 bytes")
    nk = len(key) // 4
    nr = nk + 6
    w = np.zeros((4 * (nr + 1), 4), dtype=np.uint8)
    w[:nk] = key.reshape(nk, 4)
    rcon = np.uint8(1)
    for i in range(nk, 4 * (nr + 1)):
        t = w[i - 1].copy()
        if i % nk == 0:
            t = np.roll(t, -1)
            t = _SBOX[t]
            t[0] ^= rcon
            rcon = np.uint8(((int(rcon) << 1) ^ (0x11B if rcon & 0x80 else 0)) & 0xFF)
        elif nk == 8 and i % nk == 4:
            t = _SBOX[t]
        w[i] = w[i - nk] ^ t
    # word c of round r -> flat bytes [4c .. 4c+3] == (row + 4*col) layout
    return w.reshape(nr + 1, 16)


def expand_keys_batch(keys: np.ndarray) -> np.ndarray:
    """[S, 16|32] uint8 -> [S, rounds+1, 16] uint8 round-key tensor.

    Vectorized across streams: the FIPS-197 schedule is sequential in the
    word index (44/60 steps) but embarrassingly parallel across keys, so
    each step is one [S, 4] vector op.  10k-stream installs take
    milliseconds instead of the per-key loop's seconds.
    """
    keys = np.atleast_2d(np.asarray(keys, dtype=np.uint8))
    s, kl = keys.shape
    if kl not in (16, 32):
        raise ValueError("AES keys must be 16 or 32 bytes")
    nk = kl // 4
    nr = nk + 6
    w = np.zeros((s, 4 * (nr + 1), 4), dtype=np.uint8)
    w[:, :nk] = keys.reshape(s, nk, 4)
    rcon = 1
    for i in range(nk, 4 * (nr + 1)):
        t = w[:, i - 1].copy()
        if i % nk == 0:
            t = np.roll(t, -1, axis=1)
            t = _SBOX[t]
            t[:, 0] ^= np.uint8(rcon)
            rcon = ((rcon << 1) ^ (0x11B if rcon & 0x80 else 0)) & 0xFF
        elif nk == 8 and i % nk == 4:
            t = _SBOX[t]
        w[:, i] = w[:, i - nk] ^ t
    return w.reshape(s, nr + 1, 16)


# ---------------------------------------------------------------------------
# Host cipher (NumPy mirror of the device core — cold paths only)
# ---------------------------------------------------------------------------

def _xtime_np(x):
    return ((x << 1) ^ (np.uint8(0x1B) * (x >> 7))).astype(np.uint8)


def aes_encrypt_np(round_keys: np.ndarray, blocks: np.ndarray) -> np.ndarray:
    """Host-side batched AES block encrypt (NumPy; mirrors `aes_encrypt`).

    Used by the cold paths that must not touch the device: RFC 3711 key
    derivation at stream setup, KATs, and the CPU fallback backend (the
    reference keeps a pure-Java AES fallback beside the OpenSSL JNI path in
    `.srtp.crypto.Aes`).  round_keys: [R, 16] or [B, R, 16]; blocks: [B, 16].
    """
    rk = np.asarray(round_keys, dtype=np.uint8)
    if rk.ndim == 2:
        rk = np.broadcast_to(rk, (blocks.shape[0],) + rk.shape)
    st = np.asarray(blocks, dtype=np.uint8) ^ rk[:, 0, :]
    nr = rk.shape[1] - 1
    for r in range(1, nr):
        st = _SBOX[st][:, _SHIFT_IDX]
        s = st.reshape(-1, 4, 4)
        x = _xtime_np(s)
        r0 = x[..., 0] ^ x[..., 1] ^ s[..., 1] ^ s[..., 2] ^ s[..., 3]
        r1 = s[..., 0] ^ x[..., 1] ^ x[..., 2] ^ s[..., 2] ^ s[..., 3]
        r2 = s[..., 0] ^ s[..., 1] ^ x[..., 2] ^ x[..., 3] ^ s[..., 3]
        r3 = x[..., 0] ^ s[..., 0] ^ s[..., 1] ^ s[..., 2] ^ x[..., 3]
        st = np.stack([r0, r1, r2, r3], axis=-1).reshape(st.shape) ^ rk[:, r, :]
    return (_SBOX[st][:, _SHIFT_IDX] ^ rk[:, nr, :]).astype(np.uint8)


def ctr_keystream_np(round_keys: np.ndarray, iv16: np.ndarray, nbytes: int) -> np.ndarray:
    """Host AES-CTR keystream from one IV block: [R,16] keys, [16] iv -> [nbytes]."""
    nblocks = (nbytes + 15) // 16
    iv = np.asarray(iv16, dtype=np.uint8)
    ctrs = np.zeros((nblocks, 16), dtype=np.uint8)
    val = int.from_bytes(bytes(iv), "big")
    for j in range(nblocks):
        ctrs[j] = np.frombuffer(
            ((val + j) % (1 << 128)).to_bytes(16, "big"), dtype=np.uint8
        )
    return aes_encrypt_np(np.asarray(round_keys), ctrs).reshape(-1)[:nbytes]


# ---------------------------------------------------------------------------
# Device cipher core
# ---------------------------------------------------------------------------

def _sub_bytes(st):
    return jnp.take(jnp.asarray(_SBOX), st, axis=0)


def _shift_rows(st):
    return st[..., jnp.asarray(_SHIFT_IDX)]


def _xtime(x):
    # uint8 lanes: (x<<1) wraps mod 256; conditional 0x1B reduction
    return (x << 1) ^ (jnp.uint8(0x1B) * (x >> 7))


def _mix_columns(st):
    # st: [..., 16] flat (row + 4*col) -> view as [..., 4 cols, 4 rows]
    s = st.reshape(st.shape[:-1] + (4, 4))
    s0, s1, s2, s3 = s[..., 0], s[..., 1], s[..., 2], s[..., 3]
    x0, x1, x2, x3 = _xtime(s0), _xtime(s1), _xtime(s2), _xtime(s3)
    r0 = x0 ^ (x1 ^ s1) ^ s2 ^ s3
    r1 = s0 ^ x1 ^ (x2 ^ s2) ^ s3
    r2 = s0 ^ s1 ^ x2 ^ (x3 ^ s3)
    r3 = (x0 ^ s0) ^ s1 ^ s2 ^ x3
    return jnp.stack([r0, r1, r2, r3], axis=-1).reshape(st.shape)


def aes_encrypt_table(round_keys, blocks):
    """Batched AES block encrypt (table/S-box-gather core).

    round_keys: [..., R, 16] uint8 (R = 11 for AES-128, 15 for AES-256);
    blocks: [..., 16] uint8.  -> [..., 16] uint8.  Round count is taken
    from the static shape, so this traces once per key size.
    """
    rk = jnp.asarray(round_keys, dtype=jnp.uint8)
    st = jnp.asarray(blocks, dtype=jnp.uint8) ^ rk[..., 0, :]
    nr = rk.shape[-2] - 1
    for r in range(1, nr):
        st = _mix_columns(_shift_rows(_sub_bytes(st))) ^ rk[..., r, :]
    return _shift_rows(_sub_bytes(st)) ^ rk[..., nr, :]


# The encrypt core is picked by the platform, at TRACE time: "table"
# (S-box gather; XLA:CPU's gather is cheap) on the CPU, "bitsliced_tower"
# (gather-free composite-field circuit, kernels/aes_bitsliced.py) on an
# accelerator, where a gather per byte is the vector unit's worst case.
# `set_core` is the tests' seam (it lowers the chip's core on a CPU);
# "bitsliced32" is reachable through it alone (kept for a `perf_opt`
# to try on the chip: see its banner in aes_bitsliced.py).
_CORES = ("table", "bitsliced_tower", "bitsliced32")
_CORE_NAME = None           # set only by set_core


def set_core(name: str) -> None:
    global _CORE_NAME
    if name not in _CORES:
        raise ValueError(f"aes core must be one of {_CORES}")
    if name != _CORE_NAME:
        _CORE_NAME = name
        jax.clear_caches()


def get_core() -> str:
    if _CORE_NAME is not None:
        return _CORE_NAME
    return "table" if jax.default_backend() == "cpu" else "bitsliced_tower"


def aes_encrypt(round_keys, blocks):
    """Batched AES block encrypt via the platform's core ([..., R, 16]
    keys, [..., 16] blocks; see `get_core`)."""
    core = get_core()
    if core == "bitsliced_tower":
        from libjitsi_tpu.kernels.aes_bitsliced import \
            aes_encrypt_bitsliced_tower_nd as fn
    elif core == "bitsliced32":
        from libjitsi_tpu.kernels.aes_bitsliced import \
            aes_encrypt_bitsliced32_nd as fn
    else:
        fn = aes_encrypt_table
    with jax.named_scope("aes_rounds"):
        return fn(round_keys, blocks)


def _iv_to_limbs(iv):
    """[B, 16] uint8 -> [B, 4] uint32 big-endian limbs."""
    w = iv.astype(jnp.uint32).reshape(iv.shape[0], 4, 4)
    return (w[..., 0] << 24) | (w[..., 1] << 16) | (w[..., 2] << 8) | w[..., 3]


def _limbs_to_bytes(limbs):
    """[..., 4] uint32 -> [..., 16] uint8 big-endian."""
    shifts = jnp.array([24, 16, 8, 0], dtype=jnp.uint32)
    b = (limbs[..., :, None] >> shifts) & jnp.uint32(0xFF)
    return b.astype(jnp.uint8).reshape(limbs.shape[:-1] + (16,))


def _counter_blocks(iv, nblocks):
    """[B, 16] iv -> [B, nblocks, 16] counter blocks (128-bit BE increment)."""
    limbs = _iv_to_limbs(iv)  # [B, 4]
    j = jnp.arange(nblocks, dtype=jnp.uint32)  # [n]
    l3 = limbs[:, None, 3] + j[None, :]
    carry = (l3 < j[None, :]).astype(jnp.uint32)
    l2 = limbs[:, None, 2] + carry
    carry = (l2 < carry).astype(jnp.uint32)
    l1 = limbs[:, None, 1] + carry
    carry = (l1 < carry).astype(jnp.uint32)
    l0 = limbs[:, None, 0] + carry
    return _limbs_to_bytes(jnp.stack([l0, l1, l2, l3], axis=-1))


@functools.partial(jax.jit, static_argnames=("nblocks",))
def ctr_keystream(round_keys, iv, nblocks: int):
    """AES-CTR keystream:  [B, R, 16] keys + [B, 16] iv -> [B, nblocks*16] uint8.

    The counter is the full 128-bit big-endian block (NIST SP 800-38A
    increment); SRTP's 16-bit block counter (RFC 3711 §4.1.1) is the special
    case where the IV's low 16 bits start at zero.
    """
    bsz = iv.shape[0]
    ctr = _counter_blocks(jnp.asarray(iv, dtype=jnp.uint8), nblocks)  # [B, n, 16]
    rk = jnp.asarray(round_keys, dtype=jnp.uint8)[:, None, :, :]  # [B, 1, R, 16]
    ks = aes_encrypt(jnp.broadcast_to(rk, (bsz, nblocks) + rk.shape[2:]), ctr)
    return ks.reshape(bsz, nblocks * 16)


@functools.partial(jax.jit, static_argnames=("nblocks",))
def f8_keystream(round_keys, f8_round_keys, iv, nblocks: int):
    """AES-F8 keystream (RFC 3711 §4.1.2): the reference's SRTPCipherF8.

    IV' = E(k_e XOR m, IV) is one batched block encrypt; the keystream
    S(j) = E(k_e, IV' XOR j XOR S(j-1)) has a sequential dependence over
    a packet's blocks (unlike CTR), so blocks run under `lax.scan` while
    the batch axis stays fully parallel — ≤ ~12 scan steps for audio
    MTUs.  `j` is the block counter as a 128-bit big-endian integer.

    round_keys/f8_round_keys: [B, R, 16] (schedules of k_e and k_e XOR m);
    iv: [B, 16].  -> [B, nblocks*16] uint8.
    """
    rk = jnp.asarray(round_keys, dtype=jnp.uint8)
    ivp = aes_encrypt(jnp.asarray(f8_round_keys, dtype=jnp.uint8),
                      jnp.asarray(iv, dtype=jnp.uint8))  # IV'

    def body(s_prev, j):
        blk = ivp ^ s_prev
        # XOR the 128-bit BE block counter.  j is uint32, so only the low
        # 4 counter bytes (12..15) can be nonzero — shifting uint32 by
        # >=32 would be undefined, so touch only those bytes.
        jb = (j >> (jnp.arange(4, dtype=jnp.uint32)[::-1] * 8)).astype(
            jnp.uint8)
        blk = blk.at[:, 12:].set(blk[:, 12:] ^ jb[None, :])
        s = aes_encrypt(rk, blk)
        return s, s

    _, ks = jax.lax.scan(body, jnp.zeros_like(ivp),
                         jnp.arange(nblocks, dtype=jnp.uint32))
    # ks: [nblocks, B, 16] -> [B, nblocks*16]
    return ks.transpose(1, 0, 2).reshape(ivp.shape[0], nblocks * 16)


def f8_m(session_key: bytes, session_salt: bytes) -> bytes:
    """RFC 3711 §4.1.2.2: m = k_s || 0x55.. padded to the key length."""
    return session_salt + b"\x55" * (len(session_key) - len(session_salt))


def f8_keystream_np(session_key: bytes, session_salt: bytes, iv16: bytes,
                    nbytes: int) -> bytes:
    """Independent scalar F8 oracle (OpenSSL AES via `cryptography`).

    Deliberately shares no code with the batched path — the differential
    test compares two implementations written from the RFC separately.
    """
    from cryptography.hazmat.primitives.ciphers import (
        Cipher as _C, algorithms as _a, modes as _m)

    def ecb(key: bytes, block: bytes) -> bytes:
        enc = _C(_a.AES(key), _m.ECB()).encryptor()
        return enc.update(block) + enc.finalize()

    m = f8_m(session_key, session_salt)
    kxm = bytes(a ^ b for a, b in zip(session_key, m))
    ivp = ecb(kxm, bytes(iv16))
    out = b""
    s = b"\x00" * 16
    j = 0
    while len(out) < nbytes:
        blk = bytes(a ^ b for a, b in zip(ivp, s))
        blk = bytes(a ^ b for a, b in zip(blk, j.to_bytes(16, "big")))
        s = ecb(session_key, blk)
        out += s
        j += 1
    return out[:nbytes]


def _xor_window_uniform(data, ks, offset: int, length):
    """XOR keystream `ks` into each row's [offset, offset+length) span
    with a static pad-shift (no per-row gather)."""
    width = data.shape[1]
    ks_aligned = jnp.pad(ks, ((0, 0), (offset, 0)))[:, :width]
    col = jnp.arange(width, dtype=jnp.int32)[None, :]
    ln = jnp.asarray(length, dtype=jnp.int32)[:, None]
    inside = (col >= offset) & (col < offset + ln)
    return jnp.where(inside, data ^ ks_aligned, data)


@functools.partial(jax.jit, static_argnames=("offset",))
def f8_crypt_uniform(round_keys, f8_round_keys, iv, data, offset: int,
                     length):
    """F8-encrypt/decrypt each row's payload window (uniform offset)."""
    data = jnp.asarray(data, dtype=jnp.uint8)
    width = data.shape[1]
    nblocks = max(0, (width - offset + 15) // 16)
    if nblocks == 0:
        return data
    with jax.named_scope("keystream"):
        ks = f8_keystream(round_keys, f8_round_keys, iv, nblocks)
    with jax.named_scope("xor_payload"):
        return _xor_window_uniform(data, ks, offset, length)


@functools.partial(jax.jit, static_argnames=("offset",))
def ctr_crypt_uniform(round_keys, iv, data, offset: int, length):
    """Uniform-offset fast path of `ctr_crypt_offset`.

    When every row's payload begins at the same byte offset (the common
    case: fixed 12-byte RTP headers, or SRTCP's constant 8), the keystream
    alignment is a static left-pad — the per-row `take_along_axis` gather
    in the general path is by far its dominant cost on TPU (measured ~5x
    the AES itself), so the host picks this variant whenever the batch is
    offset-uniform.  Encrypt == decrypt (CTR).  -> [B, W] uint8.
    """
    data = jnp.asarray(data, dtype=jnp.uint8)
    bsz, width = data.shape
    nblocks = max(0, (width - offset + 15) // 16)
    if nblocks == 0:            # offset beyond the buffer: nothing to crypt
        return data
    with jax.named_scope("keystream"):
        ks = ctr_keystream(round_keys, iv, nblocks)  # [B, nblocks*16]
    with jax.named_scope("xor_payload"):
        return _xor_window_uniform(data, ks, offset, length)


@jax.jit
def ctr_crypt_offset(round_keys, iv, data, offset, length):
    """XOR an AES-CTR keystream into each row's [offset, offset+length) span.

    data: [B, W] uint8; offset/length: [B] int32 — per-row payload windows
    (RTP payload begins at a per-packet header length).  Keystream byte k of
    the stream is applied at column offset+k, i.e. column j uses keystream
    byte (j - offset); bytes outside the window pass through unchanged.
    Encrypt == decrypt (CTR).  -> [B, W] uint8.
    """
    data = jnp.asarray(data, dtype=jnp.uint8)
    bsz, width = data.shape
    nblocks = (width + 15) // 16
    with jax.named_scope("keystream"):
        ks = ctr_keystream(round_keys, iv, nblocks)  # [B, nblocks*16]
    with jax.named_scope("xor_payload"):
        return _xor_window_offset(data, ks, offset, length)


def _xor_window_offset(data, ks, offset, length):
    """XOR keystream into per-row windows (per-row gather alignment)."""
    width = data.shape[1]
    col = jnp.arange(width, dtype=jnp.int32)[None, :]
    off = jnp.asarray(offset, dtype=jnp.int32)[:, None]
    ln = jnp.asarray(length, dtype=jnp.int32)[:, None]
    rel = jnp.clip(col - off, 0, ks.shape[1] - 1)
    ks_aligned = jnp.take_along_axis(ks, rel, axis=1)
    inside = (col >= off) & (col < off + ln)
    return jnp.where(inside, data ^ ks_aligned, data)


@functools.partial(jax.jit, static_argnames=("nblocks",))
def ctr_crypt_rows(round_keys, iv, data, offset, length, nblocks=None):
    """`ctr_crypt_offset` at `ctr_crypt_uniform`'s cost: the keystream
    is aligned to each row's own offset by a ladder of static shifts
    (`scatter.shift_right`), not by a [B, W] gather, so ONE program
    serves every header length, and a batch that mixes them, where the
    uniform form is a program an offset.  Same contract and bytes as
    `ctr_crypt_offset` for 0 <= offset (an offset at or past the width
    leaves the row as it is).

    `nblocks` (static): the keystream blocks computed, for a caller
    that knows no row's window is longer than 16 x `nblocks` bytes (the
    uniform form computes what lies behind its offset, and nothing for
    columns no payload can reach); default the whole width."""
    data = jnp.asarray(data, dtype=jnp.uint8)
    if nblocks is None:
        nblocks = (data.shape[1] + 15) // 16
    with jax.named_scope("keystream"):
        ks = ctr_keystream(round_keys, iv, nblocks)
    with jax.named_scope("xor_payload"):
        return _xor_window_rows(data, ks, offset, length)


def _xor_window_rows(data, ks, offset, length):
    """XOR keystream into per-row windows (shift-ladder alignment)."""
    width = data.shape[1]
    col = jnp.arange(width, dtype=jnp.int32)[None, :]
    off = jnp.asarray(offset, dtype=jnp.int32)
    ln = jnp.asarray(length, dtype=jnp.int32)[:, None]
    ks = jnp.pad(ks, ((0, 0), (0, max(0, width - ks.shape[1]))))
    ks_aligned = shift_right(ks[:, :width], off, width)
    off = off[:, None]
    inside = (col >= off) & (col < off + ln)
    return jnp.where(inside, data ^ ks_aligned, data)


@jax.jit
def f8_crypt_offset(round_keys, f8_round_keys, iv, data, offset, length):
    """F8-encrypt/decrypt per-row payload windows (general offsets)."""
    data = jnp.asarray(data, dtype=jnp.uint8)
    nblocks = (data.shape[1] + 15) // 16
    with jax.named_scope("keystream"):
        ks = f8_keystream(round_keys, f8_round_keys, iv, nblocks)
    with jax.named_scope("xor_payload"):
        return _xor_window_offset(data, ks, offset, length)
