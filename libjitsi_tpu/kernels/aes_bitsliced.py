"""Bitsliced, gather-free AES: the core an accelerator runs.

The CPU's core (`kernels.aes.aes_encrypt_table`) takes a 256-entry S-box
`jnp.take`, a gather per byte: the vector unit's worst case.  This
module builds AES-128/256 encryption as a pure Boolean circuit —
XOR/AND/slice/concat only, no gathers — and `kernels.aes.get_core`
picks its composite-field form wherever the platform is not the CPU.

Circuit construction is derived, not transcribed: the S-box is computed
as ``affine(x^254)`` over GF(2^8), with the squaring/power linear maps
and the polynomial-reduction matrix generated from field arithmetic at
import time and the complete 256-entry truth table asserted against an
independently generated S-box.  Inversion uses the addition chain
x -> x^2 -> x^3 -> x^12 -> x^15 -> x^240 -> x^252 -> x^254
(4 variable GF multiplications; squarings are linear), or the tower
GF((2^4)^2) below.

State layout: 8 bit-planes, each ``[B, 4, 4]`` (byte i = row + 4*col),
LSB-first bit order.  ShiftRows is slice+concat per row; MixColumns is
xtime/XOR over row variables — nothing here indexes by data.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# ------------------------------------------------------------ host derivation

_POLY = 0x11B


def _gf_mul(a: int, b: int) -> int:
    r = 0
    while b:
        if b & 1:
            r ^= a
        a <<= 1
        if a & 0x100:
            a ^= _POLY
        b >>= 1
    return r


def _gf_pow(a: int, n: int) -> int:
    r = 1
    while n:
        if n & 1:
            r = _gf_mul(r, a)
        a = _gf_mul(a, a)
        n >>= 1
    return r


def _linear_matrix(fn) -> np.ndarray:
    """8x8 GF(2) matrix of a linear byte map, via basis probing
    (bit i = (byte >> i) & 1)."""
    m = np.zeros((8, 8), dtype=np.uint8)
    for j in range(8):
        y = fn(1 << j)
        for i in range(8):
            m[i, j] = (y >> i) & 1
    return m


_M_SQ = _linear_matrix(lambda x: _gf_pow(x, 2))
_M_P4 = _linear_matrix(lambda x: _gf_pow(x, 4))
_M_P16 = _linear_matrix(lambda x: _gf_pow(x, 16))
# AES S-box affine layer: s = A*x + 0x63 (applied AFTER inversion)
_M_AFF = _linear_matrix(
    lambda x: (x ^ ((x << 1) | (x >> 7)) ^ ((x << 2) | (x >> 6))
               ^ ((x << 3) | (x >> 5)) ^ ((x << 4) | (x >> 4))) & 0xFF)
_AFF_C = 0x63
# x^k mod poly for the 15 product coefficients of an 8x8-bit multiply
_REDC = [_gf_pow(2, k) for k in range(15)]


# ----------------------------------------------------------- circuit builders

def _linear(bits, mat: np.ndarray, const: int = 0, ones=1):
    """`ones` is the all-true word for the plane element type: 1 for
    one-bit-per-uint8 planes, 0xFFFFFFFF for the packed-word provider
    (every bit of an int32 element is a different block)."""
    out = []
    for i in range(8):
        acc = None
        for j in range(8):
            if mat[i, j]:
                acc = bits[j] if acc is None else acc ^ bits[j]
        if acc is None:
            acc = bits[0] ^ bits[0]
        if (const >> i) & 1:
            acc = acc ^ ones
        out.append(acc)
    return out


def _gf_mult_bits(a, b):
    """Bitsliced GF(2^8) multiply of two byte variables."""
    c = []
    for k in range(15):
        acc = None
        for i in range(max(0, k - 7), min(8, k + 1)):
            t = a[i] & b[k - i]
            acc = t if acc is None else acc ^ t
        c.append(acc)
    out = []
    for i in range(8):
        acc = None
        for k in range(15):
            if (_REDC[k] >> i) & 1:
                acc = c[k] if acc is None else acc ^ c[k]
        out.append(acc)
    return out


def _sbox_bits(x, ones=1):
    """S(x) = affine(x^254): 4 GF multiplies + linear maps, no tables."""
    a2 = _linear(x, _M_SQ)
    a3 = _gf_mult_bits(a2, x)
    a12 = _linear(a3, _M_P4)
    a15 = _gf_mult_bits(a12, a3)
    a240 = _linear(a15, _M_P16)
    a252 = _gf_mult_bits(a240, a12)
    a254 = _gf_mult_bits(a252, a2)
    return _linear(a254, _M_AFF, _AFF_C, ones)


# ------------------------------------------- tower-field S-box circuit
#
# The addition-chain inversion above costs 4 GF(2^8)
# bitsliced multiplies (~860 gate-ops per byte).  The classic
# composite-field decomposition GF(2^8) ~ GF((2^4)^2) does the same
# inversion with 5 GF(2^4) multiplies (~250 gate-ops): map through a
# basis change, invert (a y + b) as (a D^-1) y + ((a+b) D^-1) with
# D = lambda a^2 + ab + b^2, and map back into the affine.  The tower
# parameters and both basis-change matrices are DERIVED at import (a
# search for an irreducible y^2+y+lambda and a tower root of the AES
# polynomial), and the whole circuit is asserted against the 256-entry
# S-box table below — same no-transcription doctrine as the rest of
# this module.

def _derive_tower():
    g4mul = [[_gf_mul_16(a, b) for b in range(16)] for a in range(16)]

    def t_mul(u, v, lam):
        a, b = u
        c, d = v
        ac = g4mul[a][c]
        return (g4mul[a][d] ^ g4mul[b][c] ^ ac,
                g4mul[b][d] ^ g4mul[ac][lam])

    def t_pow(u, n, lam):
        r = (0, 1)
        for _ in range(n):
            r = t_mul(r, u, lam)
        return r

    def is_root(g, lam):
        acc = t_pow(g, 8, lam)
        for n in (4, 3, 1):
            p = t_pow(g, n, lam)
            acc = (acc[0] ^ p[0], acc[1] ^ p[1])
        return (acc[0], acc[1] ^ 1) == (0, 0)

    for lam in range(1, 16):
        if any(g4mul[t][t] ^ t ^ lam == 0 for t in range(16)):
            continue           # y^2+y+lam reducible over GF(16)
        for hi in range(16):
            for lo in range(16):
                if (hi, lo) != (0, 0) and is_root((hi, lo), lam):
                    gamma = (hi, lo)
                    m = np.zeros((8, 8), dtype=np.uint8)
                    for i in range(8):
                        a, b = t_pow(gamma, i, lam)
                        c = (a << 4) | b
                        for j in range(8):
                            m[j, i] = (c >> j) & 1
                    return lam, m, _gf2_inv_mat(m)
    raise AssertionError("no tower isomorphism found")


def _gf_mul_16(a: int, b: int) -> int:
    r = 0
    while b:
        if b & 1:
            r ^= a
        a <<= 1
        if a & 0x10:
            a ^= 0b10011        # GF(2^4) poly x^4 + x + 1
        b >>= 1
    return r


def _gf2_inv_mat(mx: np.ndarray) -> np.ndarray:
    n = mx.shape[0]
    a = np.concatenate([mx.copy(), np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        piv = next(r for r in range(col, n) if a[r, col])
        a[[col, piv]] = a[[piv, col]]
        for r in range(n):
            if r != col and a[r, col]:
                a[r] ^= a[col]
    return a[:, n:]


_TOWER_LAM, _M_TOWER, _M_TOWER_INV = _derive_tower()


def _mul4_bits(a, b):
    """Bitsliced GF(2^4) multiply (poly x^4+x+1): 16 ANDs + XOR tree."""
    c = []
    for k in range(7):
        acc = None
        for i in range(max(0, k - 3), min(4, k + 1)):
            t = a[i] & b[k - i]
            acc = t if acc is None else acc ^ t
        c.append(acc)
    return [c[0] ^ c[4], c[1] ^ c[4] ^ c[5], c[2] ^ c[5] ^ c[6],
            c[3] ^ c[6]]


def _sq4_bits(a):
    """x^2 over GF(2^4) (linear)."""
    return [a[0] ^ a[2], a[2], a[1] ^ a[3], a[3]]


def _mul_lam_bits(a):
    """Multiply by lambda over GF(2^4) (linear; derived per _TOWER_LAM
    at import via the generic matrix probe)."""
    return _linear4(a, _M_LAM)


def _linear4(bits, mat):
    out = []
    for i in range(4):
        acc = None
        for j in range(4):
            if mat[i, j]:
                acc = bits[j] if acc is None else acc ^ bits[j]
        out.append(acc if acc is not None else bits[0] ^ bits[0])
    return out


def _lam_matrix() -> np.ndarray:
    m = np.zeros((4, 4), dtype=np.uint8)
    for j in range(4):
        v = _gf_mul_16(1 << j, _TOWER_LAM)
        for i in range(4):
            m[i, j] = (v >> i) & 1
    return m


_M_LAM = _lam_matrix()


def _inv4_bits(a):
    """GF(2^4) inverse = x^14 = x^8 * x^4 * x^2 (0 -> 0)."""
    t2 = _sq4_bits(a)
    t4 = _sq4_bits(t2)
    t8 = _sq4_bits(t4)
    return _mul4_bits(_mul4_bits(t8, t4), t2)


def _sbox_bits_tower(x, ones=1):
    """S(x) = affine(x^-1) with the inversion in GF((2^4)^2)."""
    x4 = lambda u, v: [p ^ q for p, q in zip(u, v)]  # noqa: E731
    t = _linear(x, _M_TOWER)
    b, a = t[:4], t[4:]                     # byte = (a << 4) | b
    delta = x4(x4(_mul_lam_bits(_sq4_bits(a)), _mul4_bits(a, b)),
               _sq4_bits(b))
    di = _inv4_bits(delta)
    hi = _mul4_bits(a, di)
    lo = _mul4_bits(x4(a, b), di)
    inv = _linear(lo + hi, _M_TOWER_INV)
    return _linear(inv, _M_AFF, _AFF_C, ones)


def _self_check() -> None:
    """Assert the derived circuits reproduce the full S-box table."""
    from libjitsi_tpu.kernels.aes import _SBOX

    xs = np.arange(256, dtype=np.uint8)
    bits = [((xs >> p) & 1).astype(np.uint8) for p in range(8)]
    for impl in (_sbox_bits, _sbox_bits_tower):
        out = impl(bits)
        got = np.zeros(256, dtype=np.uint16)
        for p in range(8):
            got |= out[p].astype(np.uint16) << p
        if not np.array_equal(got.astype(np.uint8), _SBOX):
            raise AssertionError(
                f"bitsliced S-box circuit {impl.__name__} != table")


_self_check()


def _vxor(a, b):
    return [x ^ y for x, y in zip(a, b)]


def _xtime_bits(v):
    """GF doubling: out = v << 1 reduced by 0x11B (LSB-first planes)."""
    return [v[7], v[0] ^ v[7], v[1], v[2] ^ v[7], v[3] ^ v[7],
            v[4], v[5], v[6]]


def _shift_rows_bits(bits):
    out = []
    for p in bits:
        rows = []
        for r in range(4):
            row = p[:, r:r + 1, :]
            rows.append(jnp.concatenate([row[..., r:], row[..., :r]], -1)
                        if r else row)
        out.append(jnp.concatenate(rows, 1))
    return out


def _mix_columns_bits(bits):
    rows = [[p[:, r, :] for p in bits] for r in range(4)]
    new_rows = []
    for r in range(4):
        a, b = rows[r], rows[(r + 1) % 4]
        c, d = rows[(r + 2) % 4], rows[(r + 3) % 4]
        new_rows.append(_vxor(_vxor(_xtime_bits(a), _vxor(_xtime_bits(b),
                                                          b)),
                              _vxor(c, d)))
    return [jnp.stack([new_rows[r][p] for r in range(4)], 1)
            for p in range(8)]


def _rounds(bits, rk_bits, nr: int, sbox, ones=1):
    """The round schedule over bit-plane state (`sbox` is the inversion
    circuit: addition-chain `_sbox_bits` or the composite-field
    `_sbox_bits_tower`; `ones` as in `_linear`)."""
    bits = _vxor(bits, rk_bits[0])
    for r in range(1, nr):
        bits = sbox(bits, ones)
        bits = _shift_rows_bits(bits)
        bits = _mix_columns_bits(bits)
        bits = _vxor(bits, rk_bits[r])
    bits = sbox(bits, ones)
    bits = _shift_rows_bits(bits)
    return _vxor(bits, rk_bits[nr])


# --------------------------------------------------------------- XLA provider

def _to_planes(blocks):
    """[B, 16] uint8 -> 8 planes [B, 4, 4] (byte i = row + 4*col)."""
    x = blocks.reshape(-1, 4, 4).transpose(0, 2, 1)   # [B, r, c]
    return [((x >> p) & 1).astype(jnp.uint8) for p in range(8)]


def _from_planes(bits):
    acc = bits[0]
    for p in range(1, 8):
        acc = acc | (bits[p] << p)
    return acc.transpose(0, 2, 1).reshape(-1, 16).astype(jnp.uint8)


# Drop-in twin of `kernels.aes.aes_encrypt_table`, gather-free:
# round_keys [B, R, 16] uint8; blocks [B, 16] uint8 -> [B, 16].  The
# jitted function's own name, `flat`, is in the lowered text and in the
# profiler's op paths of every program that holds it: leave it.
@jax.jit
def flat(round_keys, blocks):
    rk = jnp.asarray(round_keys, dtype=jnp.uint8)
    nr = rk.shape[-2] - 1
    bits = _to_planes(jnp.asarray(blocks, dtype=jnp.uint8))
    rk_bits = [_to_planes(rk[:, r, :]) for r in range(nr + 1)]
    return _from_planes(_rounds(bits, rk_bits, nr, _sbox_bits_tower))


aes_encrypt_bitsliced_tower = flat


def aes_encrypt_bitsliced_tower_nd(round_keys, blocks):
    """Leading-dim-agnostic form: [..., R, 16] broadcast keys, as the
    CTR/GCM call sites pass them."""
    rk = jnp.asarray(round_keys, dtype=jnp.uint8)
    blk = jnp.asarray(blocks, dtype=jnp.uint8)
    lead = blk.shape[:-1]
    out = flat(rk.reshape((-1,) + rk.shape[-2:]), blk.reshape(-1, 16))
    return out.reshape(lead + (16,))


# ----------------------------------------------- packed-word XLA provider
#
# The provider above stores ONE bit per uint8 element; this one packs
# 32 BLOCKS per uint32 word (plane p, word (g, byte): bit k = bit p of
# byte of block 32g + k), so every XOR/AND in the addition-chain
# circuit processes 32 blocks at once.  Per-block keys pack the same
# way, which keeps the per-packet-key SRTP contract (each lane bit
# carries its own block's key bit).  NOTHING SELECTS IT: it is kept,
# with its parity test, because the v5e ran its chained rounds 8.5x
# faster than the tower's at 57,344 blocks (PERF.md section 6, PR 29);
# making it the chip's core is a `perf_opt` with a gain to show in
# place, where the key planes are packed per launch (ROADMAP Q1.8).

def _to_packed_planes(blocks):
    """[B, 16] uint8 (B % 32 == 0) -> 8 planes [B/32, 4, 4] uint32."""
    x = blocks.reshape(-1, 32, 16).astype(jnp.uint32)
    sh = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    planes = []
    for p in range(8):
        w = jnp.sum(((x >> p) & 1) << sh, axis=1, dtype=jnp.uint32)
        planes.append(w.reshape(-1, 4, 4).transpose(0, 2, 1))
    return planes


def _from_packed_planes(bits):
    """8 planes [G, 4, 4] uint32 -> [G*32, 16] uint8."""
    sh = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    acc = None
    for p in range(8):
        w = bits[p].transpose(0, 2, 1).reshape(-1, 1, 16)   # [G, 1, 16]
        bit = (w >> sh) & 1                                 # [G, 32, 16]
        acc = (bit << p) if acc is None else acc | (bit << p)
    return acc.astype(jnp.uint8).reshape(-1, 16)


@jax.jit
def aes_encrypt_bitsliced32(round_keys, blocks):
    """Packed-word twin of `aes_encrypt_bitsliced_tower` (32 blocks/word).

    round_keys [B, R, 16] uint8; blocks [B, 16] uint8 -> [B, 16].
    Pads B up to a multiple of 32 internally (zero blocks/keys) and
    slices the pad back off.
    """
    rk = jnp.asarray(round_keys, dtype=jnp.uint8)
    blk = jnp.asarray(blocks, dtype=jnp.uint8)
    n = blk.shape[0]
    pad = (-n) % 32
    if pad:
        blk = jnp.concatenate(
            [blk, jnp.zeros((pad, 16), jnp.uint8)], axis=0)
        rk = jnp.concatenate(
            [rk, jnp.zeros((pad,) + rk.shape[1:], jnp.uint8)], axis=0)
    nr = rk.shape[-2] - 1
    ones = jnp.uint32(0xFFFFFFFF)
    bits = _to_packed_planes(blk)
    rk_bits = [_to_packed_planes(rk[:, r, :]) for r in range(nr + 1)]
    out = _rounds(bits, rk_bits, nr, _sbox_bits, ones)
    return _from_packed_planes(out)[:n]


def aes_encrypt_bitsliced32_nd(round_keys, blocks):
    """Leading-dim-agnostic wrapper (see aes_encrypt_bitsliced_tower_nd)."""
    rk = jnp.asarray(round_keys, dtype=jnp.uint8)
    blk = jnp.asarray(blocks, dtype=jnp.uint8)
    lead = blk.shape[:-1]
    out = aes_encrypt_bitsliced32(rk.reshape((-1,) + rk.shape[-2:]),
                                  blk.reshape(-1, 16))
    return out.reshape(lead + (16,))
