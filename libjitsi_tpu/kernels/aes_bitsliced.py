"""Bitsliced, gather-free AES — the SURVEY §7 "hard parts" candidate.

The production AES path (`kernels.aes.aes_encrypt`) uses a 256-entry
S-box `jnp.take`, which XLA lowers well but Mosaic (Pallas TPU) refuses
to lower at all.  This module builds AES-128/256 encryption as a pure
Boolean circuit — XOR/AND/slice/concat only, no gathers — so the same
body runs as an XLA program *and* as a Pallas kernel, and the provider
registry (`kernels.registry`, the reference's `.srtp.crypto.Aes`
benchmark-and-pick pattern) can measure all three and keep the winner.

Circuit construction is derived, not transcribed: the S-box is computed
as ``affine(x^254)`` over GF(2^8), with the squaring/power linear maps
and the polynomial-reduction matrix generated from field arithmetic at
import time and the complete 256-entry truth table asserted against an
independently generated S-box.  Inversion uses the addition chain
x -> x^2 -> x^3 -> x^12 -> x^15 -> x^240 -> x^252 -> x^254
(4 variable GF multiplications; squarings are linear).

State layout: 8 bit-planes, each ``[B, 4, 4]`` (byte i = row + 4*col),
LSB-first bit order.  ShiftRows is slice+concat per row; MixColumns is
xtime/XOR over row variables — nothing here indexes by data.
"""

from __future__ import annotations

import functools

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
# Round-5: the addition-chain inversion above costs 4 GF(2^8)
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


def _shift_rows_bits(bits, cat):
    out = []
    for p in bits:
        rows = []
        for r in range(4):
            row = p[:, r:r + 1, :]
            rows.append(cat([row[..., r:], row[..., :r]], -1)
                        if r else row)
        out.append(cat(rows, 1))
    return out


def _mix_columns_bits(bits, stack):
    rows = [[p[:, r, :] for p in bits] for r in range(4)]
    new_rows = []
    for r in range(4):
        a, b = rows[r], rows[(r + 1) % 4]
        c, d = rows[(r + 2) % 4], rows[(r + 3) % 4]
        new_rows.append(_vxor(_vxor(_xtime_bits(a), _vxor(_xtime_bits(b),
                                                          b)),
                              _vxor(c, d)))
    return [stack([new_rows[r][p] for r in range(4)], 1)
            for p in range(8)]


def _rounds(bits, rk_bits, nr: int, cat, stack, ones=1,
            sbox=None):
    """The shared round schedule over bit-plane state (`sbox` picks
    the inversion circuit: addition-chain `_sbox_bits` or the
    composite-field `_sbox_bits_tower`)."""
    sbox = sbox or _sbox_bits
    bits = _vxor(bits, rk_bits[0])
    for r in range(1, nr):
        bits = sbox(bits, ones)
        bits = _shift_rows_bits(bits, cat)
        bits = _mix_columns_bits(bits, stack)
        bits = _vxor(bits, rk_bits[r])
    bits = sbox(bits, ones)
    bits = _shift_rows_bits(bits, cat)
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


def _make_plane_provider(sbox):
    """Build the (jitted flat fn, leading-dim-agnostic wrapper) pair
    for one S-box circuit — the plane setup and the `_nd` reshape
    contract ([..., R, 16] broadcast keys from the CTR/GCM call sites)
    exist ONCE, shared by the addition-chain and tower providers."""

    @jax.jit
    def flat(round_keys, blocks):
        rk = jnp.asarray(round_keys, dtype=jnp.uint8)
        nr = rk.shape[-2] - 1
        bits = _to_planes(jnp.asarray(blocks, dtype=jnp.uint8))
        rk_bits = [_to_planes(rk[:, r, :]) for r in range(nr + 1)]
        out = _rounds(bits, rk_bits, nr, jnp.concatenate, jnp.stack,
                      sbox=sbox)
        return _from_planes(out)

    def nd(round_keys, blocks):
        rk = jnp.asarray(round_keys, dtype=jnp.uint8)
        blk = jnp.asarray(blocks, dtype=jnp.uint8)
        lead = blk.shape[:-1]
        out = flat(rk.reshape((-1,) + rk.shape[-2:]),
                   blk.reshape(-1, 16))
        return out.reshape(lead + (16,))

    return flat, nd


# Drop-in twins of `kernels.aes.aes_encrypt_table`, gather-free:
# round_keys [B, R, 16] uint8; blocks [B, 16] uint8 -> [B, 16].  The
# `_nd` forms take leading-dim-agnostic ([..., R, 16]) arguments.
# `tower` uses the composite-field S-box (5 GF(2^4) multiplies instead
# of 4 GF(2^8) ones; fetch-verified ~1.6x on v5e).
aes_encrypt_bitsliced, aes_encrypt_bitsliced_nd = \
    _make_plane_provider(_sbox_bits)
aes_encrypt_bitsliced_tower, aes_encrypt_bitsliced_tower_nd = \
    _make_plane_provider(_sbox_bits_tower)


# ----------------------------------------------- packed-word XLA provider
#
# Round-5: the provider above stores ONE bit per uint8 element; this
# one packs 32 BLOCKS per uint32 word (plane p, word (g, byte): bit k
# = bit p of byte of block 32g + k), so every XOR/AND in the identical
# circuit processes 32 blocks at once.  Per-block keys pack the same
# way, which keeps the per-packet-key SRTP contract (each lane bit
# carries its own block's key bit).  Fetch-verified on the v5e the two
# providers measured at PARITY (~10-12M blocks/s net — XLA:TPU handles
# the u8 planes better than the classic bitslice intuition predicts),
# so this stays a selectable provider for the registry/`set_core`
# rather than the default; other TPU generations may rank differently.

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
    """Packed-word twin of `aes_encrypt_bitsliced` (32 blocks/word).

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
    out = _rounds(bits, rk_bits, nr, jnp.concatenate, jnp.stack,
                  ones=ones)
    return _from_packed_planes(out)[:n]


def aes_encrypt_bitsliced32_nd(round_keys, blocks):
    """Leading-dim-agnostic wrapper (see aes_encrypt_bitsliced_nd)."""
    rk = jnp.asarray(round_keys, dtype=jnp.uint8)
    blk = jnp.asarray(blocks, dtype=jnp.uint8)
    lead = blk.shape[:-1]
    out = aes_encrypt_bitsliced32(rk.reshape((-1,) + rk.shape[-2:]),
                                  blk.reshape(-1, 16))
    return out.reshape(lead + (16,))


# ------------------------------------------------------------ Pallas provider
#
# The first Pallas twin (refused with a MosaicError on the chip) ran `reshape(-1, 4, 4).transpose(0, 2, 1)` on uint8 INSIDE the
# kernel — minor-dim relayout + 8-bit shifts, exactly what Mosaic
# declines to lower.  This version is lane-native instead: the batch
# rides the 128-wide lane axis, each bit plane is a [4, 4, 128] int32
# tile (row, col, lane), bit extraction/packing happens OUTSIDE the
# kernel as plain XLA, and the kernel body is nothing but elementwise
# XOR/AND plus static sublane slice+concat (ShiftRows) and stacks
# (MixColumns) — no transpose, no gather, no sub-32-bit arithmetic.

_LANES = 128


def _shift_rows_tile(bits):
    """[4, 4, L] planes: row r rolls left by r columns (axis 1)."""
    out = []
    for p in bits:
        rows = []
        for r in range(4):
            row = p[r]                       # [4 cols, L]
            if r:
                row = jnp.concatenate([row[r:], row[:r]], axis=0)
            rows.append(row)
        out.append(jnp.stack(rows, axis=0))
    return out


def _mix_columns_tile(bits):
    rows = [[p[r] for p in bits] for r in range(4)]   # [4 cols, L] each
    new_rows = []
    for r in range(4):
        a, b = rows[r], rows[(r + 1) % 4]
        c, d = rows[(r + 2) % 4], rows[(r + 3) % 4]
        new_rows.append(_vxor(_vxor(_xtime_bits(a),
                                    _vxor(_xtime_bits(b), b)),
                              _vxor(c, d)))
    return [jnp.stack([new_rows[r][p] for r in range(4)], axis=0)
            for p in range(8)]


def _pallas_kernel(bits_ref, rk_ref, out_ref, *, nr: int):
    """Bit-plane tile in VMEM: bits [8, 4, 4, L], rk [(nr+1)*8, 4, 4, L]."""
    bits = [bits_ref[p] for p in range(8)]
    rk_bits = [[rk_ref[r * 8 + p] for p in range(8)]
               for r in range(nr + 1)]
    bits = _vxor(bits, rk_bits[0])
    for r in range(1, nr):
        bits = _sbox_bits(bits)
        bits = _shift_rows_tile(bits)
        bits = _mix_columns_tile(bits)
        bits = _vxor(bits, rk_bits[r])
    bits = _sbox_bits(bits)
    bits = _shift_rows_tile(bits)
    bits = _vxor(bits, rk_bits[nr])
    for p in range(8):
        out_ref[p] = bits[p]


def _to_lane_planes(x16):
    """[B, 16] uint8 -> [8, 4, 4, B] int32 bit planes (row, col, lane).

    byte i = row + 4*col, same state layout as the XLA provider."""
    y = x16.reshape(-1, 4, 4).transpose(2, 1, 0)      # [row, col, B]
    return jnp.stack([((y >> p) & 1).astype(jnp.int32)
                      for p in range(8)], axis=0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def aes_encrypt_pallas_bitsliced(round_keys, blocks,
                                 interpret: bool = False):
    """Pallas twin of `aes_encrypt_bitsliced` (lane-native layout)."""
    from jax.experimental import pallas as pl

    rk = jnp.asarray(round_keys, dtype=jnp.uint8)
    blocks = jnp.asarray(blocks, dtype=jnp.uint8)
    nr = rk.shape[-2] - 1
    b = blocks.shape[0]
    pad = (-b) % _LANES
    if pad:
        blocks = jnp.pad(blocks, ((0, pad), (0, 0)))
        rk = jnp.pad(rk, ((0, pad), (0, 0), (0, 0)))
    bp = b + pad
    bits = _to_lane_planes(blocks)                    # [8, 4, 4, BP]
    rkb = _to_lane_planes(
        rk.transpose(1, 0, 2).reshape(-1, 16)
    ).reshape(8, 4, 4, nr + 1, bp)
    # [(nr+1)*8, 4, 4, BP]: round-major so the kernel indexes r*8+p
    rkb = rkb.transpose(3, 0, 1, 2, 4).reshape((nr + 1) * 8, 4, 4, bp)
    out = pl.pallas_call(
        functools.partial(_pallas_kernel, nr=nr),
        grid=(bp // _LANES,),
        in_specs=[
            pl.BlockSpec((8, 4, 4, _LANES), lambda i: (0, 0, 0, i)),
            pl.BlockSpec(((nr + 1) * 8, 4, 4, _LANES),
                         lambda i: (0, 0, 0, i)),
        ],
        out_specs=pl.BlockSpec((8, 4, 4, _LANES),
                               lambda i: (0, 0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((8, 4, 4, bp), jnp.int32),
        interpret=interpret,
    )(bits, rkb)
    acc = out[0]
    for p in range(1, 8):
        acc = acc | (out[p] << p)
    res = acc.astype(jnp.uint8).transpose(2, 1, 0).reshape(-1, 16)
    return res[:b] if pad else res


# ------------------------------------------------------------------ registry

def register_providers() -> None:
    from libjitsi_tpu.kernels import aes as aes_mod
    from libjitsi_tpu.kernels import registry

    registry.register("aes_encrypt", "xla_table", aes_mod.aes_encrypt)
    registry.register("aes_encrypt", "xla_bitsliced",
                      aes_encrypt_bitsliced)
    registry.register("aes_encrypt", "xla_bitsliced_tower",
                      aes_encrypt_bitsliced_tower)
    registry.register("aes_encrypt", "xla_bitsliced32",
                      aes_encrypt_bitsliced32)
    registry.register("aes_encrypt", "pallas_bitsliced",
                      aes_encrypt_pallas_bitsliced)


register_providers()
