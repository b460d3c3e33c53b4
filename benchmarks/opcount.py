"""Bytes and operations of the bridge's three device calls, from their
shapes alone: no constant here comes from a run.

What is counted, per call over `rows` packets of `length` bytes each
(the REAL rows and lengths of the tick; padding to a row or length
class is the program's cost, not the algorithm's need):

* `bytes`: what has to cross HBM once — the packet read and the
  protected/opened packet written (`rows x length`, twice, plus the tag
  on the protected side), the per-row key material gathered from the
  table (AES round keys `11 x 16` bytes; for HMAC-SHA1 the two
  precomputed midstates `2 x 20` bytes; for GCM the GHASH key `16`
  bytes — a precomputed multiplication matrix would be more, and is
  the program's choice), the per-row IV (16), length, stream id and
  payload offset (3 x 4), and the ROC word (4).  Keystream is produced
  and consumed on chip and moves nothing.
* `int_ops`: 32-bit integer operations of a word-parallel evaluation.
  AES-128: 10 rounds, each 16 S-boxes of 113 gates (the Boyar-Peralta
  circuit), MixColumns 4 x 108 XORs, AddRoundKey 128, plus the first
  AddRoundKey: 23,808 bit operations per 16-byte block, 32 of them to
  a 32-bit word operation when bitsliced.  HMAC-SHA1 (RFC 2104 over
  FIPS 180-4): 80 rounds of about 11 word operations plus 64 schedule
  words of 4 = 1,136 per 64-byte block; the inner hash runs over
  `length + 4` bytes (ROC appended) after a precomputed midstate, the
  outer over one block.  GHASH: one GF(2^128) multiplication per
  16-byte block of AAD and ciphertext plus the length block, 128 x 4
  word operations each as shift-and-add.

Which peak bounds it: of the v5e's published peaks (bf16 matrix FLOP/s,
int8 matrix OP/s, HBM bytes/s) only HBM bandwidth applies to lane-wise
integer work; the matrix units do none of it.  So `least_time_s` is
`bytes / hbm_bytes_per_s`, and a roofline share built on it says how
far the call is from being memory-bound — far, for a cipher.  A compute
bound for 32-bit lane operations on the v5e has no public source and is
not invented here; `int_ops` is reported so that one can be applied
later.
"""

from __future__ import annotations

AES_BLOCK = 16
AES_BIT_OPS_PER_BLOCK = 10 * (16 * 113 + 4 * 108 + 128) + 128
SHA1_WORD_OPS_PER_BLOCK = 80 * 11 + 64 * 4
GHASH_WORD_OPS_PER_BLOCK = 128 * 4
RTP_HEADER = 12

#: per suite: (tag bytes, per-row key bytes gathered from the table)
SUITE = {
    "AES_CM_128_HMAC_SHA1_80": {"tag": 10, "key_bytes": 11 * 16 + 2 * 20},
    "AEAD_AES_128_GCM": {"tag": 16, "key_bytes": 11 * 16 + 16},
}
PER_ROW_META = 16 + 3 * 4 + 4


def _blocks(n: int, size: int) -> int:
    return -(-n // size)


def call_cost(suite: str, rows: int, mean_length: float) -> dict:
    """One protect, unprotect or fan-out protect call: `rows` packets
    of `mean_length` plaintext RTP bytes (header included).  Protect
    and unprotect move and compute the same amounts; a fan-out row is a
    protect row whose key is the receiver's."""
    s = SUITE[suite]
    length = float(mean_length)
    payload = max(0.0, length - RTP_HEADER)
    aes_blocks = payload / AES_BLOCK
    ops = aes_blocks * AES_BIT_OPS_PER_BLOCK / 32
    if suite.startswith("AEAD"):
        # GHASH over AAD (header) + ciphertext + length block, and one
        # more AES block for the tag mask, one for H is in the table
        ops += (_blocks(RTP_HEADER, 16) + payload / 16 + 1) \
            * GHASH_WORD_OPS_PER_BLOCK
        ops += AES_BIT_OPS_PER_BLOCK / 32
    else:
        inner = (length + 4 + 9) / 64
        ops += (inner + 1) * SHA1_WORD_OPS_PER_BLOCK
    per_row_bytes = 2 * length + s["tag"] + s["key_bytes"] + PER_ROW_META
    return {"bytes": rows * per_row_bytes, "int_ops": rows * ops,
            "bound": "hbm_bytes_per_s"}


def least_time_s(cost: dict, peaks: dict) -> float:
    """The least time the chip could take for `cost`: the larger of
    each resource over its published peak.  Only HBM has one."""
    return cost["bytes"] / peaks[cost["bound"]]
