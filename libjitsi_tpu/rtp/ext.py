"""RFC 5285 one-byte RTP header extensions, vectorized.

The reference's `RawPacket.getHeaderExtension(byte id)` /
`addExtension(...)` walk the extension block per packet; the engines that
stamp extensions on the hot path (`AbsSendTimeEngine`,
`TransportCCEngine`, `CsrcTransformEngine`'s audio level) all use the
one-byte form (profile 0xBEDE).  Here the walk is a bounded vectorized
cursor loop over the whole batch, which ends once every row is decided,
and the insert is two slice copies per LAYOUT GROUP: rows that insert
the same number of bytes at the same offset move together, and a tick
of one kind of sender is one group.  Per-row work is `[rows]`-sized
index arithmetic; the only `[rows, capacity]` array made is the output.

Only the one-byte element form is handled (id 1..14, len 1..16); 0xBEDE
is the only recognized profile, matching what WebRTC interop actually
uses and what the reference's engines emit.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from libjitsi_tpu.core.packet import PacketBatch, RTP_FIXED_HEADER_LEN
from libjitsi_tpu.rtp.header import RtpHeaders

ONE_BYTE_PROFILE = 0xBEDE
MAX_ELEMENTS = 16  # scan bound: more elements than this are ignored


def _ceil4(x):
    return (x + 3) & ~3


def find_one_byte_ext(batch: PacketBatch, hdr: RtpHeaders, ext_id: int
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Locate element `ext_id` in each row's 0xBEDE extension block.

    Returns (data_off [B], data_len [B], found [B]): byte offset of the
    element *payload* and its length.  Rows without the element (or
    without a one-byte-profile extension) have found=False.
    """
    d = batch.data
    n = batch.batch_size
    ext_start = (RTP_FIXED_HEADER_LEN + 4 * hdr.cc).astype(np.int64)
    has = (hdr.extension == 1) & (hdr.ext_profile == ONE_BYTE_PROFILE)
    end = ext_start + 4 + 4 * hdr.ext_words.astype(np.int64)

    cur = np.where(has, ext_start + 4, np.int64(1) << 40)  # cursor per row
    off = np.zeros(n, dtype=np.int64)
    dlen = np.zeros(n, dtype=np.int64)
    found = np.zeros(n, dtype=bool)
    cap = batch.capacity
    for _ in range(MAX_ELEMENTS):
        inb = (cur < end) & ~found
        if not inb.any():
            break  # every row is found or past its block: no round can change one
        safe = np.minimum(np.maximum(cur, 0), cap - 1).astype(np.int32)
        b = np.take_along_axis(d, safe[:, None], axis=1)[:, 0].astype(np.int64)
        eid = b >> 4
        elen = (b & 0x0F) + 1  # encoded len-1
        is_pad = inb & (b == 0)
        is_stop = inb & (eid == 15)  # id 15 terminates parsing per RFC
        hit = inb & ~is_pad & ~is_stop & (eid == ext_id)
        off = np.where(hit, cur + 1, off)
        dlen = np.where(hit, elen, dlen)
        found |= hit
        # advance: padding skips 1 byte, element skips 1 + len
        step = np.where(is_pad, 1, 1 + elen)
        cur = np.where(inb & ~is_stop & ~hit, cur + step,
                       np.where(is_stop, end, cur))
    return off, dlen, found


def set_one_byte_ext(batch: PacketBatch, hdr: RtpHeaders, ext_id: int,
                     payload: np.ndarray, enable=None) -> PacketBatch:
    """Stamp element `ext_id` = payload[i] into every enabled row, batched.

    payload: uint8 [B, L] with one static L for the whole call (each
    engine stamps one fixed-size element: abs-send-time L=3, transport-cc
    seq L=2, ssrc-audio-level L=1).  Three per-row cases:

    - element already present with length L: rewritten in place;
    - 0xBEDE block present, element absent: element appended after the
      block (block grows by ceil4(1+L));
    - no extension block: a fresh one-byte-profile block is inserted
      after the CSRCs (grows by 4 + ceil4(1+L)).

    Rows with enable=False pass through untouched.  Returns a new
    PacketBatch (host-side NumPy; stamping happens before SRTP in the
    send chain, exactly as the reference orders its engines).
    """
    return stamp_one_byte_ext(batch, hdr, ext_id, payload, enable)[0]


def _put(out: np.ndarray, rows: np.ndarray, col: np.ndarray, val) -> None:
    """out[rows[i], col[i]] = val[i].  A row whose offset lies past the
    buffer is skipped: a malformed block length points anywhere."""
    ok = col < out.shape[1]
    if not ok.all():
        rows, col, val = rows[ok], col[ok], np.broadcast_to(val, ok.shape)[ok]
    out[rows, col] = val


def stamp_one_byte_ext(batch: PacketBatch, hdr: RtpHeaders, ext_id: int,
                       payload: np.ndarray, enable=None
                       ) -> Tuple[PacketBatch, int]:
    """`set_one_byte_ext`, and the number of layout groups the call had:
    distinct (insertion offset, growth) among its rows, the rows that do
    not grow being one.  Near 1 the copies are whole-batch slices; near
    the row count each row is copied alone."""
    payload = np.asarray(payload, dtype=np.uint8)
    n, L = payload.shape
    if not (1 <= ext_id <= 14) or not (1 <= L <= 16):
        raise ValueError("one-byte ext needs id in 1..14, len in 1..16")
    enable = np.ones(n, bool) if enable is None else np.asarray(enable, bool)

    d = batch.data
    cap = batch.capacity
    ln = np.asarray(batch.length, dtype=np.int64)
    ext_start = (RTP_FIXED_HEADER_LEN + 4 * hdr.cc).astype(np.int64)
    has_block = (hdr.extension == 1) & (hdr.ext_profile == ONE_BYTE_PROFILE)
    eoff, elen, present = find_one_byte_ext(batch, hdr, ext_id)
    rewrite = enable & present & (elen == L)
    append = enable & has_block & ~rewrite
    fresh = enable & ~has_block & (hdr.extension == 0)

    elem_sz = _ceil4(1 + L)
    grow = np.where(append, elem_sz, np.where(fresh, 4 + elem_sz, 0)
                    ).astype(np.int64)
    if np.any(ln + grow > cap):
        raise ValueError("extension stamp would exceed batch capacity")

    # insertion point: end of existing block (append) or ext_start (fresh)
    block_end = ext_start + 4 + 4 * hdr.ext_words.astype(np.int64)
    ins = np.where(append, block_end, ext_start)
    tag = (ext_id << 4) | (L - 1)

    # rows of one (ins, grow) share a layout; growth is at most 24
    key = np.where(grow > 0, ins * 32 + grow, 0)
    order = np.argsort(key, kind="stable")
    groups = np.split(order, np.flatnonzero(np.diff(key[order])) + 1) \
        if n else []
    out = np.empty_like(d)
    for g in groups:
        # a run of neighbours is a slice: no gathered copy of its rows
        rows = slice(g[0], g[-1] + 1) if g[-1] - g[0] + 1 == len(g) else g
        i, w = int(ins[g[0]]), int(grow[g[0]])
        if w == 0:
            out[rows] = d[rows]
            continue
        # the inserted bytes: [0xBEDE | words] on a fresh row, then
        # tag || payload, then zeros (implicit padding)
        blk = np.zeros((len(g), w), dtype=np.uint8)
        head = w - elem_sz  # 4 on a fresh row, 0 on an appending one
        if head:
            blk[:, :4] = (ONE_BYTE_PROFILE >> 8, ONE_BYTE_PROFILE & 0xFF,
                          0, elem_sz // 4)
        blk[:, head] = tag
        blk[:, head + 1:head + 1 + L] = payload[rows]
        out[rows, :i] = d[rows, :i]
        out[rows, i:i + w] = blk[:, :max(cap - i, 0)]
        out[rows, i + w:] = d[rows, i:cap - w]
        if head:
            out[rows, 0] |= 0x10  # the X bit

    rw = np.flatnonzero(rewrite)
    at = eoff[rw] - 1
    _put(out, rw, at, tag)
    for k in range(L):
        _put(out, rw, at + 1 + k, payload[rw, k])
    # an appended element lengthens its block: patch the header's length
    # field (`has_block` read the 0xBEDE before it)
    ap = np.flatnonzero(append)
    words = hdr.ext_words[ap].astype(np.int64) + elem_sz // 4
    _put(out, ap, ext_start[ap] + 2, (words >> 8) & 0xFF)
    _put(out, ap, ext_start[ap] + 3, words & 0xFF)
    # same id already present at a DIFFERENT length: blank the stale
    # element to padding zeros, or receivers scanning in order would keep
    # seeing the old value shadowing the new one.  It starts inside the
    # block, before the insertion point; a malformed length can run it
    # past that point, and that part has moved by the row's growth
    for r in np.flatnonzero(append & present):
        a, b, i, w = (int(v) for v in
                      (eoff[r] - 1, eoff[r] + elen[r], ins[r], grow[r]))
        out[r, a:min(b, i)] = 0
        if b > i:
            out[r, i + w:b + w] = 0

    new_len = (ln + grow).astype(np.int32)
    return PacketBatch(out, new_len, batch.stream), len(groups)
