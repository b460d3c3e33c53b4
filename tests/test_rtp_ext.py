"""`rtp/ext.py` against the functions it replaced, byte for byte.

`_ref_find` / `_ref_set` are the parent's `find_one_byte_ext` /
`set_one_byte_ext` (PR 24), copied verbatim: one shift pass of index
arithmetic over `[rows, capacity]` int64 grids.  The present functions
copy slices per layout group and must return the same `data` over the
whole buffer, the same `length` and the same `stream`, whatever the
rows hold.  The other tests hold the mechanism, not a time: how much a
stamp allocates, that a batch without a block ends the search at once,
and that the span reports its layout groups.
"""

import tracemalloc

import numpy as np
import pytest

from libjitsi_tpu.core.packet import PacketBatch, RTP_FIXED_HEADER_LEN
from libjitsi_tpu.rtp import ext as rtp_ext
from libjitsi_tpu.rtp import header as rtp_header
from libjitsi_tpu.rtp.ext import MAX_ELEMENTS, ONE_BYTE_PROFILE, _ceil4

EXT_ID = 3
CAP = 1504


# ---------------------------------------------------------------- reference
def _ref_find(batch, hdr, ext_id):
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


def _ref_set(batch, hdr, ext_id, payload, enable=None):
    payload = np.asarray(payload, dtype=np.uint8)
    n, L = payload.shape
    if not (1 <= ext_id <= 14) or not (1 <= L <= 16):
        raise ValueError("one-byte ext needs id in 1..14, len in 1..16")
    enable = np.ones(n, bool) if enable is None else np.asarray(enable, bool)

    d = batch.data
    ln = np.asarray(batch.length, dtype=np.int64)
    ext_start = (RTP_FIXED_HEADER_LEN + 4 * hdr.cc).astype(np.int64)
    has_block = (hdr.extension == 1) & (hdr.ext_profile == ONE_BYTE_PROFILE)
    eoff, elen, present = _ref_find(batch, hdr, ext_id)
    rewrite = enable & present & (elen == L)
    append = enable & has_block & ~rewrite
    fresh = enable & ~has_block & (hdr.extension == 0)

    # same id already present at a DIFFERENT length: blank the stale
    # element to padding zeros before appending, or receivers scanning in
    # order would keep seeing the old value shadowing the new one
    stale = enable & present & (elen != L)
    if np.any(stale):
        d = d.copy()
        scols = np.arange(batch.capacity, dtype=np.int64)[None, :]
        zone = (scols >= (eoff - 1)[:, None]) & \
            (scols < (eoff + elen)[:, None]) & stale[:, None]
        d = np.where(zone, 0, d)

    elem_sz = _ceil4(1 + L)
    grow = np.where(append, elem_sz, np.where(fresh, 4 + elem_sz, 0)
                    ).astype(np.int64)
    if np.any(ln + grow > batch.capacity):
        raise ValueError("extension stamp would exceed batch capacity")

    # insertion point: end of existing block (append) or ext_start (fresh)
    block_end = ext_start + 4 + 4 * hdr.ext_words.astype(np.int64)
    ins = np.where(append, block_end, ext_start)

    # batched shift: out[:, j] = d[:, j - grow] for j >= ins + grow
    cols = np.arange(batch.capacity, dtype=np.int64)[None, :]
    src = np.where(cols >= (ins + grow)[:, None], cols - grow[:, None], cols)
    out = np.take_along_axis(d, src.astype(np.int32), axis=1)

    # write the inserted region (zeros first: implicit padding)
    ins_region = (cols >= ins[:, None]) & (cols < (ins + grow)[:, None])
    out = np.where(ins_region, 0, out)

    def _write_at(arr, pos, vals):
        """Scatter vals [B, K] at per-row byte offset pos (masked rows only)."""
        k = vals.shape[1]
        rel = cols - pos[:, None]
        sel = (rel >= 0) & (rel < k)
        gathered = np.take_along_axis(
            vals, np.clip(rel, 0, k - 1).astype(np.int32), axis=1)
        return np.where(sel, gathered, arr)

    # fresh rows: block header 0xBEDE | words
    words = np.where(fresh, elem_sz // 4,
                     hdr.ext_words.astype(np.int64) + np.where(append, elem_sz // 4, 0))
    bh = np.zeros((n, 4), dtype=np.uint8)
    bh[:, 0] = ONE_BYTE_PROFILE >> 8
    bh[:, 1] = ONE_BYTE_PROFILE & 0xFF
    bh[:, 2] = (words >> 8) & 0xFF
    bh[:, 3] = words & 0xFF
    out = _write_at(out, np.where(fresh, ext_start, np.int64(1) << 40), bh)
    # append rows: patch the existing block header's length field
    out = _write_at(out, np.where(append, ext_start, np.int64(1) << 40), bh)

    # element bytes: tag || payload
    elem = np.zeros((n, 1 + L), dtype=np.uint8)
    elem[:, 0] = (ext_id << 4) | (L - 1)
    elem[:, 1:] = payload
    elem_pos = np.where(rewrite, eoff - 1,
                        np.where(append, ins, ins + 4))
    elem_pos = np.where(rewrite | append | fresh, elem_pos, np.int64(1) << 40)
    out = _write_at(out, elem_pos, elem)

    # set the X bit on fresh rows
    x = out[:, 0] | np.where(fresh, 0x10, 0).astype(np.uint8)
    out[:, 0] = x
    new_len = (ln + grow).astype(np.int32)
    return PacketBatch(out, new_len, batch.stream)


# ----------------------------------------------------------------- traffic
KINDS = ("fresh", "append", "rewrite", "stale", "other_profile",
         "malformed")


def _elements(rng, ext_id, count, stops=0.15):
    """`count` one-byte elements of other ids, with padding bytes and,
    with probability `stops`, the id-15 terminator with the target id
    behind it (whatever follows is then out of a receiver's sight)."""
    body = b""
    for _ in range(count):
        eid = int(rng.choice([i for i in range(1, 15) if i != ext_id]))
        ln = int(rng.integers(1, 17))
        body += bytes([(eid << 4) | (ln - 1)]) + rng.bytes(ln)
        body += b"\x00" * int(rng.integers(0, 3))
    if rng.random() < stops:
        body += bytes([0xF0, (ext_id << 4) | 2]) + rng.bytes(3)
    return body


def _row(rng, kind, ext_id, L, stops=0.15):
    """One packet of `kind` with 0-15 CSRCs and a 40-160 byte payload."""
    cc = int(rng.integers(0, 16))
    hdr = bytearray(rng.bytes(12 + 4 * cc))
    hdr[0] = 0x80 | cc
    ext = b""
    if kind in ("append", "rewrite", "stale", "malformed"):
        body = _elements(rng, ext_id, int(rng.integers(0, 4)), stops)
        if kind == "rewrite":
            body += bytes([(ext_id << 4) | (L - 1)]) + rng.bytes(L)
        elif kind == "stale":
            other = int(rng.choice([k for k in range(1, 17) if k != L]))
            body += bytes([(ext_id << 4) | (other - 1)]) + rng.bytes(other)
        body += _elements(rng, ext_id, int(rng.integers(0, 3)), stops)
        body += b"\x00" * (-len(body) % 4)
        words = len(body) // 4
        if kind == "malformed":
            # a block length that lies: past the packet, past the buffer
            words = int(rng.choice([words + 3, 200, 376, 1000, 65535]))
        ext = ONE_BYTE_PROFILE.to_bytes(2, "big") + \
            words.to_bytes(2, "big") + body
    elif kind == "other_profile":
        body = rng.bytes(4 * int(rng.integers(0, 5)))
        ext = b"\x10\x00" + (len(body) // 4).to_bytes(2, "big") + body
    if ext:
        hdr[0] |= 0x10
    return bytes(hdr) + ext + rng.bytes(int(rng.integers(40, 161)))


def _batch(rng, rows, kinds, ext_id, L, cap=CAP, stops=0.15):
    """`rows` packets drawn from `kinds`; the bytes past each packet's
    length are noise, so a shift of the whole buffer has to show."""
    pkts = [_row(rng, str(rng.choice(kinds)), ext_id, L, stops)
            for _ in range(rows)]
    b = PacketBatch(rng.integers(0, 256, (rows, cap), dtype=np.uint8),
                    np.zeros(rows, np.int32),
                    rng.integers(-1, 1000, rows).astype(np.int32))
    for i, p in enumerate(pkts):
        b.data[i, :len(p)] = np.frombuffer(p, np.uint8)
        b.length[i] = len(p)
    return b


def _enable(rng, rows, how):
    return {"all": None, "none": np.zeros(rows, bool),
            "mixed": rng.random(rows) < 0.5}[how]


def _assert_same(batch, ext_id, payload, enable):
    hdr = rtp_header.parse(batch)
    before = batch.data.copy()
    want = _ref_set(batch, hdr, ext_id, payload, enable)
    got = rtp_ext.set_one_byte_ext(batch, hdr, ext_id, payload, enable)
    np.testing.assert_array_equal(batch.data, before)   # input untouched
    assert got.data.dtype == np.uint8 and got.length.dtype == np.int32
    np.testing.assert_array_equal(got.data, want.data)
    np.testing.assert_array_equal(got.length, want.length)
    np.testing.assert_array_equal(got.stream, want.stream)
    # and the search alone, on what came out as on what went in
    for b in (batch, got):
        h = rtp_header.parse(b)
        for a, r in zip(rtp_ext.find_one_byte_ext(b, h, ext_id),
                        _ref_find(b, h, ext_id)):
            assert a.dtype == r.dtype
            np.testing.assert_array_equal(a, r)
    return got


# ------------------------------------------------------------ byte identity
@pytest.mark.parametrize("rows", [1, 88, 584])
@pytest.mark.parametrize("L", [1, 2, 3, 16])
@pytest.mark.parametrize("kinds", [(k,) for k in KINDS] + [KINDS],
                         ids=list(KINDS) + ["all_mixed"])
def test_set_matches_parent(kinds, L, rows):
    rng = np.random.default_rng([KINDS.index(kinds[0]), len(kinds), L, rows])
    batch = _batch(rng, rows, kinds, EXT_ID, L)
    payload = rng.integers(0, 256, (rows, L), dtype=np.uint8)
    _assert_same(batch, EXT_ID, payload, None)


@pytest.mark.parametrize("how", ["all", "none", "mixed"])
@pytest.mark.parametrize("kinds", [(k,) for k in KINDS] + [KINDS],
                         ids=list(KINDS) + ["all_mixed"])
def test_set_matches_parent_under_enable_mask(kinds, how):
    rng = np.random.default_rng([KINDS.index(kinds[0]), len(kinds),
                                 len(how)])
    rows = 88
    batch = _batch(rng, rows, kinds, 5, 2)
    payload = rng.integers(0, 256, (rows, 2), dtype=np.uint8)
    got = _assert_same(batch, 5, payload, _enable(rng, rows, how))
    if how == "none":
        np.testing.assert_array_equal(got.data, batch.data)
        np.testing.assert_array_equal(got.length, batch.length)


def test_other_profile_rows_pass_untouched():
    rng = np.random.default_rng(7)
    batch = _batch(rng, 88, ("other_profile",), EXT_ID, 3)
    got = _assert_same(batch, EXT_ID, np.ones((88, 3), np.uint8), None)
    np.testing.assert_array_equal(got.data, batch.data)
    np.testing.assert_array_equal(got.length, batch.length)


@pytest.mark.parametrize("kind", ["fresh", "append", "rewrite", "stale"])
def test_stamp_reads_back_per_row_payload(kind):
    """What a receiver finds: each row's own payload under the id, at
    the stated length, once."""
    rng = np.random.default_rng(KINDS.index(kind))
    rows, L = 88, 3
    batch = _batch(rng, rows, (kind,), EXT_ID, L, stops=0)
    payload = rng.integers(0, 256, (rows, L), dtype=np.uint8)
    got = _assert_same(batch, EXT_ID, payload, None)
    off, ln, found = rtp_ext.find_one_byte_ext(
        got, rtp_header.parse(got), EXT_ID)
    # the search counts a padding byte as a round of its sixteen, so a
    # long stale element blanked to zeros can hide what follows it: the
    # parent's behaviour, kept byte for byte
    assert found.all() if kind != "stale" else found.sum() > rows // 4
    assert (ln[found] == L).all()
    read = np.stack([got.data[np.arange(rows), off + k] for k in range(L)],
                    axis=1)
    np.testing.assert_array_equal(read[found], payload[found])


@pytest.mark.parametrize("kind", ["fresh", "append"])
def test_row_may_grow_to_exactly_capacity_and_no_further(kind):
    rng = np.random.default_rng(11)
    rows, L = 8, 3
    grow = _ceil4(1 + L) + (4 if kind == "fresh" else 0)
    batch = _batch(rng, rows, (kind,), EXT_ID, L)
    payload = rng.integers(0, 256, (rows, L), dtype=np.uint8)
    batch.length[5] = CAP - grow
    got = _assert_same(batch, EXT_ID, payload, None)
    assert got.length[5] == CAP
    batch.length[5] = CAP - grow + 1
    hdr = rtp_header.parse(batch)
    for fn in (_ref_set, rtp_ext.set_one_byte_ext):
        with pytest.raises(ValueError, match="exceed batch capacity"):
            fn(batch, hdr, EXT_ID, payload)
    # a row that is not stamped does not count against the capacity
    mask = np.ones(rows, bool)
    mask[5] = False
    _assert_same(batch, EXT_ID, payload, mask)


@pytest.mark.parametrize("ext_id,L", [(0, 3), (15, 3), (3, 0), (3, 17)])
def test_bad_id_or_length_is_refused(ext_id, L):
    batch = _batch(np.random.default_rng(1), 4, ("fresh",), 3, 3)
    with pytest.raises(ValueError, match="id in 1..14"):
        rtp_ext.set_one_byte_ext(batch, rtp_header.parse(batch), ext_id,
                                 np.zeros((4, L), np.uint8))


def test_small_buffer_and_empty_batch():
    """A narrower buffer and a batch of no rows."""
    rng = np.random.default_rng(3)
    batch = _batch(rng, 88, ("fresh", "append", "rewrite", "stale"),
                   EXT_ID, 3, cap=512)
    _assert_same(batch, EXT_ID,
                 rng.integers(0, 256, (88, 3), dtype=np.uint8), None)
    empty = PacketBatch.empty(0, CAP)
    got = _assert_same(empty, EXT_ID, np.zeros((0, 3), np.uint8), None)
    assert got.data.shape == (0, CAP)


# ------------------------------------------------------------ the mechanism
def _uniform(kind, rows=584, L=3, cc=0):
    """One kind of sender: every row the same layout, as a tick of the
    benchmark's generator or of one browser build is."""
    rng = np.random.default_rng(KINDS.index(kind))
    body = {"fresh": None,
            "append": bytes([0x10, 0x55, 0, 0]),
            "rewrite": bytes([0x10, 0x55, (EXT_ID << 4) | (L - 1)])
            + bytes(L) + b"\x00" * (-(3 + L) % 4)}[kind]
    pay = [rng.bytes(int(rng.integers(40, 161))) for _ in range(rows)]
    return rtp_header.build(
        pay, np.arange(rows), 0, 0x1000 + np.arange(rows), 111,
        csrcs=[[9] * cc] * rows,
        ext=None if body is None else [(ONE_BYTE_PROFILE, body)] * rows)


@pytest.mark.parametrize("kind", ["fresh", "append", "rewrite"])
def test_stamp_allocates_a_few_buffers_not_a_hundred(kind):
    """The parent built `[rows, 1504]` int64 grids: near 100 x the batch
    at its peak.  One output, one gathered group and the `[rows]`-sized
    index arrays fit in 4 x."""
    batch = _uniform(kind)
    hdr = rtp_header.parse(batch)
    payload = np.full((batch.batch_size, 3), 7, np.uint8)
    peaks = {}
    for name, fn in (("now", rtp_ext.set_one_byte_ext), ("ref", _ref_set)):
        fn(batch, hdr, EXT_ID, payload)
        tracemalloc.start()
        try:
            out = fn(batch, hdr, EXT_ID, payload)
            peaks[name] = tracemalloc.get_traced_memory()[1]
        finally:
            tracemalloc.stop()
        assert out.data.shape == batch.data.shape
    assert peaks["now"] <= 4 * batch.data.nbytes, peaks
    assert peaks["ref"] >= 20 * batch.data.nbytes, peaks


def test_find_without_a_block_gathers_nothing(monkeypatch):
    batch = _uniform("fresh")
    hdr = rtp_header.parse(batch)
    calls = []
    real = np.take_along_axis

    def counting(*a, **kw):
        calls.append(a[1].shape)
        return real(*a, **kw)

    with_block = _uniform("rewrite")
    hdr_block = rtp_header.parse(with_block)
    monkeypatch.setattr(np, "take_along_axis", counting)
    _off, _ln, found = rtp_ext.find_one_byte_ext(batch, hdr, EXT_ID)
    assert calls == [] and not found.any()
    # with a block: one round per element up to the hit, not sixteen
    _off, _ln, found = rtp_ext.find_one_byte_ext(with_block, hdr_block,
                                                 EXT_ID)
    assert len(calls) == 2 and found.all()


@pytest.mark.parametrize("kinds,cc,groups", [
    (("fresh",), (0,), 1),
    (("fresh",), (0, 2), 2),
    (("rewrite",), (0, 1, 5), 1),          # nothing grows: one plain copy
    (("fresh", "rewrite"), (0,), 2),
    (("fresh", "append"), (0, 3), 4),
])
def test_layout_groups_counted(kinds, cc, groups):
    parts = [_uniform(k, rows=16, cc=c) for k in kinds for c in cc]
    batch = PacketBatch(np.concatenate([p.data for p in parts]),
                        np.concatenate([p.length for p in parts]),
                        np.concatenate([p.stream for p in parts]))
    rng = np.random.default_rng(5)
    perm = rng.permutation(batch.batch_size)     # groups are not runs
    batch = PacketBatch(batch.data[perm], batch.length[perm],
                        batch.stream[perm])
    payload = rng.integers(0, 256, (batch.batch_size, 3), dtype=np.uint8)
    _assert_same(batch, EXT_ID, payload, None)
    _out, n = rtp_ext.stamp_one_byte_ext(batch, rtp_header.parse(batch),
                                         EXT_ID, payload)
    assert n == groups


def test_abs_send_time_span_counts_rows_and_groups(sfu_with_traffic):
    """The engagement counter: the `abs_send_time` span's `rows` and
    `groups` reach `sup.last_counts` (and with it `/healthz` and the
    trace's `stage:abs_send_time` stats)."""
    sfu, sup, send = sfu_with_traffic
    send.until_forwarded()
    assert sup.last_counts["abs_send_time"] == {"rows": 3, "groups": 1}
    send.csrcs[1] = [0xC5C5]          # one sender behind a mixer
    send.until_forwarded()
    assert sup.last_counts["abs_send_time"] == {"rows": 3, "groups": 2}
    assert sup.health()["last_counts"]["abs_send_time"]["groups"] == 2
