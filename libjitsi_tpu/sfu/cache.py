"""Retransmission cache (reference: `.caching.CachingTransformer` /
`RawPacketCache`): recently-sent packets keyed (ssrc, seq), serving
NACK-triggered retransmission (RFC 4585 NACK -> RFC 4588 RTX or verbatim
resend).

Host-side: NACKs are rare and tiny relative to media; an OrderedDict FIFO
with byte/age bounds matches the reference's size-limited cache without
device involvement.

Two structures, chosen by what the caller holds.  `PacketCache` takes
one packet at a time (the video tracks' pre-SRTP cache, the cascade
trunk, `RtxSender`): an ordered dict is right there.  `SlabCache` takes
a whole fan-out batch as the plane it already lies in (the SFU bridge's
per-leg cache): the insert is a handful of NumPy calls whatever the row
count, and `bytes` are made only for the packets a NACK asks for.
"""

from __future__ import annotations

import collections
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np


def nack_serve_order(lost_seqs: Sequence[int]) -> List[int]:
    """A NACK's seqs, deduplicated, in the circular order they are
    served in (see `PacketCache.lookup_nack`, whose rule this is; the
    one thing the two caches share)."""
    ss = sorted({int(s) & 0xFFFF for s in lost_seqs})
    if len(ss) > 1:
        gaps = [(ss[i] - ss[i - 1]) & 0xFFFF for i in range(len(ss))]
        k = gaps.index(max(gaps))     # i=0 wraps to ss[-1]
        ss = ss[k:] + ss[:k]
    return ss


class PacketCache:
    def __init__(self, max_bytes: int = 4 << 20, max_age: float = 1.0):
        self.max_bytes = max_bytes
        self.max_age = max_age
        self._store: "collections.OrderedDict[Tuple[int, int], Tuple[float, bytes]]" = (
            collections.OrderedDict())
        self._bytes = 0

    def insert(self, ssrc: int, seq: int, packet: bytes,
               now: Optional[float] = None) -> None:
        """`ssrc` is the cache namespace: a plain 32-bit SSRC for the
        single-stream RTX case, or any wider composite key (e.g. the
        SFU's (leg_sid << 32) | sender_ssrc) — it is NOT masked, so
        composite namespaces never collide."""
        now = time.time() if now is None else now
        key = (int(ssrc), seq & 0xFFFF)
        old = self._store.pop(key, None)
        if old is not None:
            self._bytes -= len(old[1])
        self._store[key] = (now, packet)
        self._bytes += len(packet)
        self._evict(now)

    def insert_batch(self, ssrcs, seqs, packets: Sequence[bytes],
                     now: Optional[float] = None) -> None:
        now = time.time() if now is None else now
        for ssrc, seq, pkt in zip(ssrcs, seqs, packets):
            self.insert(int(ssrc), int(seq), pkt, now)

    def get(self, ssrc: int, seq: int) -> Optional[bytes]:
        e = self._store.get((int(ssrc), seq & 0xFFFF))
        return e[1] if e is not None else None

    def lookup_nack(self, ssrc: int, lost_seqs: Sequence[int],
                    return_missing: bool = False):
        """Packets available for retransmission out of a NACK's list.

        Deduplicates and serves in *circular* seq order: a NACK whose
        list straddles 65535->0 parses (sorted numerically) as e.g.
        [0, 1, 65534, 65535] — a plain sort would retransmit the wrap
        side first and re-scramble the very packets the receiver is
        trying to repair.  The serve order is anchored just after the
        largest mod-2^16 gap between the requested seqs, which is
        where the circular sequence "starts".

        With `return_missing=True` returns `(packets, missing_seqs)` so
        the caller can count cache misses.
        """
        ss = nack_serve_order(lost_seqs)
        out: List[bytes] = []
        missing: List[int] = []
        for s in ss:
            p = self.get(ssrc, s)
            if p is not None:
                out.append(p)
            else:
                missing.append(s)
        if return_missing:
            return out, missing
        return out

    def _evict(self, now: float) -> None:
        while self._store:
            (key, (t, pkt)) = next(iter(self._store.items()))
            if self._bytes > self.max_bytes or now - t > self.max_age:
                self._store.popitem(last=False)
                self._bytes -= len(pkt)
            else:
                break

    @property
    def size_bytes(self) -> int:
        return self._bytes

    def __len__(self) -> int:
        return len(self._store)


_M64 = (1 << 64) - 1


class _Slab:
    """One `insert_batch`: the plane, its lengths, and the index over
    its rows.  Rows below `low` are evicted."""

    __slots__ = ("t", "data", "length", "ns", "keys", "order", "low",
                 "nbytes", "cum")

    def __init__(self, t, data, length, ns, keys, order, nbytes):
        self.t, self.data, self.length, self.ns = t, data, length, ns
        self.keys, self.order, self.nbytes = keys, order, nbytes
        self.low = 0
        self.cum = None         # cumulative lengths, once the byte bound bites


def _root_nbytes(a: np.ndarray) -> int:
    """Bytes of the array whose memory `a` is a view of."""
    while isinstance(a.base, np.ndarray):
        a = a.base
    return int(a.nbytes)


class SlabCache:
    """`PacketCache`'s contract for a caller that inserts whole batches
    of RTP packets: the namespace of a row is `(leg << 32) | ssrc`, the
    ssrc and the seq read from the packet's own header.

    An insert keeps the plane it is given (no copy, no `bytes`, no
    per-row Python object) and indexes it with one sorted `uint64` key
    array, `(namespace << 16 | seq) mod 2**64`; a hit is checked against
    the row's full namespace, so a composite namespace wider than 48
    bits cannot answer for another.  The caller hands over a plane that
    nobody writes to afterwards: the slab is a view of it, and pins the
    whole of its memory (padding rows and columns included) until the
    slab's last row is evicted: `resident_bytes`.

    Lookups walk the live slabs newest first, so a key inserted twice
    answers with the newer copy.  The superseded copy is not found
    again but stays in `size_bytes` and `len()` until it ages out (the
    bridge's replay window drops duplicates before the fan-out, so in
    service there is none).  Eviction is `PacketCache`'s: at insert,
    oldest first, whole slabs by `max_age` (every row of one insert
    shares `now`) and single packets by `max_bytes`, through a
    low-water row in the oldest slab.
    """

    def __init__(self, max_bytes: int = 4 << 20, max_age: float = 1.0):
        self.max_bytes = max_bytes
        self.max_age = max_age
        self._slabs: "collections.deque[_Slab]" = collections.deque()
        self._bytes = 0
        self._rows = 0

    def insert_batch(self, data: np.ndarray, length, legs,
                     now: Optional[float] = None) -> int:
        """`data` `[rows, width]` uint8 RTP packets, `length` `[rows]`,
        `legs` `[rows]` the receiver leg of each row.  Returns the rows
        it evicted."""
        if not len(legs):
            return 0            # as `PacketCache`: nothing in, nothing out
        now = time.time() if now is None else now
        length = np.asarray(length)
        seq = np.ascontiguousarray(data[:, 2:4]).view(">u2")[:, 0]
        ssrc = np.ascontiguousarray(data[:, 8:12]).view(">u4")[:, 0]
        ns = (np.asarray(legs).astype(np.uint64) << np.uint64(32)) | ssrc
        keys = (ns << np.uint64(16)) | seq
        order = np.argsort(keys)
        nbytes = int(length.sum())
        self._slabs.append(_Slab(now, data, length, ns, keys[order], order,
                                 nbytes))
        self._bytes += nbytes
        self._rows += len(order)
        return self._evict(now)

    def _evict(self, now: float) -> int:
        slabs, evicted = self._slabs, 0
        while slabs:
            s = slabs[0]
            excess = self._bytes - self.max_bytes
            if now - s.t > self.max_age or excess > s.nbytes:
                gone, freed = len(s.order) - s.low, s.nbytes
                slabs.popleft()
            elif excess > 0:
                # the fewest oldest rows that bring the total under
                # the bound
                if s.cum is None:
                    s.cum = np.cumsum(s.length, dtype=np.int64)
                base = int(s.cum[s.low - 1]) if s.low else 0
                k = int(np.searchsorted(s.cum, base + excess, side="left"))
                gone, freed = k + 1 - s.low, int(s.cum[k]) - base
                s.low, s.nbytes = k + 1, s.nbytes - freed
                if s.low == len(s.order):
                    slabs.popleft()
            else:
                break
            self._bytes -= freed
            self._rows -= gone
            evicted += gone
        return evicted

    def _find(self, ns: int, seqs: Sequence[int]) -> List[Optional[bytes]]:
        ns = int(ns)
        out: List[Optional[bytes]] = [None] * len(seqs)
        want = np.array([((ns << 16) | s) & _M64 for s in seqs],
                        dtype=np.uint64)
        left = np.arange(len(seqs))
        for s in reversed(self._slabs):
            if not len(left):
                break
            pos = np.searchsorted(s.keys, want[left], side="right") - 1
            for j in np.nonzero(s.keys[pos] == want[left])[0]:
                # of the rows under this key (one, but for a repeat or
                # a namespace that wrapped onto it) the newest live one
                p, key, r = int(pos[j]), want[left[j]], -1
                while p >= 0 and s.keys[p] == key:
                    if int(s.ns[s.order[p]]) == ns:
                        r = max(r, int(s.order[p]))
                    p -= 1
                if r >= s.low:
                    out[left[j]] = s.data[r, :s.length[r]].tobytes()
            left = left[[out[i] is None for i in left]]
        return out

    def get(self, ssrc: int, seq: int) -> Optional[bytes]:
        return self._find(ssrc, [seq & 0xFFFF])[0]

    def lookup_nack(self, ssrc: int, lost_seqs: Sequence[int],
                    return_missing: bool = False):
        """`PacketCache.lookup_nack`, same order and same `missing`."""
        ss = nack_serve_order(lost_seqs)
        found = self._find(ssrc, ss)
        out = [p for p in found if p is not None]
        if return_missing:
            return out, [s for s, p in zip(ss, found) if p is None]
        return out

    @property
    def size_bytes(self) -> int:
        return self._bytes

    @property
    def slabs(self) -> int:
        return len(self._slabs)

    @property
    def resident_bytes(self) -> int:
        """What the live slabs pin in memory: each plane whole, and
        its index."""
        return sum(_root_nbytes(s.data) + s.length.nbytes + s.ns.nbytes
                   + s.keys.nbytes + s.order.nbytes for s in self._slabs)

    def __len__(self) -> int:
        return self._rows
