"""One packed array in and one out per device launch.

A device call over a packet batch needs, beside the bytes, a handful of
per-row values: the key row, the length, the payload offset, the ROC
and the IV.  Staged as arrays of their own, each is a host-to-device
transfer (and, where its NumPy dtype is not the program's, a
`convert_element_type` program) and each output a blocking copy back:
a dozen fixed costs a tick round two programs.  Here they ride in
`TAIL` trailing columns of the `uint8` packet plane instead:

    [ packet bytes ............ | w0 w1 w2 w3 | iv ]      in
    [ packet bytes ............ | o0 o1 ..    |  0 ]      out
      width                       4 x 4 B       16 B

so a launch is one `jax.device_put` of a C-contiguous array whose dtype
is the program's, and one `np.asarray` back.  The plane that comes back
has the staged plane's shape, so a donated input can be written over.

Words are little-endian on both sides, put together and taken apart by
shifts in the program: no width-changing `bitcast_convert_type`, whose
byte order would be the backend's to choose.

Host side: `alloc` where the rows are copied anyway, `pack`, then one
`put` (the `jax.device_put`, a span of its own inside the call's
`dispatch`); `Launch.fetch` and `split_out`.  Inside the jitted
program: `unpack`, `repack`.  The two programs a served tick launches stage this way
under AES-CM and under AES-GCM alike: the RTP unprotect
(`transform/srtp/context.py`; GCM in its per-row form) and the per-row
fan-out (`sfu/translator.py`).  GCM has no ROC word (three words, the
fourth 0) and its 12-byte IV rides in the first 12 IV columns.  On a
device mesh the two CM programs stage the same plane gathered into
lanes, `[chips, lanes, width + TAIL]`, a block a chip each way, with
the chip-local key row as word 0 (mesh/table.py `_packed_call`).

What keeps an array an argument (`put_each`, which counts what
crossed): the grouped GCM form above the row classes (its grid arrays
have another shape than the rows), the leg-major GCM fan-out,
`protect_rtp`, AES-F8, a keystream-cache hit, SRTCP, and the mesh
seams no cell runs (CM protect, F8, GCM, SRTCP, the GCM fan-outs),
which route an array per lane to its owning chip (mesh/table.py
`_sharded_call`, counted the same way).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from libjitsi_tpu.utils.tracing import span_of

WORDS = 4                       # per-row 32-bit words in, and room out
IV_BYTES = 16
TAIL = 4 * WORDS + IV_BYTES     # 32: 224 -> 256, 544 -> 576, 1536 -> 1568

# (tracer, "<seam>_put") of the `dispatch` open on this thread
_seam = threading.local()


# ---- host ----------------------------------------------------------------

def alloc(rows: int, width: int) -> np.ndarray:
    """A zeroed plane for `rows` packets of up to `width` bytes; the
    packet bytes are `plane[:, :width]`."""
    return np.zeros((rows, width + TAIL), dtype=np.uint8)


def pack(plane: np.ndarray, words: Sequence, iv) -> None:
    """Write up to WORDS per-row words (each `[rows]`, any integer
    dtype, taken modulo 2**32) and the IVs (`[rows, 16]`, or GCM's
    `[rows, 12]`) behind the packet bytes.  Columns of a word or an IV
    byte not given keep what `alloc` left there: zero."""
    rows, w = plane.shape[0], plane.shape[1] - TAIL
    side = np.empty((rows, len(words)), dtype="<u4")
    for k, word in enumerate(words):
        side[:, k] = word
    plane[:, w:w + 4 * len(words)] = side.view(np.uint8)
    at = w + 4 * WORDS
    plane[:, at:at + np.shape(iv)[1]] = iv


def split_out(host: np.ndarray, n_words: int
              ) -> Tuple[np.ndarray, np.ndarray]:
    """A plane that came back -> (packet bytes, a view; `[rows,
    n_words]` int32 of the program's output words)."""
    w = host.shape[1] - TAIL
    words = np.ascontiguousarray(host[:, w:w + 4 * n_words])
    return host[:, :w], words.view("<i4")


class dispatch:
    """`with staging.dispatch(tracer, "fanout") as sp:`: the span
    `<seam>_dispatch` of one device call of a tick (seam `unprotect` or
    `fanout`), from `pack` to the jit call's return.  While it is open,
    a `put` / `put_each` on THIS thread books `<seam>_put` as its
    child; one on a thread inside no `dispatch` (a warm-up in the
    compile pool, a table standing alone) opens no span, so the
    tracer's lock-free tree stays the tick thread's."""

    __slots__ = ("_span", "_here", "_prev")

    def __init__(self, tracer, seam: str, **counts):
        self._span = span_of(tracer, seam + "_dispatch", **counts)
        self._here = (tracer, seam + "_put")

    def __enter__(self):
        self._prev = getattr(_seam, "at", None)
        _seam.at = self._here
        return self._span.__enter__()

    def __exit__(self, *exc) -> None:
        _seam.at = self._prev
        self._span.__exit__(*exc)


def put_each(arrays, sharding=None) -> Tuple[list, int, int]:
    """THE host-to-device copy of a device call, and the one place a
    served call reaches `jax.device_put`: every array of `arrays`
    crosses as it stands (its dtype is settled on the host by the
    caller, so no `convert_element_type` program runs for it), to the
    default device, or onto a mesh a block a chip where `sharding`
    gives an array its `NamedSharding`.  Inside a `dispatch` the copies
    lie in ONE span `<seam>_put`, booked with what crossed.  Returns
    (device arrays, how many crossed, their bytes)."""
    host = [np.ascontiguousarray(a) for a in arrays]
    n, nbytes = len(host), sum(int(a.nbytes) for a in host)
    tracer, stage = getattr(_seam, "at", None) or (None, "")
    with span_of(tracer, stage, h2d_arrays=n, h2d_bytes=nbytes):
        dev = [jax.device_put(a, None if sharding is None else sharding(a))
               for a in host]
    return dev, n, nbytes


def put(plane: np.ndarray, sharding=None):
    """`put_each` of one packed plane (on a mesh: one lane plane, a
    block a chip under the `NamedSharding` `sharding`); returns the
    device array."""
    (dev,), _n, _nbytes = put_each(
        (plane,), None if sharding is None else lambda _a: sharding)
    return dev


class Launch:
    """A device call in flight: what crossed to the device for it, and
    how its outputs come to the host.  `outs` are the program's outputs
    as they stand on the device (one packed plane, on a mesh in lane
    layout, a block a chip; the three arrays of a grouped GCM, an F8
    or a cache-hit unprotect; an unpacked mesh seam's outputs in lane
    layout); `split` turns their host copies into what the caller
    reads.  `fetch` waits, copies each
    output once and caches; `is_ready` says without waiting whether
    the wait would be none, for a caller that collects the launch long
    after it made it (`dispatched_at`); `h2d_arrays` / `h2d_bytes` / `d2h_arrays` /
    `d2h_bytes` count the arrays that really crossed.  `counts` is what
    else the caller's span should book for the call (the GCM calls:
    `gm_gather_bytes`, `grouped`; a mesh call: `shards`, `lanes`,
    `rows_hottest_shard`, `affine`), `d2h_counts` what the span round
    the copy back should (`unprotect_d2h`, `fanout_d2h`: a mesh call's
    four again)."""

    __slots__ = ("_outs", "_split", "_host", "h2d_arrays", "h2d_bytes",
                 "d2h_arrays", "d2h_bytes", "counts", "d2h_counts",
                 "dispatched_at")

    def __init__(self, outs, split: Optional[Callable] = None,
                 h2d_arrays: int = 0, h2d_bytes: int = 0,
                 counts: Optional[dict] = None,
                 d2h_counts: Optional[dict] = None):
        self._outs = tuple(outs)
        self._split = split
        self._host = None
        self.h2d_arrays, self.h2d_bytes = h2d_arrays, h2d_bytes
        self.d2h_arrays = self.d2h_bytes = 0
        self.counts = counts or {}
        self.d2h_counts = d2h_counts or {}
        #: `time.perf_counter()` when the call had been made (the jit
        #: call returned): what a later collection measures from
        self.dispatched_at = time.perf_counter()

    def copy_back_async(self) -> "Launch":
        """Ask for the outputs' host copies now: each starts when the
        program ends, not when the thread that waits for it has woken
        (what `np.asarray` of an output still in flight does first).
        An output that is no device array (a mesh seam's deferred
        scatter, a length known on the host) is passed over, as
        `jax.block_until_ready` passes it over."""
        for o in self._outs:
            if isinstance(o, jax.Array):
                o.copy_to_host_async()
        return self

    def is_ready(self) -> bool:
        """Whether a `block_until_ready` now would return at once:
        every output that is a device array has been computed (one
        that is none counts as ready, as `copy_back_async` passes it
        over).  Never waits."""
        return all(o.is_ready() for o in self._outs
                   if isinstance(o, jax.Array))

    def block_until_ready(self) -> "Launch":
        if self._host is None:
            jax.block_until_ready(self._outs)
        return self

    def fetch(self) -> tuple:
        if self._host is None:
            host = [np.asarray(o) for o in self._outs]
            self.d2h_arrays = len(host)
            self.d2h_bytes = sum(int(a.nbytes) for a in host)
            self._host = (tuple(host) if self._split is None
                          else self._split(*host))
            self._outs = ()
        return self._host


# ---- inside the jitted program -------------------------------------------

def unpack(plane):
    """plane `[rows, width + TAIL]` uint8 -> (data `[rows, width]`,
    words `[rows, WORDS]` uint32, iv `[rows, 16]`)."""
    w = plane.shape[1] - TAIL
    b = plane[:, w:w + 4 * WORDS].reshape(-1, WORDS, 4).astype(jnp.uint32)
    words = (b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16)
             | (b[..., 3] << 24))
    return plane[:, :w], words, plane[:, w + 4 * WORDS:]


def as_i32(word):
    """A packed word as the signed value the host put in (a stream id
    of -1 stays -1)."""
    return jax.lax.bitcast_convert_type(word, jnp.int32)


def repack(data, *words):
    """data `[rows, width]` and up to WORDS per-row outputs (bool or
    32-bit integers) -> one plane of the staged plane's shape."""
    rows = data.shape[0]
    cols = [jax.lax.bitcast_convert_type(w, jnp.uint32)
            if w.dtype == jnp.int32 else w.astype(jnp.uint32)
            for w in words]
    shifts = jnp.array((0, 8, 16, 24), dtype=jnp.uint32)
    side = jnp.stack(cols, axis=1)[..., None] >> shifts
    side = (side & 0xFF).astype(jnp.uint8).reshape(rows, 4 * len(cols))
    pad = jnp.zeros((rows, TAIL - 4 * len(cols)), dtype=jnp.uint8)
    return jnp.concatenate([data, side, pad], axis=1)
