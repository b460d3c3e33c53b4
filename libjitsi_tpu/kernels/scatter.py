"""Per-row byte scatter/gather shared by the SRTP and GCM kernels.

`scatter_bytes` writes a small per-row byte vector at a per-row column
offset using UNROLLED broadcast compare+selects.  The obvious
`take_along_axis(src, col - pos)` form is a per-element dynamic gather
over the full [B, W] plane — fetch-verified at ~135 ms per scatter at
65536x192 on a v5e, 3x the cost of the AES keystream it decorates —
while n broadcast compares are plain vector ops.  `gather_span` keeps
`take_along_axis` because its gather plane is only [B, n] (n <= 20).
`shift_right` moves whole rows by a per-row amount the same way: a
ladder of static pad-shifts, one select a bit of the amount.
"""

from __future__ import annotations

import jax.numpy as jnp


def scatter_bytes(data, pos, src, n: int):
    """Write src[:, :n] ([B, >=n] uint8) into data [B, W] at per-row
    byte offset pos [B]; positions beyond W fall off the end (no-op),
    matching the masked-gather form this replaces."""
    col = jnp.arange(data.shape[1], dtype=jnp.int32)[None, :]
    pos = pos[:, None]
    out = data
    for k in range(n):
        out = jnp.where(col == pos + k, src[:, k][:, None], out)
    return out


def gather_span(data, pos, n: int):
    """Read n bytes at per-row byte offset `pos` -> [B, n] (clamped)."""
    idx = pos[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
    idx = jnp.clip(idx, 0, data.shape[1] - 1)
    return jnp.take_along_axis(data, idx, axis=1)


def shift_right(data, amount, limit: int):
    """Each row of data [B, W] moved right by its own `amount` [B]
    (int32, 0 <= amount < `limit`, `limit` static), zeros coming in on
    the left: out[b, j] = data[b, j - amount[b]].  One static pad-shift
    and one select a bit of `limit - 1`, so an amount that is data
    costs what a compiled-in one costs and no [B, W] gather runs."""
    width = data.shape[1]
    amount = amount[:, None]
    out = data
    for k in range(max(0, limit - 1).bit_length()):
        moved = jnp.pad(out, ((0, 0), (1 << k, 0)))[:, :width]
        out = jnp.where((amount >> k) & 1 == 1, moved, out)
    return out
