"""`fanout_ghash_share_pct`: GHASH's share of the GCM fan-out's device
time — device time of the `XLA Ops` events under the named scope `ghash`
(`kernels/gcm.py:_tag`: the Horner chain of `[128, 128]` int8 matrix
products) inside the fan-out programs, per-row
(`jit__fanout_protect_gcm`) or leg-major, over those launches' device
time.  None under a suite without GHASH, or where the device events
carry no scope path."""

import scopes


def read(ctx):
    return scopes.share_pct(
        ctx, ("jit__fanout_protect_gcm", "jit__fanout_protect_gcm_legs"),
        "ghash")
