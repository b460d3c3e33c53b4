"""`unprotect_ghash_share_pct`: GHASH's share of the GCM unprotect's
device time — the named scope `ghash` inside the unprotect programs,
per-row (`jit__unprotect_gcm_impl`) or grouped, over those launches'
device time.  None under a suite without GHASH, or where the device
events carry no scope path."""

import scopes


def read(ctx):
    return scopes.share_pct(
        ctx, ("jit__unprotect_gcm_impl", "jit__unprotect_gcm_grouped_dev"),
        "ghash")
