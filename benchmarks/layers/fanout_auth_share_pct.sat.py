"""`fanout_auth_share_pct`: the HMAC's share of the fan-out program's
device time — device time of the `XLA Ops` events under the named scope
`auth` inside `jit__fanout_protect` launches over those launches' device
time.  None where the device events carry no scope path."""

import xstats


def read(ctx):
    return xstats.scope_share_pct(ctx, "jit__fanout_protect", "auth")
