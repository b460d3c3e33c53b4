"""A named scope's share of several programs' device time.

`xstats.scope_share_pct` reads one program.  A GCM call has two forms
(per-row and grouped GHASH; per-row and leg-major fan-out), each a
program of its own, and which runs is the shape's to say: the `ghash`
readers take the scope's share over whichever of them ran in the slice.
"""

from __future__ import annotations

import reduce
import xstats


def share_pct(ctx, programs, scope: str):
    """100 x device time of the `XLA Ops` events under the named scope
    `scope` inside any of `programs` (`jit__<function>` names) over
    those programs' `XLA Modules` time.  None where none of them ran in
    the slice, or no operation of theirs carries the scope: an untraced
    run, a program without them (the parent), a trace whose operations
    carry no scope path."""
    evs = xstats.slice_events(ctx)
    if evs is None:
        return None
    total = sum(d for name, _s, d, _st in evs["modules"]
                if reduce.program_name(name) in programs)
    if not total:
        return None
    prefixes = tuple("jit(" + p[len("jit_"):] + ")/" for p in programs)
    needle = "/" + scope + "/"
    under = sum(d for d, strings in xstats.op_paths(
        ctx["trace"]["xplane"], evs["lo"], evs["hi"])
        if any(s.startswith(prefixes) and needle in s + "/"
               for s in strings))
    return 100.0 * under / total if under else None
