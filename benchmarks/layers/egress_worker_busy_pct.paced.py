"""`egress_worker_busy_pct`: the share of the window the egress worker
spent inside `sendmmsg`: the window's summed `egress_send` seconds
(`ctx["ticks"]["stage"]`, booked by the tick that reaps each burst) over
the window's seconds (first tick's start to last tick's end).  One
worker sends every delivery, so this is how near the cell stands to the
worker's knee: at 100 the worker, not the tick, is the pace.  None where
the program books no `egress_send`."""


def read(ctx):
    t = ctx["ticks"]
    send = t["stage"].get("egress_send")
    if send is None or len(t["t_end"]) < 2:
        return None
    window_s = (float(t["t_end"][-1] - t["t_end"][0]) / 1e9
                + float(t["tick_s"][0]))
    return 100.0 * float(send.sum()) / window_s if window_s > 0 else None
