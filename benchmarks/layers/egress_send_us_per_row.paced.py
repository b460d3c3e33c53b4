"""`egress_send_us_per_row`: what one delivery costs the egress worker:
the window's summed `egress_send` seconds (the worker's `sendmmsg` of
each fan-out burst by its own stamps, booked by the tick that reaps it:
`ctx["ticks"]["stage"]`) over the fan-out rows the window's ticks made
(packets read x receivers a packet), in us.  8-10 on the chip machines'
user-space kernel.  None where the program books no `egress_send` (it
sends on the tick thread) or the window read nothing."""


def read(ctx):
    t = ctx["ticks"]
    send = t["stage"].get("egress_send")
    rows = float(t["rx"].sum()) * ctx["system"]["fanout"]
    if send is None or rows <= 0:
        return None
    return 1e6 * float(send.sum()) / rows
