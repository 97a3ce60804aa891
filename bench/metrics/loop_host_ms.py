"""Engine loop: host time per decode step in which the serial loop gives the
chip nothing to do, ms -- the summed wall time of the program's host-phase
tracer spans (admission, page-table rebuild, decode dispatch, token
bookkeeping) in the window over the ``decode_step`` spans in it."""

HOST_PHASES = ("sched_admit", "page_table", "decode_dispatch", "sched_post_decode")


def read(ctx):
    steps = sum(1 for name, _, _ in ctx.spans if name == "decode_step")
    host = [e - s for name, s, e in ctx.spans if name in HOST_PHASES]
    if not steps or not host:
        return None
    return sum(host) / steps * 1e3
