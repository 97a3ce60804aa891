"""Engine loop: mean wall time of the program's ``decode_step`` tracer spans
in the window (each ends in a device sync), ms."""


def read(ctx):
    d = [e - s for name, s, e in ctx.spans if name == "decode_step"]
    return sum(d) / len(d) * 1e3 if d else None
