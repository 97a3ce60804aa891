"""Engine loop: mean wall time of the program's ``prefill`` tracer spans that
ended in the window, ms.  The span ends when the first token is read back,
which waits for the forward and the page scatter: how long decode stands
still for one arrival.  A program whose ``prefill`` span holds no
``prefill_sync`` times only the enqueue, and reads nothing here."""


def read(ctx):
    d = [e - s for name, s, e in ctx.spans if name == "prefill"]
    if not d or not any(name == "prefill_sync" for name, _, _ in ctx.spans):
        return None
    return sum(d) / len(d) * 1e3
