"""Model step: the whole decode step's share of the chip's peak, %:
max(FLOPs / peak FLOP/s, bytes / peak B/s) over the decode program's
device time.  FLOPs of the live slots only; bytes of the packed weights,
the lm_head and the live tokens' KV, once per step."""


def read(ctx):
    return ctx.decode_step_share()
