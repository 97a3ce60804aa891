"""Page pool: the share of the window the device spends in the program that
quantizes a prefill's K/V into pool pages (``_quantize_scatter``), %."""


def read(ctx):
    if ctx.device is None:
        return None
    return 100.0 * ctx.module_s("_quantize_scatter") / ctx.end
