"""Kernel: the paged decode attention's share of its roofline, %.  The work
is the live tokens' (their KV codes and scales read once, QK^T and PV over
them), not the pages the kernel visits; the kernel's time is the sum of its
``razer_paged_attention`` events in the decode program."""
from bench import flops


def read(ctx):
    t, n = ctx.decode_kernel_s("razer_paged_attention")
    _, dsteps = ctx.decode_device_s()
    _, attended, hsteps = ctx.decode_work()
    if not n or not dsteps or not hsteps:
        return None
    cfg, pk = ctx.cfg, ctx.peaks
    need = max(flops.attention_flops(cfg, [attended]) / pk["bf16_flops"],
               flops.decode_attention_bytes(cfg, [attended]) / pk["hbm_bytes_per_s"])
    return 100.0 * (need / hsteps) / (t / dsteps)
