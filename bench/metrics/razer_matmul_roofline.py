"""Kernel: the packed-weight matmul's share of its roofline in the decode
program, %.  Per step, every projection at M = max_slots needs
max(2MKN / peak FLOP/s, (K*N/2 + K*N/16 + 2MK + 2MN) / peak B/s); the
kernel's time is the sum of its ``razer_matmul`` events there."""
from bench import flops
from bench.weights import matrix_shapes


def read(ctx):
    t, n = ctx.decode_kernel_s("razer_matmul")
    _, steps = ctx.decode_device_s()
    if not n or not steps:
        return None
    m = ctx.cfg["serving"]["max_slots"]
    need = sum(max(f / ctx.peaks["bf16_flops"], b / ctx.peaks["hbm_bytes_per_s"])
               for f, b in (flops.matmul_call(m, k, nn)
                            for k, nn in matrix_shapes(ctx.cfg).values()))
    return 100.0 * need * ctx.cfg["num_hidden_layers"] / (t / steps)
