"""Model step: model FLOPs of the prompt tokens prefilled in the window
(unpadded, causal attention, the last position's lm_head) over the device
time of the prefill, suffix-prefill and page-scatter programs at peak, %."""
from bench import flops


def read(ctx):
    done = ctx.prefills()
    dev = ctx.prefill_device_s(len(done))
    if not done or not dev:
        return None
    f = sum(flops.prefill(ctx.cfg, s.prompt_len - s.cached_tokens, s.cached_tokens)
            for s in done)
    return 100.0 * f / (dev * ctx.peaks["bf16_flops"])
