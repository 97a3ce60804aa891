"""Prefix cache: prompt tokens served from cached pages over all prompt
tokens, for the requests admitted in the window, %."""


def read(ctx):
    adm = [s for s in ctx.seen if not s.preload and s.prefill_start is not None]
    total = sum(s.prompt_len for s in adm)
    return 100.0 * sum(s.cached_tokens for s in adm) / total if total else None
