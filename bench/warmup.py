"""Set-up requests that compile every program shape a cell's traffic can
reach, served before the window through the same ``Engine.serve``.

The engine compiles one prefill per power-of-two prompt bucket (B=1), one
prefix-cached suffix prefill per (suffix bucket, prefix-page bucket) pair,
one page scatter per bucket, and one decode step at ``max_slots``.  Which of
those a mix reaches follows from its length clips: independent prompts never
share a page (their first tokens differ), so they reach full prefills only;
a session's later request reuses the earlier prompt's whole pages and
prefills the rest, 16 to 271 tokens.
"""
from __future__ import annotations

from typing import Dict, List, Set, Tuple

import numpy as np

from bench.traffic import Planned


def bucket(n: int) -> int:
    """The engine's prefill bucket for ``n`` tokens."""
    return max(8, 1 << (n - 1).bit_length())


def page_bucket(cached: int, page_size: int, pages_per_seq: int) -> int:
    """The engine's bucket of gathered prefix pages for ``cached`` tokens."""
    return min(1 << (-(-cached // page_size) - 1).bit_length(), pages_per_seq)


def shapes(mix: Dict, cfg: Dict) -> Tuple[Set[int], Set[Tuple[int, int]]]:
    """(prefill buckets, (suffix bucket, page bucket) pairs) the mix reaches."""
    sv = cfg["serving"]
    ps, pps = sv["page_size"], -(-sv["max_len"] // sv["page_size"])
    if mix["kind"] == "independent":
        lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
        return {bucket(n) for n in range(lo, hi + 1)}, set()
    lo, top = mix["context"]["min"], mix["max_prompt"]
    full = {bucket(n) for n in range(lo, top + 1)}
    amin, amax = mix["append"]["min"], mix["append"]["max"]
    pairs = set()
    for prev in range(lo, top - amin + 1):
        cached = ps * (prev // ps)
        npb = page_bucket(cached, ps, pps)
        for s in range(prev % ps + amin, prev % ps + min(amax, top - prev) + 1):
            pairs.add((bucket(s), npb))
    return full, pairs


def plan(mix: Dict, cfg: Dict, seed: int) -> List[Planned]:
    """Requests whose prefills hit every reachable shape once, then one that
    decodes, all due at once (served in this order)."""
    sv = cfg["serving"]
    ps, pps = sv["page_size"], -(-sv["max_len"] // sv["page_size"])
    top = mix.get("max_prompt", mix.get("prompt", {}).get("max"))
    rng = np.random.default_rng(seed)
    vocab = cfg["vocab_size"]
    out: List[Planned] = []
    # distinct first tokens: no warm-up prompt shares a page with another
    firsts = iter(rng.permutation(vocab).tolist())

    def fresh(n: int) -> List[int]:
        return [next(firsts)] + rng.integers(0, vocab, n - 1).tolist()

    def add(prompt, new=1):
        out.append(Planned(len(out), prompt, new, 0.0))

    full, pairs = shapes(mix, cfg)
    for b in sorted(full):
        add(fresh(min(b, top)))
    for npb in sorted({p for _, p in pairs}):
        pages = 1 if npb == 1 else npb // 2 + 1
        assert page_bucket(pages * ps, ps, pps) == npb
        donor = fresh(pages * ps + ps)
        add(donor)
        for sb in sorted(s for s, p in pairs if p == npb):
            s = min(sb, top - pages * ps)
            assert bucket(s) == sb, (sb, s)
            # the suffix starts with a token the donor's next page lacks
            tail = fresh(s)
            while tail[0] == donor[pages * ps]:
                tail[0] = (tail[0] + 1) % vocab
            add(donor[: pages * ps] + tail)
    add(fresh(ps), new=2)
    return out
