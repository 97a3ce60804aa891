"""The one traffic generator: a mix's data file (``bench/traffic/<name>.json``)
plus a seed in, a list of requests out.

Every seed gets the same schedule -- lengths, gaps and session sizes drawn
at the quantiles ``(i + 0.5) / n`` of their distributions, in one fixed
shuffled order -- and its own token ids.  So two seeds do the same work,
and the spread between runs is the system's, not the sampler's: with a few
arrivals in a window, each of whose prefills stalls decoding for seconds,
which of them land inside the window would otherwise set the numbers.
Arrivals are open loop: gaps are scaled so that the ``n`` window arrivals
fall inside ``[0, seconds)`` at exactly the cell's rate.

No two requests (two sessions) share a first token, so prompts that are
meant to be distinct share no page: a one-token partial match would send
them down the copy-on-write suffix path.

Times are seconds from the window's start.  Preloaded requests (``arrival``
0, ``preload`` true) are served before the window opens, so that it starts
with the decode batch about as full as in steady state; backlog requests
arrive at the window's first instant.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"

# distributions are tabulated once from this fixed sample, and their draws
# put in one fixed order: the schedule is the same for every seed
_TABLE_SIZE = 200_000
_TABLE_SEED = 20240117
_ORDER_SEED = 7


@dataclasses.dataclass
class Planned:
    """One request as the generator plans it."""

    rid: int
    prompt: List[int]
    max_new_tokens: int
    arrival: float
    preload: bool = False
    session: int = -1


def load(name: str) -> Dict:
    with open(TRAFFIC_DIR / f"{name}.json") as f:
        return json.load(f)


def _table(dist: Dict) -> np.ndarray:
    rng = np.random.default_rng(_TABLE_SEED)
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * np.exp(dist["sigma"] * rng.standard_normal(_TABLE_SIZE))
    elif kind == "exponential":
        x = rng.exponential(dist.get("mean", 1.0), _TABLE_SIZE)
    elif kind == "gamma":
        shape = 1.0 / dist["cv"] ** 2
        x = rng.gamma(shape, dist.get("mean", 1.0) / shape, _TABLE_SIZE)
    elif kind == "uniform":
        x = rng.uniform(dist["min"], dist["max"] + 1, _TABLE_SIZE)
    elif kind == "geometric":
        # failures before the first success, support {0, 1, ...}
        x = rng.geometric(1.0 / (1.0 + dist["mean"]), _TABLE_SIZE) - 1.0
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return np.sort(x)


def stratified(dist: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` draws at the quantiles (i + 0.5) / n, clipped, put in the order
    ``rng`` (the schedule's, not the seed's) draws."""
    if n <= 0:
        return np.zeros(0)
    table = _table(dist)
    idx = ((np.arange(n) + 0.5) / n * len(table)).astype(np.int64)
    x = table[idx]
    if "min" in dist:
        x = np.clip(x, dist["min"], dist["max"])
    return rng.permutation(x)


def lengths(dist: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    return np.floor(stratified(dist, n, rng)).astype(np.int64)


def arrival_times(dist: Dict, n: int, seconds: float, rng) -> np.ndarray:
    """``n`` arrival times in (0, seconds): n + 1 stratified gaps scaled to
    sum to the window, so the rate is exact and bursts keep their shape."""
    gaps = stratified(dict(dist, mean=1.0), n + 1, rng)
    return (np.cumsum(gaps) / gaps.sum() * seconds)[:n]


def generate(mix: Dict, *, seed: int, seconds: float, rate: float,
             max_slots: int, vocab: int) -> List[Planned]:
    """The requests of one run: preload, backlog and window arrivals; the
    schedule is fixed, the token ids come from ``seed``."""
    rng = _Split(np.random.default_rng(_ORDER_SEED), np.random.default_rng(seed))
    n_pre = round(mix["preload_share"] * max_slots)
    n_back = round(mix["backlog_share"] * max_slots)
    n_win = round(rate * seconds)
    if mix["kind"] == "independent":
        return _independent(mix, rng, n_pre, n_back, n_win, seconds, vocab)
    if mix["kind"] == "sessions":
        return _sessions(mix, rng, n_pre, n_win, seconds, vocab)
    raise ValueError(f"unknown traffic kind {mix['kind']!r}")


class _Split:
    """The schedule's generator for shuffles, the seed's for token ids."""

    def __init__(self, order: np.random.Generator, tokens: np.random.Generator):
        self.order, self.tokens = order, tokens

    def permutation(self, x):
        return self.order.permutation(x)

    def integers(self, lo, hi, n):
        return self.tokens.integers(lo, hi, n)


def _tokens(rng, n: int, vocab: int, first: int) -> List[int]:
    return [first] + rng.integers(0, vocab, n - 1).tolist()


def _independent(mix, rng, n_pre, n_back, n_win, seconds, vocab) -> List[Planned]:
    out: List[Planned] = []
    firsts = rng.tokens.permutation(vocab)
    groups = [(n_pre, None, True), (n_back, None, False),
              (n_win, arrival_times(mix["arrivals"], n_win, seconds, rng), False)]
    for n, times, pre in groups:
        plen = lengths(mix["prompt"], n, rng)
        olen = lengths(mix["output"], n, rng)
        for i in range(n):
            # backlog arrives at the window's first instant, not before it
            t = 0.0 if pre else (1e-6 if times is None else float(times[i]))
            out.append(Planned(len(out), _tokens(rng, int(plen[i]), vocab, int(firsts[len(out)])),
                               int(olen[i]), t, preload=pre))
    return out


def _sessions(mix, rng, n_pre, n_win, seconds, vocab) -> List[Planned]:
    n = n_pre + n_win
    starts = np.concatenate([np.zeros(n_pre),
                             arrival_times(mix["arrivals"], n_win, seconds, rng)])
    ctx = lengths(mix["context"], n, rng)
    extra = np.round(stratified(mix["extra_requests"], n, rng)).astype(np.int64)
    later = int(extra.sum())
    appends = lengths(mix["append"], later, rng)
    gaps = stratified(mix["session_gap_s"], later, rng)
    outs = lengths(mix["output"], n + later, rng)
    out: List[Planned] = []
    firsts = rng.tokens.permutation(vocab)
    j = 0  # index into the per-later-request draws
    for s in range(n):
        prompt = _tokens(rng, int(ctx[s]), vocab, int(firsts[s]))
        t = float(starts[s])
        pre = s < n_pre
        for k in range(1 + int(extra[s])):
            if k:
                prompt = prompt + rng.integers(0, vocab, int(appends[j])).tolist()
                t += float(gaps[j])
                j += 1
            if len(prompt) > mix["max_prompt"] or t >= seconds:
                j += int(extra[s]) - k  # keep later sessions' draws aligned
                break
            out.append(Planned(len(out), list(prompt), int(outs[len(out)]), t,
                               preload=pre and k == 0, session=s))
    return out
