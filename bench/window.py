"""The measured window, kept through the clock ``Engine.serve`` is given,
and the end-to-end arithmetic over what it saw.

``WindowClock`` stands still at 0 while the preloaded requests are served,
opens the window the first time it is read after every one of them has its
first token, and ends the run by raising ``WindowClosed`` from the first
reading past the window's end: ``Engine.serve`` otherwise returns only once
every request has drained.  Every time the engine records is then seconds
from the window's start.  Token counts are taken from the first reading
after the window opens: the decode step that ran while the clock stood
still has appended its tokens by then (no tracer), or has not yet run (a
tracer's ``decode_step`` span opened the window).  From then on every
reading notes which requests gained tokens, which gives each output token
the time of the step that made it.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Dict, List, Optional, Sequence


class WindowClosed(Exception):
    """Raised by ``WindowClock.now`` once the window is over."""


class WindowClock:
    def __init__(self, preload: Sequence, seconds: float, requests: Sequence,
                 on_open: Optional[Callable[[], None]] = None):
        self._preload = list(preload)
        self._requests = list(requests)
        self.seconds = float(seconds)
        self._on_open = on_open
        self.t_open: Optional[float] = None  # perf_counter at the window's start
        self.tokens_at_open: Optional[Dict[int, int]] = None
        # rid -> [tokens produced in the window, time of its first, of its last]
        self.token_times: Dict[int, List[float]] = {}
        self._count: Dict[int, int] = {}
        self._prev = 0.0

    def now(self) -> float:
        if self.t_open is None:
            if any(r.first_token_time is None for r in self._preload):
                return 0.0
            if self._on_open is not None:
                self._on_open()
            self.t_open = time.perf_counter()
            return 0.0
        t = time.perf_counter() - self.t_open
        if self.tokens_at_open is None:
            self.tokens_at_open = {r.rid: len(r.out_tokens) for r in self._requests}
            self._count = dict(self.tokens_at_open)
        else:
            self._poll(self._prev)
        self._prev = t
        if t >= self.seconds:
            raise WindowClosed
        return t

    def _poll(self, at: float) -> None:
        """Tokens that appeared since the last reading were appended right
        after it, once the step the engine read the clock for had ended:
        they are stamped with that reading."""
        for r in self._requests:
            n, c = len(r.out_tokens), self._count[r.rid]
            if n > c:
                rec = self.token_times.setdefault(r.rid, [0, at, at])
                rec[0] += n - c
                rec[2] = at
                self._count[r.rid] = n

    def drained(self) -> None:
        """``serve`` returned before the window closed: stamp the tokens of
        its last step."""
        if self.tokens_at_open is not None:
            self._poll(self._prev)

    def sleep(self, seconds: float) -> None:
        if seconds > 0:
            time.sleep(seconds if self.t_open is None
                       else min(seconds, max(self.seconds - self.now(), 0.0) + 1e-3))


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    return v[max(0, math.ceil(q / 100 * len(v)) - 1)]


@dataclasses.dataclass
class Seen:
    """What one request looked like when the window closed."""

    arrival: float
    prompt_len: int
    preload: bool
    tokens_at_open: int
    tokens: int
    prefill_start: Optional[float]
    first_token_time: Optional[float]
    finish_time: Optional[float]
    cached_tokens: int
    in_window: int = 0              # tokens produced inside the window
    first_in_window: float = 0.0    # when the first and the last of them came
    last_in_window: float = 0.0

    @classmethod
    def of(cls, req, preload: bool, clock: "WindowClock") -> "Seen":
        at_open = (clock.tokens_at_open or {}).get(req.rid, 0)
        k, first, last = clock.token_times.get(req.rid, (0, 0.0, 0.0))
        return cls(req.arrival, len(req.prompt), preload, at_open, len(req.out_tokens),
                   req.prefill_start, req.first_token_time, req.finish_time,
                   req.cached_tokens, int(k), first, last)


def in_window(s: Seen, end: float) -> bool:
    """Arrived inside the window (preloaded requests did not)."""
    return not s.preload and s.arrival <= end


def ttft_values(seen: List[Seen], end: float) -> List[float]:
    """First-token time minus arrival, for every request that arrived in the
    window; one with no first token counts as ``end - arrival``."""
    return [(s.first_token_time if s.first_token_time is not None else end) - s.arrival
            for s in seen if in_window(s, end)]


def queue_wait_values(seen: List[Seen], end: float) -> List[float]:
    """Admission minus arrival, censored at the window's end likewise."""
    return [(s.prefill_start if s.prefill_start is not None else end) - s.arrival
            for s in seen if in_window(s, end)]


def tpot_values(seen: List[Seen], end: float) -> List[float]:
    """Each decoding request's mean gap between output tokens in the window.

    The span runs from the request's first token, or from the window's
    start where that token came before it, to its last token if it finished
    in the window, or else to the window's end: a request still waiting for
    its next token when the window closes is censored there, so a stall that
    is still running counts.  The span is divided by the tokens made inside
    it after its start (at least one where the request is unfinished: its
    pending gap).  A request that finished with one token in the window
    decoded nothing and is left out; so is one that was not yet decoding."""
    out = []
    for s in seen:
        started_before = s.tokens_at_open > 0
        if s.in_window == 0 and not (started_before and s.finish_time is None):
            continue  # not decoding in the window
        start = 0.0 if started_before else s.first_in_window
        gaps = s.in_window if started_before else s.in_window - 1
        if s.finish_time is not None:
            if gaps < 1:
                continue
            out.append((s.last_in_window - start) / gaps)
        else:
            out.append((end - start) / max(gaps, 1))
    return out


def window_tokens(seen: List[Seen]) -> int:
    """Output tokens produced inside the window."""
    return sum(s.in_window for s in seen)
