"""What a per-layer metric reader is given: the run's requests as the window
saw them, the program's tracer spans and the device trace, with the
reductions several readers share.  Readers live one per metric in
``bench/metrics/<name>.py`` and return ``None`` where they find nothing to
read.
"""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench import flops, trace as tr
from bench.window import Seen

METRICS_DIR = Path(__file__).resolve().parent / "metrics"

# the engine's jitted programs, as their names appear in the trace
DECODE = "_paged_decode_step"
PREFILLS = ("_prefill", "_suffix")
SCATTER = "_quantize_scatter"


@dataclasses.dataclass
class Context:
    cfg: Dict
    peaks: Dict
    seen: List[Seen]
    end: float                      # window seconds
    spans: List[Tuple[str, float, float]] = dataclasses.field(default_factory=list)
    device: Optional[Dict] = None   # one device plane of bench.trace.load
    window_s: Optional[float] = None
    busy_s: Optional[float] = None

    # -- host side -----------------------------------------------------------
    def decode_work(self) -> Tuple[int, int, int]:
        """(tokens decoded in the window, the sum of the lengths they
        attended, decode steps the tracer saw complete)."""
        n = attended = 0
        for s in self.seen:
            # token t >= 1 came from a decode step that attended prompt + t
            for t in range(max(1, s.tokens - s.in_window), s.tokens):
                n += 1
                attended += s.prompt_len + t
        steps = sum(1 for name, _, _ in self.spans if name == "decode_step")
        return n, attended, steps

    def prefills(self) -> List[Seen]:
        """Requests whose prefill ran and finished inside the window."""
        return sorted((s for s in self.seen
                       if not s.preload and s.prefill_start is not None
                       and s.first_token_time is not None
                       and s.cached_tokens < s.prompt_len),
                      key=lambda s: s.prefill_start)

    # -- device side ---------------------------------------------------------
    def _modules(self, part: str):
        return tr.modules_named(self.device["modules"], part) if self.device else []

    def module_s(self, part: str) -> float:
        """Device seconds of the programs whose name contains ``part``."""
        return sum(d for _, _, d in self._modules(part)) * 1e-9

    def decode_device_s(self) -> Tuple[float, int]:
        m = self._modules(DECODE)
        return sum(d for _, _, d in m) * 1e-9, len(m)

    def decode_kernel_s(self, kernel: str) -> Tuple[float, int]:
        if not self.device:
            return 0.0, 0
        t, n = tr.kernel_ns(self.device["ops"], kernel, self._modules(DECODE))
        return t * 1e-9, n

    def prefill_device_s(self, n: int) -> float:
        """Device time of the first ``n`` prefill (full or suffix) programs
        and of the first ``n`` page scatters that store their K/V."""
        runs = sorted((m for part in PREFILLS for m in self._modules(part)),
                      key=lambda m: m[1])[:n]
        scat = self._modules(SCATTER)[:n]
        return sum(d for _, _, d in runs + scat) * 1e-9

    def decode_step_share(self) -> Optional[float]:
        """The decode steps' share of the chip's peak, %."""
        dev, dsteps = self.decode_device_s()
        ntok, attended, hsteps = self.decode_work()
        if not dsteps or not hsteps:
            return None
        f, b = flops.decode_steps(self.cfg, hsteps, ntok, attended)
        need = max(f / self.peaks["bf16_flops"], b / self.peaks["hbm_bytes_per_s"])
        return 100.0 * need / (dev / dsteps * hsteps)


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", METRICS_DIR / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def spans_of(tracer) -> List[Tuple[str, float, float]]:
    """Complete B/E spans of an ``obs.Tracer`` that ended inside the window."""
    out, open_ = [], {}
    for ph, name, ts, _, _, _ in tracer.events:
        if ph == "B":
            open_[name] = ts
        elif ph == "E" and name in open_:
            s = open_.pop(name)
            if ts > 0:
                out.append((name, s, ts))
    return out
