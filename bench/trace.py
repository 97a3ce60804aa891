"""From a profiler trace to numbers: device busy time, idle gaps, time per
op name and per program, kernel time inside one program.

``load`` turns the ``.xplane.pb`` the JAX profiler writes into plain data,
``{"devices": [{"name", "ops", "modules"}], "host": [events]}``, each event
``[name, start_ns, dur_ns]``; the rest works on that form, which is also
what the recorded test fixture holds.  Device planes are the ``/device:TPU:n``
planes; ``ops`` is their ``XLA Ops`` line, ``modules`` their ``XLA Modules``
line (one event per program execution).
"""
from __future__ import annotations

import bisect
import glob
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, dur_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def load(profile_dir: str) -> Dict:
    from jax.profiler import ProfileData

    paths = glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {profile_dir}, found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    out = {"devices": [], "host": []}
    for plane in data.planes:
        lines = {line.name: [(short(e.name), float(e.start_ns), float(e.duration_ns))
                             for e in line.events] for line in plane.lines}
        if DEVICE_PLANE.match(plane.name):
            out["devices"].append({"name": plane.name,
                                   "ops": lines.get("XLA Ops", []),
                                   "modules": lines.get("XLA Modules", [])})
        elif plane.name == "/host:CPU":
            for evs in lines.values():
                out["host"].extend(evs)
    out["devices"].sort(key=lambda d: d["name"])
    return out


def short(name: str) -> str:
    """``%fusion.3 = f32[8] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(ops: Sequence[Event], start: float, end: float) -> List[Event]:
    """The parts of ``ops`` inside [start, end)."""
    out = []
    for name, s, d in ops:
        a, b = max(s, start), min(s + d, end)
        if b > a:
            out.append((name, a, b - a))
    return out


def busy_ns(ops: Sequence[Event]) -> float:
    """Time in which at least one op ran."""
    return sum(e - s for s, e in merge((s, s + d) for _, s, d in ops))


def idle_gaps(ops: Sequence[Event]) -> List[Tuple[float, float]]:
    """The gaps between busy stretches, longest first, as (start_ns, dur_ns)."""
    m = merge((s, s + d) for _, s, d in ops)
    gaps = [(m[i][1], m[i + 1][0] - m[i][1]) for i in range(len(m) - 1)]
    return sorted(gaps, key=lambda g: -g[1])


def op_family(name: str) -> str:
    """Op name without XLA's numeric suffix: ``fusion.123`` -> ``fusion``."""
    return re.sub(r"[.\-_]?\d+$", "", name)


# control flow whose body ops are events of their own
CONTAINERS = ("while", "conditional", "call")


def time_by_name(ops: Sequence[Event]) -> List[Tuple[str, float]]:
    """Seconds per op family, most first; loops and calls are left out, as
    the ops inside them are counted."""
    acc: Dict[str, float] = {}
    for name, _, d in ops:
        k = op_family(name)
        if k in CONTAINERS:
            continue
        acc[k] = acc.get(k, 0.0) + d
    return sorted(((k, v * 1e-9) for k, v in acc.items()), key=lambda kv: -kv[1])


def modules_named(modules: Sequence[Event], part: str) -> List[Event]:
    """Program executions whose name contains ``part``, in time order."""
    return sorted((m for m in modules if part in m[0]), key=lambda m: m[1])


def ops_within(ops: Sequence[Event], spans: Sequence[Event]) -> List[Event]:
    """Ops that start inside one of ``spans`` (sorted, disjoint)."""
    starts = [s for _, s, _ in spans]
    out = []
    for op in ops:
        i = bisect.bisect_right(starts, op[1]) - 1
        if i >= 0 and op[1] < spans[i][1] + spans[i][2]:
            out.append(op)
    return out


def kernel_ns(ops: Sequence[Event], kernel: str, spans: Sequence[Event]) -> Tuple[float, int]:
    """Total time and count of ``kernel`` ops inside ``spans``."""
    hits = [op for op in ops_within(ops, spans) if kernel in op[0]]
    return sum(d for _, _, d in hits), len(hits)


def label_gaps(gaps: Sequence[Tuple[float, float]], host: Sequence[Event],
               limit: int = 10) -> List[Tuple[str, float]]:
    """Name each of the longest gaps by the host event that covers most of
    it (the profiler puts host and device on one clock); ``idle`` where
    nothing on the host covers it."""
    out = []
    for start, dur in gaps[:limit]:
        best, cover = "idle", 0.0
        for name, s, d in host:
            c = min(start + dur, s + d) - max(start, s)
            if c > cover:
                best, cover = name, c
        out.append((best, dur * 1e-9))
    return out


def first_device(trace: Dict) -> Optional[Dict]:
    return trace["devices"][0] if trace["devices"] else None
