"""Find a cell's knee on the chip: one set-up and one preload, then a
staircase of windows, each at a higher arrival rate than the last, served
back to back in one ``Engine.serve``, each reporting whether the waiting
queue grew while it ran.

    python3 bench/sweep.py --workload qwen3_8b.chat --seconds 51 --seed 5 --rates 0.1 0.13 0.16

Each step's arrivals are the cell's traffic at that rate (the fixed
schedule, this seed's tokens), shifted to start where the step before
ended; the preload is the first step's alone.  Requests admitted in one step
go on decoding through the next, so every step after the first starts from
the load the one before left.  A step holds its rate if the queue of
arrived, unadmitted requests at its end is no longer than at its start (one
request of slack): the queue did not grow across it.  The knee is the
highest rate up to which every step held.  Cells run at fixed rates
written in ``bench/cells/<name>.json``; this is how those were found,
once, and is not part of a run.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import run as bench_run  # noqa: E402  (puts src on the path)


def queue_at(seen, t: float) -> int:
    return sum(1 for s in seen if not s.preload and s.arrival <= t
               and (s.prefill_start is None or s.prefill_start > t))


def staircase(mix, *, seed: int, seconds: float, rates, max_slots: int, vocab: int):
    """The planned requests of every step, one after another, with ids and
    first tokens renumbered so that no two requests share either."""
    import numpy as np

    from bench import traffic

    out = []
    for i, rate in enumerate(rates):
        m = mix if i == 0 else dict(mix, preload_share=0.0, backlog_share=0.0)
        for p in traffic.generate(m, seed=seed + i, seconds=seconds, rate=rate,
                                  max_slots=max_slots, vocab=vocab):
            out.append(dataclasses.replace(p, rid=len(out), arrival=p.arrival + i * seconds))
    firsts = np.random.default_rng(seed).permutation(vocab)
    for p, first in zip(out, firsts):
        p.prompt = [int(first)] + p.prompt[1:]
    return out


def steps(seen, seconds: float, rates):
    """One line per step: its arrivals, the queue at its start, middle and
    end, and whether every step up to it held its rate."""
    lines, held = [], True
    for i, rate in enumerate(rates):
        t0, mid, end = i * seconds, (i + 0.5) * seconds, (i + 1) * seconds
        q_start, q_mid, q_end = queue_at(seen, t0), queue_at(seen, mid), queue_at(seen, end)
        held = held and q_end <= q_start + 1
        lines.append({"rate": rate, "arrivals": sum(1 for s in seen
                                                    if not s.preload and t0 < s.arrival <= end),
                      "queue_start": q_start, "queue_mid": q_mid, "queue_end": q_end,
                      "held_so_far": held})
    return lines


def main() -> None:
    from bench import system, warmup
    from bench.window import Seen, WindowClock, WindowClosed
    from repro.obs import Clock

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True, help="length of each step")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    rates = sorted(args.rates)
    cell = bench_run.Cell(args.workload)
    bench_run.device_info(cell.chips)
    bench_run.enable_compile_cache()
    cfg = cell.cfg
    engine = system.make_engine(cfg, system.make_params(cfg, args.seed))
    system.serve(engine, cfg, system.requests(warmup.plan(cell.mix, cfg, args.seed)),
                 clock=Clock())
    plan = staircase(cell.mix, seed=args.seed, seconds=args.seconds, rates=rates,
                     max_slots=cfg["serving"]["max_slots"], vocab=cfg["vocab_size"])
    reqs = system.requests(plan)
    total = args.seconds * len(rates)
    clock = WindowClock([r for r, p in zip(reqs, plan) if p.preload], total, reqs)
    try:
        system.serve(engine, cfg, reqs, clock=clock)
        clock.drained()
    except WindowClosed:
        pass
    seen = [Seen.of(r, p.preload, clock) for r, p in zip(reqs, plan)]
    held = [s["rate"] for s in steps(seen, args.seconds, rates) if s["held_so_far"]]
    for line in steps(seen, args.seconds, rates):
        print(json.dumps(dict(line, workload=args.workload)), flush=True)
    print(json.dumps({"workload": args.workload, "knee": max(held) if held else None}))


if __name__ == "__main__":
    main()
