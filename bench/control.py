"""Readings that set a cell's ``mean_logit_gap`` limit, on the chip.

    python3 bench/control.py --workload qwen3_8b.chat --seconds 51 --seeds 1 2 3 ...

For each seed, in one process: a run of the cell as the benchmark makes it
(``run.run``: the same set-up, window, load and check), then, over the
check's own sample of served tokens, the control: at every position of the
same prompts and served tokens, the reference computed with float8 E4M3
activations picks its own best token, and its gap is that token's distance
below the float32 reference's best.  The program's mean gap is the lower
reading (what sound runs give), the control's the upper one; the limit goes
between the two (``PERF.md``).  Both are judged by the harness's own
comparison (``run.judge``) against the cell's limit as it stands, and
printed with their verdicts and the run's result.  Only the first seed's
``setup_s`` is a set-up time: later seeds start in a process that has run.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import run as bench_run  # noqa: E402  (puts src on the path)


def readings(cell, seed: int, seconds: float, device) -> dict:
    import numpy as np

    from bench import reference

    kept: dict = {}
    result = bench_run.run(cell, seed=seed, seconds=seconds, trace=False, device=device,
                           keep=kept)
    cfg, p = cell.cfg, cell.params
    picked = bench_run.sample(kept["requests"], seed, p["check_tokens"], p["check_requests"])
    seqs, pos, toks = reference.teacher_forced(
        [(list(r.prompt), list(r.out_tokens)) for r in picked])
    ref, ctl = reference.logits_at(cfg, seed, seqs, pos, rows=p["check_requests"],
                                   length=cfg["serving"]["max_len"], control=True)
    program = reference.served_gaps(ref, toks)
    control = reference.served_gaps(ref, ctl.argmax(axis=-1))
    q = [0.5, 0.9, 0.99]
    limit = p["mean_logit_gap"]
    return {"seed": seed, "tokens": len(toks), "requests": len(seqs), "limit": limit,
            "program": float(program.mean()), "control": float(control.mean()),
            "program_correct": bench_run.judge(program, limit)["correct"],
            "control_correct": bench_run.judge(control, limit)["correct"],
            "program_max": float(program.max()), "control_max": float(control.max()),
            "program_q": np.quantile(program, q).tolist(),
            "control_q": np.quantile(control, q).tolist(),
            "program_agree": float(np.mean(program == 0)),
            "control_agree": float(np.mean(control == 0)),
            "run": result}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    cell = bench_run.Cell(args.workload)
    device = bench_run.device_info(cell.chips)
    bench_run.enable_compile_cache()
    for seed in args.seeds:
        print(json.dumps(dict(readings(cell, seed, args.seconds, device),
                              workload=args.workload)), flush=True)


if __name__ == "__main__":
    main()
