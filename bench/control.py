#!/usr/bin/env python3
"""Readings that the limit of ``correct`` is set from (not part of a run).

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 30

For each seed, in one process: a run's set-up and window at the cell's own
load, then, on the same sample of served requests, the program's widest
served-token gap (the lower reading) and the control's: the reference in
the next lower precision, float8 weights with bfloat16 activations, read as
the gap of the token it puts first (the upper reading).  One JSON line per
seed on stdout, each reading also put through the decision of ``correct``
(``check.passes``), so that the control is seen to come out not correct;
the limit goes between the largest program reading and the smallest
control reading.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

if __name__ == "__main__":
    # run as a script, sys.path[0] is bench/ itself: import the package
    sys.path[0] = str(Path(__file__).resolve().parents[1])
from bench.run import accelerator, enable_compile_cache, load_cell  # noqa: E402


def main(argv=None) -> int:
    from bench import check, run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    _, cell, cfg, spec = load_cell(args.workload)
    devices = accelerator(cell["chips"])
    enable_compile_cache()
    rows = []
    t_start = time.perf_counter()
    for seed in (int(s) for s in args.seeds.split(",")):
        r = run.serve_window(args.workload, cfg, spec, seed=seed,
                             seconds=args.seconds, trace=False,
                             devices=devices, t_start=t_start)
        gaps = run.reference_check(cfg, r, seed, control=True)
        limit = cfg["check"]["served_logit_gap"]
        row = {"seed": seed, **gaps, "finished": r["e2e"]["finished"],
               "correct": check.passes(gaps["served_gap"], gaps["requests"],
                                       limit),
               "control_correct": check.passes(gaps["control_gap"],
                                               gaps["requests"], limit)}
        rows.append(row)
        print(json.dumps(row), flush=True)
        t_start = time.perf_counter()
    print(json.dumps({
        "workload": args.workload,
        "program_max": max(r["served_gap"] for r in rows),
        "control_min": min(r["control_gap"] for r in rows),
        "limit": cfg["check"]["served_logit_gap"],
        "program_correct": all(r["correct"] for r in rows),
        "control_correct_any": any(r["control_correct"] for r in rows)}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
