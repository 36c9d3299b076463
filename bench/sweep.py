#!/usr/bin/env python3
"""Offered-load sweep of an open-loop mix (not part of a run): finds the
knee, the highest rate at which the backlog does not grow over the window.

    python3 bench/sweep.py --config <config> --traffic <mix> --rates 1,2,4 \\
        --seconds 30 --seed 1

One process, one server, one window per rate.  The backlog at time t is
the number of requests due by t without a first token by t; it is printed
at each quarter of the window, with the client-side metrics.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

if __name__ == "__main__":
    # run as a script, sys.path[0] is bench/ itself: import the package
    sys.path[0] = str(Path(__file__).resolve().parents[1])
from bench.run import accelerator, enable_compile_cache  # noqa: E402


def backlog(window, frac: float) -> int:
    t = window.t_open + frac * (window.t_close - window.t_open)
    return sum(1 for r in window.records
               if r.due <= t and not (r.times and r.times[0] <= t))


def main(argv=None) -> int:
    from bench import drive, model, program, traffic, weights

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    accelerator(1)
    enable_compile_cache()
    cfg, spec = model.load(args.config), traffic.load(args.traffic)
    d = model.dense(cfg)
    grid = weights.make_grid(d, args.seed)
    server = program.build_server(program.model_config(d, args.config), grid,
                                  cfg["serving"])
    del grid
    program.warm(server)
    for rate in (float(r) for r in args.rates.split(",")):
        plan = traffic.plan(dict(spec, rate_rps=rate), instances=d.instances,
                            vocab=d.vocab, seconds=args.seconds, seed=args.seed)
        w = drive.run(server, plan, args.seconds)
        out = drive.end_to_end(w)
        row = {"rate_rps": rate, **{k: out[k] for k in (
            "attempted", "finished", "failed", "tokens_per_s", "ttft_p90_s",
            "itl_p95_s", "send_late_max_s")},
            "backlog_q1_q2_q3_end": [backlog(w, f) for f in (.25, .5, .75, 1)]}
        print(json.dumps(row), flush=True)
        gc.collect()
        time.sleep(1.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
