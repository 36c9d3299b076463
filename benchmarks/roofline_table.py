"""Render the §Roofline / §Dry-run tables of EXPERIMENTS.md from
results/dryrun/*.json.

  PYTHONPATH=src python -m benchmarks.roofline_table [--mesh 1pod|2pod] [--tag ""]
"""
from __future__ import annotations

import argparse
import glob
import json
from pathlib import Path

SHAPE_ORDER = {"train_4k": 0, "prefill_32k": 1, "decode_32k": 2, "long_500k": 3}


def load(mesh: str, tag: str = "", d: str = "results/dryrun"):
    rows = []
    for f in glob.glob(f"{d}/*_{mesh}{tag}.json"):
        stem = Path(f).stem
        if tag == "" and (stem.count("_m") or "_opt" in stem):
            # skip tagged variants when rendering the baseline table
            if not stem.endswith(mesh):
                continue
        rows.append(json.load(open(f)))
    rows.sort(key=lambda r: (r["arch"], SHAPE_ORDER.get(r["shape"], 9)))
    return rows


def fmt(rows, *, show_mem=True) -> str:
    out = [
        "| arch | shape | t_compute (s) | t_memory (s) | t_collective (s) | bottleneck "
        "| MODEL_FLOPs/chip | useful ratio | HBM GB/chip | compile s |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if not r.get("ok"):
            why = r.get("skipped", r.get("error", ""))[:60]
            out.append(f"| {r['arch']} | {r['shape']} | — | — | — | SKIP ({why}) | — | — | — | — |")
            continue
        t = r["roofline"]
        mem = r.get("memory_analysis", {})
        gb = (mem.get("temp_size_in_bytes", 0) + mem.get("argument_size_in_bytes", 0)) / 1e9
        ur = r.get("useful_compute_ratio")
        out.append(
            f"| {r['arch']} | {r['shape']} | {t['t_compute_s']:.2e} | {t['t_memory_s']:.2e} "
            f"| {t['t_collective_s']:.2e} | **{t['bottleneck']}** "
            f"| {r['model_flops_per_chip']:.2e} | {ur:.2f} | {gb:.1f} | {r['compile_s']:.0f} |"
        )
    return "\n".join(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="1pod", choices=["1pod", "2pod"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--dir", default="results/dryrun",
                    help="results/dryrun_baseline for the pre-§Perf snapshot")
    args = ap.parse_args()
    rows = load(args.mesh, args.tag, args.dir)
    print(fmt(rows))
    n_ok = sum(1 for r in rows if r.get("ok"))
    n_skip = sum(1 for r in rows if "skipped" in r)
    print(f"\n{n_ok} compiled OK, {n_skip} documented skips, "
          f"{len(rows) - n_ok - n_skip} failures")


if __name__ == "__main__":
    main()
