import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Dry-run profiler for the §Perf loop: lower+compile ONE (arch × shape)
pair on the production mesh and print the top collective ops and the top
HBM-bytes ops from the optimized HLO.

  PYTHONPATH=src python -m repro.launch.profile_pair --arch qwen3-moe-30b-a3b \
      --shape train_4k [--tag _dp] [--multi-pod] [--num-instances 8]
"""
import argparse

import jax

from repro.configs.base import SHAPES
from repro.configs import registry
from repro.launch import hlo_analysis
from repro.launch import dryrun as D
from repro.launch.mesh import make_production_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=sorted(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--num-instances", type=int, default=1)
    ap.add_argument("--tag", default="")
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args()

    shape = SHAPES[args.shape]
    cfg = registry.config_for_shape(args.arch, shape, num_instances=args.num_instances)
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    rules, opt_rules, micro = D.rules_for(mesh, shape.kind, args.tag, arch=args.arch)
    with jax.set_mesh(mesh), rules:
        fn, fargs, in_sh = D.build_lowerable(
            cfg, shape, mesh, rules, opt_rules=opt_rules,
            micro_override=micro,
        )
        txt = jax.jit(fn, in_shardings=in_sh).lower(*fargs).compile().as_text()

    print(f"== {args.arch} x {args.shape} tag={args.tag!r} "
          f"m={args.num_instances} {'2pod' if args.multi_pod else '1pod'} ==")
    print("-- top collectives (moved bytes x trips) --")
    for label, by, cnt in hlo_analysis.breakdown_collectives(txt, args.top):
        print(f"  {by/1e9:11.2f} GB  x{cnt:<5d} {label}")
    print("-- top HBM-bytes ops --")
    for label, fl, by in hlo_analysis.breakdown(txt, args.top):
        print(f"  {by/1e9:11.2f} GB  {fl/1e12:8.2f} TF  {label}")


if __name__ == "__main__":
    main()
