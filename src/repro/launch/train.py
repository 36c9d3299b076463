"""Training launcher.

CPU-scale real runs (smoke configs, synthetic data) AND the production
path: with --mesh the same train_step is pjit-compiled against the
sharding rules (on real hardware this is the entry point; on this
container use dryrun.py for the 512-device lowering).

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \\
      --smoke --steps 100 --batch 4 --seq 64
"""
from __future__ import annotations

import argparse
import time

import jax

from repro import api
from repro.configs import registry
from repro.data import pipeline
from repro.optim import cosine_with_warmup
from repro.train import loop as train_loop
from repro import checkpoint as ckpt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(registry.ASSIGNED))
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--num-instances", type=int, default=1,
                    help="NetFuse-merge M instances and train them together")
    ap.add_argument("--save", default=None, help="checkpoint dir")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", action="store_true",
                    help="run under the production sharding rules on the "
                         "available devices (pjit path; on this container "
                         "that is a 1x1 mesh — the 512-device lowering "
                         "lives in dryrun.py)")
    # size overrides (e.g. the ~100M CPU end-to-end run in EXPERIMENTS.md:
    #   --arch tinyllama-1.1b --smoke --layers 8 --d-model 768 --heads 12
    #   --kv-heads 4 --d-ff 2048 --vocab 32000 --steps 300)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--heads", type=int, default=0)
    ap.add_argument("--kv-heads", type=int, default=0)
    ap.add_argument("--d-ff", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=0)
    args = ap.parse_args()

    cfg = registry.get_smoke_config(args.arch) if args.smoke else registry.get_config(args.arch)
    cfg = cfg.with_(num_instances=args.num_instances)
    over = {k: v for k, v in (
        ("num_layers", args.layers), ("d_model", args.d_model),
        ("num_heads", args.heads), ("num_kv_heads", args.kv_heads),
        ("d_ff", args.d_ff), ("vocab_size", args.vocab),
    ) if v}
    if over:
        if "d_model" in over:
            over.setdefault("head_dim", 0)  # recompute from new dims
        cfg = cfg.with_(**over)
    print(f"arch={cfg.name} family={cfg.family} M={cfg.num_instances} "
          f"devices={jax.device_count()}")

    data = _data_for(cfg, args.seq)
    sched = cosine_with_warmup(args.lr, warmup_steps=args.steps // 10 + 1,
                               total_steps=args.steps)
    t0 = time.perf_counter()

    def run():
        return train_loop.train_loop(
            cfg, data, steps=args.steps, batch_size=args.batch,
            seq_len=args.seq, lr_schedule=sched,
            key=jax.random.PRNGKey(args.seed),
        )

    if args.mesh:
        from repro.launch.compat import make_host_mesh
        from repro.launch.shardings import train_rules
        mesh = make_host_mesh()
        print(f"mesh=(data={mesh.size}, model=1); rules active (constrain/shard_map paths engaged)")
        with jax.set_mesh(mesh), train_rules(mesh):
            state, losses = run()
    else:
        state, losses = run()
    print(f"done in {time.perf_counter()-t0:.1f}s; "
          f"loss {losses[0][1]:.3f} -> {losses[-1][1]:.3f}")
    if args.save:
        ckpt.save(args.save, state.params, extra={"arch": cfg.name, "steps": args.steps})
        print(f"saved params to {args.save}")


def _data_for(cfg, seq):
    class _D:
        def batch(self, step, batch_size, seq_len):
            return pipeline.make_batch(cfg, step, batch_size, seq_len, seed=17)
    return _D()


if __name__ == "__main__":
    main()
