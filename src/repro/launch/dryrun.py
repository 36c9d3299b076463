import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
on the production meshes and extract the roofline terms.

The two lines above MUST run before any other import (jax locks the
device count at first init); 512 placeholder CPU devices back both the
16x16 single-pod mesh and the 2x16x16 multi-pod mesh.  Nothing here
allocates model memory — params, caches and batches are
ShapeDtypeStructs end to end.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all            # 1-pod baselines
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod

Results are cached as JSON under results/dryrun/.
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro import api
from repro.configs.base import SHAPES
from repro.configs import registry
from repro.launch import hlo_analysis
from repro.launch.mesh import chip_peaks, make_production_mesh, num_chips
from repro.launch.shardings import (
    batch_shardings, dp_train_rules, moe_dp_compute, moe_ep_shmap,
    moments_rules, replicated, serve_rules, train_rules, tree_shardings,
)
from repro.models.common import count_params
from repro.optim.adamw import OptState, adamw_update
from repro.train.loop import TrainState

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"

# Gradient-accumulation microbatch counts for the train_4k dry-run.
# Production-realistic for the big architectures: bounds the live
# activation stack (saved remat carries + logits) per chip.
TRAIN_MICROBATCHES = {
    "deepseek-67b": 8,
    "internvl2-26b": 8,
    "qwen3-moe-30b-a3b": 4,
    "hymba-1.5b": 4,
    "xlstm-1.3b": 4,
    "granite-3-2b": 4,
    "tinyllama-1.1b": 2,
    "olmoe-1b-7b": 2,
}


# ---------------------------------------------------------------------------
# model FLOPs (6·N·D train / 2·N·D inference; N = active params)
# ---------------------------------------------------------------------------


def active_params(cfg, n_total: int) -> int:
    """Active params per token (MoE: only top-k experts count)."""
    if cfg.family != "moe":
        return n_total
    per_expert = 3 * cfg.d_model * cfg.d_ff
    dense_part = n_total - cfg.num_layers * cfg.num_experts * per_expert * cfg.num_instances
    active = dense_part + cfg.num_layers * cfg.num_experts_per_tok * per_expert * cfg.num_instances
    return active


def model_flops(cfg, shape, n_total: int) -> float:
    n_act = active_params(cfg, n_total) / max(cfg.num_instances, 1)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_act * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_act * tokens
    tokens = shape.global_batch * 1  # decode: ONE new token
    return 2.0 * n_act * tokens


# ---------------------------------------------------------------------------
# lowering
# ---------------------------------------------------------------------------


def build_lowerable(cfg, shape, mesh, rules, *, opt_rules=None, micro_override=None):
    """Returns (fn, arg_specs, in_shardings).  opt_rules optionally shards
    optimizer moments differently from params (ZeRO-1 under dp rules)."""
    specs = api.input_specs(cfg, shape)
    params_abs = api.abstract_params(cfg)
    params_ax = api.axes(cfg)
    p_shard = tree_shardings(rules, params_ax, params_abs)

    if shape.kind == "train":
        mrules = opt_rules or rules
        opt_abs = OptState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            mu=jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32), params_abs),
            nu=jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32), params_abs),
        )
        opt_shard = OptState(
            step=replicated(rules),
            mu=tree_shardings(mrules, params_ax, opt_abs.mu),
            nu=tree_shardings(mrules, params_ax, opt_abs.nu),
        )
        state_abs = TrainState(params_abs, opt_abs)
        state_shard = TrainState(p_shard, opt_shard)
        b_shard = batch_shardings(rules, specs["batch"])

        micro = micro_override or TRAIN_MICROBATCHES.get(cfg.name, 1)

        def train_step(state, batch):
            params, opt = state

            def grads_of(b):
                return jax.value_and_grad(
                    lambda p: api.loss_fn(cfg, p, b), has_aux=True
                )(params)

            if micro > 1:
                def mb(i, carry):
                    lsum, gsum = carry
                    sub = jax.tree.map(
                        lambda x: x.reshape(
                            x.shape[0], micro, x.shape[1] // micro, *x.shape[2:]
                        )[:, i],
                        batch,
                    )
                    (l, _), g = grads_of(sub)
                    return lsum + l, jax.tree.map(jnp.add, gsum, g)

                zero = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params
                )
                loss, grads = jax.lax.fori_loop(
                    0, micro, mb, (jnp.float32(0.0), zero)
                )
                loss = loss / micro
                grads = jax.tree.map(lambda g: g / micro, grads)
            else:
                (loss, _), grads = grads_of(batch)
            new_params, new_opt, om = adamw_update(grads, opt, params, lr=1e-4)
            return TrainState(new_params, new_opt), {"loss": loss, **om}

        return train_step, (state_abs, specs["batch"]), (state_shard, b_shard)

    if shape.kind == "prefill":
        b_shard = batch_shardings(rules, specs["batch"])

        def prefill_step(params, batch):
            return api.prefill(cfg, params, batch)

        return prefill_step, (params_abs, specs["batch"]), (p_shard, b_shard)

    # decode
    cache_abs = specs["cache"]
    cache_ax = api.cache_axes(cfg)
    c_shard = tree_shardings(rules, cache_ax, cache_abs)
    tok_shard = batch_shardings(rules, specs["tokens"])
    pos_shard = batch_shardings(rules, specs["pos"])

    def serve_step(params, cache, tokens, pos):
        return api.decode_step(cfg, params, cache, tokens, pos)

    return (
        serve_step,
        (params_abs, cache_abs, specs["tokens"], specs["pos"]),
        (p_shard, c_shard, tok_shard, pos_shard),
    )


# §Perf production defaults ("ship the winners"): models whose params fit
# replicated (≲3 B) train pure-DP with ZeRO-1 moments — the TP=16 Megatron
# collectives dominate them otherwise (hymba/xlstm iterations: ~10-20x on
# the dominant roofline term).  Opt back into TP with --tag _tp.
DP_TRAIN_ARCHS = {
    "tinyllama-1.1b", "qwen1.5-0.5b", "granite-3-2b", "hymba-1.5b",
    "xlstm-1.3b",
}


def rules_for(mesh, kind: str, tag: str, arch: str | None = None):
    """(rules, opt_rules) for a §Perf variant tag.  Tags:
      ""       production default (train: DP+ZeRO-1 for DP_TRAIN_ARCHS,
               else TP+SP+FSDP; serve: TP+SP+context-sharded caches;
               MoE train: DP-compute dispatch)
      "_tp"    force the TP+SP+FSDP train baseline
      "_dp"    force pure-DP train
      "_moeep" MoE train: force expert-parallel einsums (paper baseline)
      "_moedp" MoE: force DP-compute dispatch (train default; serve opt-in)
    """
    opt_rules = None
    micro = None
    want_dp = tag.startswith("_dp") or (
        not tag.startswith("_tp") and arch in DP_TRAIN_ARCHS
    )
    if kind == "train" and want_dp:
        rules, opt_rules = dp_train_rules(mesh), moments_rules(mesh)
        micro = 1   # batch shards over all 256+ chips; no accumulation needed
    elif kind == "train":
        rules = train_rules(mesh)
    else:
        rules = serve_rules(mesh)
    # MoE dispatch-buffer compute placement: weight-gather (DP-compute)
    # wins for training shapes (dispatched activations ~K*cf x token
    # bytes >> expert weights); EP wins for decode (1-token buffers <<
    # weights).  serve rules therefore stay EP unless _moedp is forced.
    if tag.startswith("_moeps") or not tag.startswith(("_moeep", "_moedp")):
        # §Perf A4: canonical EP (expert-window dispatch + token psum)
        # dominates GSPMD-EP and weight-gather for training AND serving
        # (ablated: olmoe prefill 9.9->8.35 s, qwen3 decode 37.4->35.9 ms).
        rules = moe_ep_shmap(rules)
    elif tag.startswith("_moedp"):
        rules = moe_dp_compute(rules)
    return rules, opt_rules, micro


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            num_instances: int = 1, force: bool = False, tag: str = "") -> dict:
    shape = SHAPES[shape_name]
    mesh_tag = "2pod" if multi_pod else "1pod"
    inst_tag = f"_m{num_instances}" if num_instances != 1 else ""
    out_path = RESULTS_DIR / f"{arch}_{shape_name}_{mesh_tag}{inst_tag}{tag}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_tag,
        "num_instances": num_instances, "ok": False,
    }
    if not registry.supported(arch, shape):
        rec["skipped"] = "unsupported (see DESIGN.md §4)"
        _write(out_path, rec)
        return rec

    t0 = time.perf_counter()
    try:
        cfg = registry.config_for_shape(arch, shape, num_instances=num_instances)
        if "_c128" in tag:    # §Perf knob: mLSTM chunk length
            cfg = cfg.with_(mlstm_chunk=128)
        mesh = make_production_mesh(multi_pod=multi_pod)
        rules, opt_rules, micro = rules_for(mesh, shape.kind, tag, arch=arch)
        with jax.set_mesh(mesh), rules:
            fn, args, in_sh = build_lowerable(
                cfg, shape, mesh, rules, opt_rules=opt_rules,
                micro_override=micro,
            )
            lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower

        chips = num_chips(mesh)
        peaks = chip_peaks("TPU v5 lite")   # the production mesh's chip
        n_total = count_params(api.abstract_params(cfg))
        txt = compiled.as_text()
        analysis = hlo_analysis.analyze_hlo_text(txt)
        terms = hlo_analysis.roofline_terms(
            analysis, chips=chips,
            peak_flops=peaks.flops_bf16, hbm_bw=peaks.hbm_bw,
            ici_bw=peaks.ici_bw,
        )
        mf = model_flops(cfg, shape, n_total)
        # per-chip useful model flops for the useful-compute ratio
        mf_per_chip = mf / chips

        mem = {}
        try:
            ma = compiled.memory_analysis()
            for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                      "output_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes"):
                if hasattr(ma, k):
                    mem[k] = int(getattr(ma, k))
        except Exception as e:  # pragma: no cover
            mem["error"] = str(e)

        xla_ca = {}
        try:
            ca = compiled.cost_analysis()
            xla_ca = {k: float(ca[k]) for k in ("flops", "bytes accessed") if k in ca}
        except Exception as e:  # pragma: no cover
            xla_ca["error"] = str(e)

        rec.update({
            "ok": True,
            "family": cfg.family,
            "chips": chips,
            "params_total": int(n_total),
            "params_active": int(active_params(cfg, n_total)),
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "hlo": {k: analysis[k] for k in ("flops", "bytes", "collective_bytes")},
            "collectives": analysis["collectives"],
            "roofline": terms,
            "model_flops_global": mf,
            "model_flops_per_chip": mf_per_chip,
            "useful_compute_ratio": (
                mf_per_chip / analysis["flops"] if analysis["flops"] else None
            ),
            "memory_analysis": mem,
            "xla_cost_analysis_reference": xla_ca,
        })
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    _write(out_path, rec)
    return rec


def _write(path: Path, rec: dict):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(rec, indent=1, default=float))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(registry.ASSIGNED), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--all", action="store_true", help="all arch x shape pairs")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--num-instances", type=int, default=1)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="", help="rules variant tag (e.g. _dp)")
    args = ap.parse_args()

    pairs = (
        [(a, s) for a in sorted(registry.ASSIGNED) for s in
         ("train_4k", "prefill_32k", "decode_32k", "long_500k")]
        if args.all else [(args.arch, args.shape)]
    )
    for arch, shape in pairs:
        t0 = time.perf_counter()
        rec = run_one(
            arch, shape, multi_pod=args.multi_pod,
            num_instances=args.num_instances, force=args.force, tag=args.tag,
        )
        status = "OK " if rec.get("ok") else ("SKIP" if "skipped" in rec else "FAIL")
        extra = ""
        if rec.get("ok"):
            r = rec["roofline"]
            extra = (
                f"compute {r['t_compute_s']:.3e}s mem {r['t_memory_s']:.3e}s "
                f"coll {r['t_collective_s']:.3e}s -> {r['bottleneck']}"
            )
            # paper deliverable: print the compile artifacts' analyses
            print(f"  memory_analysis: {rec['memory_analysis']}")
            print(f"  cost_analysis(xla reference): {rec['xla_cost_analysis_reference']}")
        elif "error" in rec:
            extra = rec["error"][:200]
        print(f"[{status}] {arch} x {shape} ({'2pod' if args.multi_pod else '1pod'}) "
              f"{time.perf_counter()-t0:.1f}s {extra}", flush=True)


if __name__ == "__main__":
    main()
