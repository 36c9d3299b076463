#!/usr/bin/env python3
"""Chip smoke test: serve qwen1.5-0.5b at its published widths on a TPU.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # four chips, one host

One chip: M=4 merged instances of qwen1.5-0.5b (random weights from
``--seed``, bf16) are served by ``MultiModelServer`` with 8 slots per
instance and ``max_context`` 1024.  Three requests per instance, with
prompts of a few hundred tokens (several chunked-prefill calls each),
decode greedily to ``max_new`` 32 at 8 decode steps per device call.
Checks:

* every Result is ``ok`` and ran to ``max_new`` (no EOS is configured,
  and no prompt reaches the context cap, so nothing else may stop it);
* NetFuse's claim, merged == per-instance: for one prompt per instance,
  the merged grid's prefill logits (bf16) match a plain f32 forward of
  that instance alone.  The reference reuses the grid's own f32 draw of
  the instance (``api.init_instance``), at highest matmul precision;
* the serving path itself: the logits of each prompt's first generated
  token, taken from a server (its chunked prefill, then the decode step
  its decode block runs, under its mesh context), match the same
  reference at the prompt's last position.

Four chips (``--chips 4``, this phase only): M=16 instances (about 19.8
GB of bf16 weights, more than one chip holds), built in place over a
data=4 mesh of the four chips and served there, against a one-chip M=4
run of instances 0-3 in the same process: prefill logits and the
serving path's first-token logits per instance must agree within the
tolerance; greedy-token agreement is printed, not gated.

Timings and memory printed on the way are informal readings, not
benchmark metrics.  The last line of stdout is the JSON result; the
script exits non-zero without it when JAX finds no TPU or any check
fails.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ARCH = "qwen1.5-0.5b"
# Relative L2 error of the bf16 merged grid's logits against the f32
# reference.  bf16 weights and activations carry 8 mantissa bits
# (relative rounding 2^-9 per value), and the error compounds over 24
# layers; 5e-2 leaves that room, while a wrong or mixed-up instance gives
# uncorrelated logits, a relative error near 1.4.
LOGITS_RTOL = 5e-2


def _src_on_path() -> None:
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "src"))


class CheckFailed(RuntimeError):
    pass


def require(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def make_requests(m: int, vocab: int, *, per_instance: int, lo: int, hi: int,
                  seed: int) -> list[tuple[int, list[int]]]:
    """(instance, prompt) pairs; instance i's prompts do not depend on M."""
    import numpy as np

    out = []
    for i in range(m):
        rng = np.random.default_rng([seed, i])
        for _ in range(per_instance):
            n = int(rng.integers(lo, hi + 1))
            out.append((i, rng.integers(1, vocab, size=n).tolist()))
    return out


def serve_phase(cfg, grid, requests, *, slots: int, max_context: int,
                max_new: int, decode_steps: int, mesh=None) -> dict:
    """Serve ``requests`` twice through one server: the first wave
    compiles every shape, the second is steady.  Raises
    ``CheckFailed`` when a Result is not ok or stopped before
    ``max_new``."""
    from repro.serving import MultiModelServer, Request

    server = MultiModelServer(cfg, grid, slots_per_instance=slots,
                              max_context=max_context,
                              decode_steps=decode_steps, mesh=mesh)

    def wave():
        for inst, prompt in requests:
            server.submit(Request(instance=inst, prompt=prompt,
                                  max_new_tokens=max_new))
        t0 = time.perf_counter()
        res = sorted(server.run_until_drained(), key=lambda r: r.request_id)
        wall = time.perf_counter() - t0
        require(len(res) == len(requests),
                f"{len(res)} results for {len(requests)} requests")
        for r in res:
            require(r.status == "ok",
                    f"request {r.request_id}: {r.status} ({r.error})")
            require(len(r.tokens) == max_new or r.finish_reason == "stop",
                    f"request {r.request_id} stopped after {len(r.tokens)} "
                    f"tokens ({r.finish_reason})")
        return [r.tokens for r in res], wall

    first, first_s = wave()
    steady, steady_s = wave()
    return {
        "tokens": steady,
        "first_wave_s": first_s,
        "steady_s": steady_s,
        "steady_tok_s": sum(map(len, steady)) / steady_s,
        "waves_agree": first == steady,
        "prefill_calls": server.prefill.device_calls,
    }


def first_token_logits(cfg, grid, tokens, *, max_context: int, mesh=None):
    """The serving path's f32 logits (n, V) of the first generated token
    for prompt i = ``tokens[i]`` ((n, L) int32) sent to instance i.

    A one-slot ``MultiModelServer`` prefills the n prompts together with
    its chunked prefill (equal lengths, so they finish in the same call).
    At its first decode dispatch the grid cache it holds is decoded once
    with ``api.decode_step``, the function its decode block runs, under
    its mesh context; then the dispatch goes on as usual."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.serving import MultiModelServer, Request

    server = MultiModelServer(cfg, grid, slots_per_instance=1,
                              max_context=max_context, mesh=mesh)
    decode = jax.jit(lambda p, c, t, q: api.decode_step(
        cfg, p, c, t[..., None], q)[0].astype(jnp.float32))
    step, got = server._step, []

    def probe(params, cache, tok, pos, key, alive, remaining, k):
        if not got:
            require(np.asarray(alive)[:len(tokens), 0].all(),
                    "the probe prompts did not finish prefill together")
            # to the host whole: slicing a sharded array on the device
            # would gather it onto one chip
            got.append(np.asarray(decode(params, cache, tok, pos))[
                :len(tokens), 0])
        return step(params, cache, tok, pos, key, alive, remaining, k)

    server._step = probe
    for i, prompt in enumerate(np.asarray(tokens).tolist()):
        server.submit(Request(instance=i, prompt=prompt, max_new_tokens=1))
    for r in server.run_until_drained():
        require(r.status == "ok", f"probe {r.request_id}: {r.status} "
                f"({r.error})")
    require(len(got) == 1, "the probe server never decoded")
    # the server's reference cycles would keep its cache on the device
    del server, step, probe
    gc.collect()
    return got[0]


def merged_logits(cfg, grid, tokens, *, mesh=None):
    """f32 prefill logits (M, 1, L, V) of the merged grid for one prompt
    per instance (``tokens``: (M, 1, L) int32)."""
    import jax
    import jax.numpy as jnp

    from repro import api
    from repro.launch.compat import mesh_context
    from repro.launch.shardings import default_serve_rules

    rules = default_serve_rules(mesh)
    fwd = lambda p, t: api.train_logits(
        cfg, p, {"tokens": t}, remat=False).astype(jnp.float32)
    with mesh_context(mesh, rules):
        if mesh is not None:
            from jax.sharding import NamedSharding

            tokens = jax.device_put(tokens, NamedSharding(
                mesh, rules.spec(("instances", "batch", None), tokens.shape)))
        return jax.jit(fwd)(grid, tokens)


def reference_logits(cfg, key, i: int, tokens):
    """Plain f32 forward of instance ``i`` alone, at highest matmul
    precision (``tokens``: (1, 1, L))."""
    import jax
    import jax.numpy as jnp

    from repro import api

    one = cfg.with_(num_instances=1, dtype="float32", param_dtype="float32")
    # the grid's own f32 draw of instance i, as api.init_instances makes it
    params = api.init_instance(cfg.with_(param_dtype="float32"), key, i)
    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda p, t: api.train_logits(
            one, p, {"tokens": t}, remat=False).astype(jnp.float32))(
                params, tokens)
    return out


def rel_err(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def check_vs_reference(cfg, grid, key, *, prompt_len: int, seed: int,
                       max_context: int) -> None:
    """Per-instance relative error against the f32 reference of the
    merged prefill logits and of the serving path's first-token logits;
    raises ``CheckFailed`` beyond LOGITS_RTOL."""
    import jax.numpy as jnp
    import numpy as np

    m = cfg.num_instances
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size,
                                      size=(m, 1, prompt_len)), jnp.int32)
    got = np.asarray(merged_logits(cfg, grid, tokens))
    served = first_token_logits(cfg, grid, tokens[:, 0],
                                max_context=max_context)
    errs = []
    for i in range(m):
        want = np.asarray(reference_logits(cfg, key, i, tokens[i:i + 1])[0])
        err = rel_err(got[i], want)
        top1 = float(np.mean(np.argmax(got[i], -1) == np.argmax(want, -1)))
        print(f"instance {i}: merged bf16 vs f32 reference logits rel err "
              f"{err:.3e} (tolerance {LOGITS_RTOL:g}), top-1 agreement "
              f"{top1:.3f}")
        last = want[0, -1]
        err_served = rel_err(served[i], last)
        print(f"instance {i}: served first-token logits vs f32 reference "
              f"rel err {err_served:.3e} (tolerance {LOGITS_RTOL:g}), same "
              f"top-1: {np.argmax(served[i]) == np.argmax(last)}")
        errs += [err, err_served]
    require(max(errs) <= LOGITS_RTOL, f"logits rel errors {errs}")


def one_chip(cfg, key, *, seed: int, slots: int, max_context: int,
             max_new: int, decode_steps: int, per_instance: int,
             prompt_lo: int, prompt_hi: int, logits_len: int) -> None:
    import jax

    from repro import api

    t0 = time.perf_counter()
    grid = api.init_instances(cfg, key)
    jax.block_until_ready(grid)
    print(f"merged grid of {cfg.num_instances} instances built in "
          f"{time.perf_counter() - t0:.1f} s (compile included)")
    check_vs_reference(cfg, grid, key, prompt_len=logits_len, seed=seed,
                       max_context=max_context)
    reqs = make_requests(cfg.num_instances, cfg.vocab_size,
                         per_instance=per_instance, lo=prompt_lo,
                         hi=prompt_hi, seed=seed)
    out = serve_phase(cfg, grid, reqs, slots=slots, max_context=max_context,
                      max_new=max_new, decode_steps=decode_steps)
    print(f"served {len(reqs)} requests x {max_new} tokens, all ok; "
          f"{out['prefill_calls']} prefill chunk calls over two waves")
    print(f"informal: first wave (compiles included) {out['first_wave_s']:.1f}"
          f" s; steady wave {out['steady_s']:.2f} s = "
          f"{out['steady_tok_s']:.1f} tok/s; waves agree: "
          f"{out['waves_agree']}")


def four_chips(cfg, key, devices, *, seed: int, slots: int, max_context: int,
               max_new: int, decode_steps: int, per_instance: int,
               prompt_lo: int, prompt_hi: int, logits_len: int,
               m_small: int = 4, m_mesh: int = 16) -> None:
    """M=m_mesh over a data=len(devices) mesh vs a one-device M=m_small
    run of instances 0..m_small-1."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.launch.compat import make_host_mesh

    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size,
                                      size=(m_mesh, 1, logits_len)), jnp.int32)
    kw = dict(slots=slots, max_context=max_context, max_new=max_new,
              decode_steps=decode_steps)

    small = cfg.with_(num_instances=m_small)
    grid = api.init_instances(small, key)
    want = np.asarray(merged_logits(small, grid, tokens[:m_small]))
    want_served = first_token_logits(small, grid, tokens[:m_small, 0],
                                     max_context=max_context)
    reqs_small = make_requests(m_small, cfg.vocab_size,
                               per_instance=per_instance, lo=prompt_lo,
                               hi=prompt_hi, seed=seed)
    served_small = serve_phase(small, grid, reqs_small, **kw)
    print(f"one chip, M={m_small}: served {len(reqs_small)} requests, all ok")
    # the server's reference cycles keep its cache and the M=4 grid on
    # chip 0 until the cycle collector runs
    del grid
    gc.collect()

    big = cfg.with_(num_instances=m_mesh)
    mesh = make_host_mesh((len(devices), 1), devices=devices)
    t0 = time.perf_counter()
    grid = api.init_instances(big, key, mesh=mesh)
    jax.block_until_ready(grid)
    share = grid["embed"].addressable_shards[0].data.shape[0]
    require(all(leaf.sharding.device_set == set(devices)
                for leaf in jax.tree.leaves(grid)), "grid not on every chip")
    require(share == m_mesh // len(devices), f"{share} instances per chip")
    print(f"mesh {dict(mesh.shape)}: merged grid of {m_mesh} instances "
          f"built in {time.perf_counter() - t0:.1f} s, {share} per chip")
    # to the host shard by shard: slicing the sharded array on the device
    # would gather it onto one chip
    got = np.asarray(merged_logits(big, grid, tokens, mesh=mesh))[:m_small]
    got_served = first_token_logits(big, grid, tokens[:m_small, 0],
                                    max_context=max_context, mesh=mesh)
    errs = []
    for i in range(m_small):
        errs += [rel_err(got[i], want[i]),
                 rel_err(got_served[i], want_served[i])]
        print(f"instance {i}: M={m_mesh} on the mesh vs M={m_small} on one "
              f"chip, prefill logits rel err {errs[-2]:.3e}, served "
              f"first-token logits rel err {errs[-1]:.3e} (tolerance "
              f"{LOGITS_RTOL:g}), same first token: "
              f"{np.argmax(got_served[i]) == np.argmax(want_served[i])}")
    require(max(errs) <= LOGITS_RTOL, f"logits rel errors {errs}")
    reqs_big = make_requests(m_mesh, cfg.vocab_size,
                             per_instance=per_instance, lo=prompt_lo,
                             hi=prompt_hi, seed=seed)
    served_big = serve_phase(big, grid, reqs_big, mesh=mesh, **kw)
    first = served_big["tokens"][:len(reqs_small)]
    same = sum(a == b for a, b in zip(first, served_small["tokens"]))
    print(f"mesh, M={m_mesh}: served {len(reqs_big)} requests, all ok; "
          f"greedy streams of instances 0-{m_small - 1} identical to the "
          f"one-chip run: {same}/{len(reqs_small)} (not gated)")
    print(f"informal: mesh steady wave {served_big['steady_s']:.2f} s = "
          f"{served_big['steady_tok_s']:.1f} tok/s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    _src_on_path()
    import jax

    from repro.configs import registry
    from repro.launch.compat import enable_compile_cache

    devices = jax.devices()
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX sees {len(devices)} {devices[0].platform} "
              f"device(s)", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} TPUs, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}")
    print(f"device: {kind} x {len(devices)}")

    cfg = registry.serving_config(ARCH)
    key = jax.random.PRNGKey(args.seed)
    kw = dict(seed=args.seed, slots=8, max_context=1024, max_new=32,
              decode_steps=8, per_instance=3, prompt_lo=200, prompt_hi=400,
              logits_len=256)
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(cfg.with_(num_instances=4), key, **kw)
        used = devices[:1]
    else:
        used = devices[:4]
        four_chips(cfg, key, used, **kw)
    for d in used:
        stats = d.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        print(f"informal: {d} peak HBM "
              + (f"{peak / 2**30:.2f} GiB" if peak else "not reported"))
    print(f"informal: wall {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
