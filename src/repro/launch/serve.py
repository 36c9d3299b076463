"""Serving launcher: NetFuse-merged multi-model serving demo/driver.

Trains nothing — initializes (or restores) M fine-tuned instances,
merges them (the paper's offline merge step, timed), and serves batched
requests from per-instance queues through the fused decode.  Every
servable family works (dense / moe / vlm / audio / ssm / hybrid);
admission policy, sampling and the prefill chunk/budget are flags.
Per-instance throughput/latency/queue metrics are reported at the end.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \\
      --smoke --num-instances 4 --requests 32 --policy token-budget

Mesh-parametric serving: ``--devices N`` forces N host-platform devices
(must be consumed before jax initializes) and ``--mesh-shape DxT``
serves the (M, B) grid under a (data=D, model=T) mesh — slot surgery,
prefill, decode and sampling all run sharded (engine ``mesh=``).

Async frontend (DESIGN.md §6.4): ``--stream`` drives the same synthetic
workload through the ``AsyncEngine`` as concurrent clients, printing
tokens as each fused step lands; ``--http PORT`` serves the engine over
HTTP (OpenAI-style ``POST /v1/completions`` with SSE streaming, ``GET
/metrics``) until interrupted, then drains gracefully and prints the
metrics table (now including TTFT/ITL p50/p95/p99 tails).
"""
from __future__ import annotations

import argparse
import asyncio
import sys
import time

# --devices must win before the first jax backend init (the device
# count locks there; importing jax below is still safe)
from repro.launch.compat import (
    enable_compile_cache, force_host_devices_from_argv, mesh_from_args)

force_host_devices_from_argv(sys.argv)

import numpy as np
import jax

from repro import api
from repro.configs import registry
from repro.serving import MultiModelServer, Request, SERVABLE_FAMILIES
from repro.serving.scheduler import POLICIES


def _supervise(engine, args):
    """Wrap the engine in a Supervisor when the run asked for fault
    tolerance (--fault-plan and/or --watchdog-ms); returns it or None."""
    if not (args.fault_plan or args.watchdog_ms > 0):
        return None
    from repro.serving import Supervisor

    sup = Supervisor(
        engine,
        watchdog_s=(args.watchdog_ms / 1e3) if args.watchdog_ms > 0 else None,
        max_restarts=args.max_restarts, seed=args.seed,
    )
    sup.start()
    return sup


def _print_obs(server) -> None:
    """End-of-run per-tenant attribution + SLO tables (DESIGN.md §6.9);
    silent when neither accounting nor an SLO config is active."""
    acct = server.accounting
    if acct.enabled or acct.settled_s > 0:
        print(acct.format_table())
    rep = server.metrics.slo_report()
    if rep.get("configured"):
        cfg = rep["config"]
        lines = [f"SLO (target {cfg['target']:.0%}"
                 + (f", ttft<={cfg['ttft_ms']:g}ms" if cfg["ttft_ms"] else "")
                 + (f", itl<={cfg['itl_ms']:g}ms" if cfg["itl_ms"] else "")
                 + ")"]
        for i, inst in enumerate(rep["instances"]):
            objs = "  ".join(
                f"{name}: {o['bad_frac']:.2%} bad, "
                f"burn {o['burn_rate']:.2f}, "
                f"budget {o['budget_remaining']:.0%}"
                for name, o in inst["objectives"].items())
            lines.append(f"  inst {i} [{inst['state']:>8}]  {objs}")
        print("\n".join(lines))


def _print_recovery(sup) -> None:
    if sup is None:
        return
    s = sup.snapshot()
    print(f"supervision: {s['driver_restarts']} restart(s), "
          f"{s['watchdog_timeouts']} watchdog timeout(s), "
          f"{s['request_retries']} request requeue(s), "
          f"{s['tokens_replayed']} token(s) replayed"
          + (f", last recovery {s['last_recovery_s'] * 1e3:.1f} ms"
             if s["last_recovery_s"] is not None else ""))


async def _stream_clients(server, reqs, max_queue, args):
    """The --stream path: one async client per request, tokens printed
    as each fused engine step lands (the sync path's streams are
    bit-identical under greedy sampling, even across supervised driver
    crashes — replayed tokens are never re-printed)."""
    from repro.serving import AsyncEngine

    engine = AsyncEngine(server, max_queue_depth=max_queue)
    sup = _supervise(engine, args)

    async def client(r):
        stream = await engine.submit(r)
        async for tok in stream:
            print(f"  req {stream.request_id:>3} inst {r.instance} +{tok}")
        return await stream.result()

    results = await asyncio.gather(*(client(r) for r in reqs))
    await engine.aclose()
    _print_recovery(sup)
    return results


def _serve_http(server, args):
    """The --http path: expose the engine over HTTP until interrupted,
    then drain in-flight requests and print the metrics table."""
    from repro.serving import AsyncEngine, start_http_server

    async def run():
        engine = AsyncEngine(server, max_queue_depth=args.max_queue)
        sup = _supervise(engine, args)
        http = await start_http_server(engine, port=args.http)
        addr = http.sockets[0].getsockname()
        print(f"serving HTTP on {addr[0]}:{addr[1]} — "
              f"POST /v1/completions (model-0..model-{server.m - 1}, "
              f"prompt = token ids, \"stream\": true for SSE), GET /metrics")
        if sup is not None:
            print(f"supervised: watchdog="
                  f"{args.watchdog_ms or 'off'} ms, "
                  f"max_restarts={args.max_restarts}"
                  + (f", fault plan armed ({args.fault_plan})"
                     if args.fault_plan else ""))
        try:
            async with http:
                await http.serve_forever()
        except asyncio.CancelledError:
            pass
        finally:
            http.close()
            await http.wait_closed()
            await engine.aclose()          # graceful drain
            _print_recovery(sup)

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass
    print(server.metrics.format_table())
    _print_obs(server)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(registry.ASSIGNED))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--num-instances", type=int, default=4)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-context", type=int, default=128)
    ap.add_argument("--policy", choices=sorted(POLICIES), default="fifo")
    ap.add_argument("--chunk", type=int, default=32,
                    help="prefill chunk size (tokens per admission call)")
    ap.add_argument("--chunk-budget", type=int, default=4,
                    help="max prefill chunk calls interleaved per engine step")
    ap.add_argument("--lanes", type=int, default=4,
                    help="concurrent prefill lanes (requests mid-admission)")
    ap.add_argument("--no-tail-fold", action="store_true",
                    help="disable padded-final-chunk tail folding (two "
                         "compiled shapes + per-token tail calls, for A/B)")
    ap.add_argument("--decode-steps", type=int, default=1, metavar="K",
                    help="fuse K decode+sample steps into one device call "
                         "(multi-step decode, DESIGN.md §6.6; stop "
                         "handling is on-device, streams are bit-identical "
                         "to K=1 under greedy sampling)")
    ap.add_argument("--pallas-kernels", action="store_true",
                    help="route decode through the fused Pallas path "
                         "(decode-layer megakernel + fused greedy "
                         "sampling, DESIGN.md §6.7; interpret mode off "
                         "TPU, so expect launch-count wins, not "
                         "wall-clock, on CPU)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host-platform devices (0 = real devices)")
    ap.add_argument("--mesh-shape", default=None, metavar="DxT",
                    help="serve under a (data=D, model=T) mesh, e.g. 2x4; "
                         "default with --devices: all devices on data")
    ap.add_argument("--stream", action="store_true",
                    help="drive the workload through the AsyncEngine as "
                         "concurrent clients, printing tokens as they arrive")
    ap.add_argument("--http", type=int, default=0, metavar="PORT",
                    help="serve over HTTP on this port (POST /v1/completions "
                         "SSE + GET /metrics) until interrupted")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="per-instance queue bound for the async frontend "
                         "(0 = unbounded); full queues answer HTTP 429")
    ap.add_argument("--fault-plan", default=None, metavar="JSON",
                    help="deterministic fault plan (DESIGN.md §6.8): a "
                         "path to a JSON file or inline JSON, e.g. "
                         "'{\"seed\": 0, \"faults\": [{\"site\": "
                         "\"driver\", \"at_call\": 3}]}'; armed for the "
                         "whole run — with --stream/--http a Supervisor "
                         "recovers the driver")
    ap.add_argument("--watchdog-ms", type=float, default=0.0,
                    help="supervised per-device-step deadline in ms for "
                         "the async paths (0 = no watchdog); steps that "
                         "overrun are treated as stalls and recovered")
    ap.add_argument("--max-restarts", type=int, default=5,
                    help="supervisor restart budget before giving up")
    ap.add_argument("--trace-out", default=None, metavar="TRACE.json",
                    help="capture a step trace of the run and write it as "
                         "Chrome-trace JSON (Perfetto / chrome://tracing); "
                         "with --http, toggle capture via POST "
                         "/debug/trace/start|stop instead")
    ap.add_argument("--slo-ttft-ms", type=float, default=0.0,
                    help="per-instance TTFT objective in ms (DESIGN.md "
                         "§6.9); 0 = no TTFT SLO. Error-budget burn is "
                         "reported per instance at end of run and on "
                         "GET /v1/slo")
    ap.add_argument("--slo-itl-ms", type=float, default=0.0,
                    help="per-instance inter-token-latency objective in "
                         "ms; 0 = no ITL SLO")
    ap.add_argument("--slo-target", type=float, default=0.99,
                    help="fraction of tokens that must meet each latency "
                         "objective (the SLO target, default 0.99)")
    ap.add_argument("--account", action="store_true",
                    help="per-tenant device-time attribution (DESIGN.md "
                         "§6.9): split every settled device call's wall "
                         "time across the instances occupying the grid; "
                         "prints the attribution table at end of run")
    ap.add_argument("--flight-dir", default=None, metavar="DIR",
                    help="arm the flight recorder: on driver crash, "
                         "watchdog fire, or quarantine, dump last-N trace "
                         "events + metrics + scheduler depths + SLO state "
                         "to DIR/flight-NNNN.json")
    args = ap.parse_args()
    enable_compile_cache()

    base = (registry.get_smoke_config(args.arch) if args.smoke
            else registry.serving_config(args.arch))
    if base.family not in SERVABLE_FAMILIES:
        raise SystemExit(f"family {base.family!r} is not servable")
    max_context = args.max_context
    if base.family == "hybrid":
        from repro.models import hybrid as H
        need = H.min_serving_context(base, args.max_new)
        if max_context < need:
            print(f"raising --max-context {max_context} -> {need} "
                  f"(hybrid meta tokens + SWA ring)")
            max_context = need
    if args.pallas_kernels:
        base = base.with_(use_pallas_kernels=True)
    m = args.num_instances
    cfg = base.with_(num_instances=m)

    mesh = mesh_from_args(args.devices, args.mesh_shape)
    if mesh is not None:
        print(f"serving mesh: {dict(mesh.shape)} over {mesh.size} devices")

    # M independently-"fine-tuned" instances (different random weights),
    # drawn straight into the merged grid (the paper's offline merge),
    # which on a mesh is built in place in its serving layout
    t0 = time.perf_counter()
    merged = api.init_instances(cfg, jax.random.PRNGKey(args.seed), mesh=mesh)
    jax.block_until_ready(merged)
    print(f"NetFuse merged grid of {m} instances built in "
          f"{(time.perf_counter()-t0)*1e3:.1f} ms")

    faults = None
    if args.fault_plan:
        from repro.serving import FaultInjector
        faults = FaultInjector.from_json(args.fault_plan)
        print(f"fault plan: {len(faults.plan)} spec(s), seed {faults.seed}")

    slo = None
    if args.slo_ttft_ms > 0 or args.slo_itl_ms > 0:
        from repro.serving import SLOConfig
        slo = SLOConfig(
            ttft_ms=args.slo_ttft_ms or None, itl_ms=args.slo_itl_ms or None,
            target=args.slo_target)
    flight = None
    if args.flight_dir:
        from repro.serving import FlightRecorder
        flight = FlightRecorder(args.flight_dir)

    server = MultiModelServer(
        cfg, merged, slots_per_instance=args.slots, max_context=max_context,
        temperature=args.temperature, top_k=args.top_k, seed=args.seed,
        scheduler=args.policy, prefill_chunk=args.chunk,
        prefill_lanes=args.lanes, chunk_budget=args.chunk_budget,
        tail_fold=not args.no_tail_fold, mesh=mesh,
        decode_steps=args.decode_steps, faults=faults,
        slo=slo, flight=flight,
    )
    if args.account:
        server.accounting.start()
    if faults is not None:
        faults.arm()
    if args.http:
        _serve_http(server, args)
        return

    rng = np.random.default_rng(args.seed)
    reqs = [
        Request(
            instance=i % m,
            prompt=rng.integers(1, cfg.vocab_size,
                                size=rng.integers(2, 8)).tolist(),
            max_new_tokens=args.max_new,
        )
        for i in range(args.requests)
    ]
    if args.trace_out:
        server.tracer.start()
    t0 = time.perf_counter()
    if args.stream:
        results = asyncio.run(
            _stream_clients(server, reqs, args.max_queue, args))
    else:
        for r in reqs:
            server.submit(r)
        results = server.run_until_drained()
    dt = time.perf_counter() - t0
    failed = [r for r in results if r.status == "error"]
    results = [r for r in results if r.status == "ok"]
    if args.trace_out:
        import json as _json
        server.tracer.stop()
        chrome = server.tracer.export_chrome()
        summ = server.tracer.summary()
        with open(args.trace_out, "w") as f:
            _json.dump(chrome, f)
        do = summ["dispatch_overhead_ms"]
        print(f"wrote {args.trace_out}: {len(chrome['traceEvents'])} events, "
              f"dispatch overhead p50/p95 "
              f"{do['p50']:.2f}/{do['p95']:.2f} ms, "
              f"grid occupancy {summ['mean_grid_occupancy']:.2f}"
              if do is not None else f"wrote {args.trace_out}")
    toks = sum(len(r.tokens) for r in results)
    snap = server.metrics.snapshot()
    print(f"served {len(results)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s, {snap['decode_steps']} fused decode steps "
          f"in {server.steps} device calls @ K={args.decode_steps}, "
          f"{snap['tokens_per_device_call']:.1f} tok/device-call, "
          f"policy={args.policy})")
    print(f"chunked prefill: chunk={server.prefill.chunk}, "
          f"tail_fold={'off' if args.no_tail_fold else 'on'}, "
          f"{server.prefill.compiled_shapes} compiled shape(s), "
          f"{server.prefill.device_calls} device calls for "
          f"{server.prefill.admitted} admissions, "
          f"{1e3 * server.metrics.admission_stall_s:.1f} ms admission stall")
    print(server.metrics.format_table())
    _print_obs(server)
    for r in results[:4]:
        print(f"  req {r.request_id} (instance {r.instance}): {r.tokens[:8]}...")
    if failed:
        for r in failed:
            print(f"  req {r.request_id} (instance {r.instance}) failed: "
                  f"{r.error}", file=sys.stderr)
        raise SystemExit(f"{len(failed)} request(s) ended in error")


if __name__ == "__main__":
    main()
