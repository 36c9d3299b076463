#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in BENCHMARK.json) names a configuration,
``bench/configs/<config>.json``, and a traffic mix,
``bench/traffic/<traffic>.json``.  Set-up makes the merged grid's weights
on the device from the seed, builds the program's server and compiles (or
fetches from JAX's persistent cache, ``.jax_cache`` in the checkout unless
``JAX_COMPILATION_CACHE_DIR`` is set) every program the window runs; it is
timed from the moment the chip is found, so Python's start, ``import jax``
and the TPU runtime's start, which no change to the program can move and
which vary by seconds from run to run, are logged apart.  The
window then drives the traffic through ``AsyncEngine`` for ``--seconds``
and times it on the client's side.  With ``--trace 1`` the middle of the
window is traced by the profiler and the per-layer metrics are read from
the trace (``bench/metrics``); with ``--trace 0`` nothing is instrumented
and the end-to-end metrics are reported.  Afterwards a sample of the served
requests is compared with the plain reference (``bench/check.py``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), and
``checks``: each number compared, with its limit.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                                 # noqa: E402
import gc                                                       # noqa: E402
import json                                                     # noqa: E402
import math                                                     # noqa: E402
import os                                                       # noqa: E402
import shutil                                                   # noqa: E402
import sys                                                      # noqa: E402
from pathlib import Path                                        # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# run as a script, sys.path[0] is bench/ itself; import the package instead
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)
elif str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoAccelerator(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(BENCHMARK.json, the cell, its configuration, its traffic mix)."""
    from bench import model, traffic

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    return spec, cell, model.load(cell["config"]), traffic.load(cell["traffic"])


def cell_metrics(spec: dict, cell: str, section: str) -> dict[str, str]:
    """name -> unit of the cell's metrics in ``section``."""
    return {m["name"]: m["unit"] for m in spec[section]
            if cell in m.get("workloads", [cell])}


def accelerator(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoAccelerator(f"{len(devices)} chips, the cell needs {chips}")
    return devices[:chips]


def enable_compile_cache() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    # cache every program, however quick to compile, so that a second run
    # of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileCounter:
    """Programs lowered (compiled or fetched from the cache) while on."""

    def __init__(self):
        import jax

        self.n = 0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.n += 1

    def stop(self) -> int:
        self._jax.monitoring.unregister_event_duration_listener(self._on)
        return self.n


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _layer_metrics(names, probe, logdir, d, serving, devices):
    from bench import layers, peaks, trace_reduce as tr

    trace = tr.extract(tr.find_xplane(logdir), tr.load_table())
    lo, hi = tr.window(trace)
    ctx = layers.LayerContext(
        model=d, slots=serving["slots_per_instance"],
        peaks=peaks.peaks(devices[0].device_kind), chips=len(devices),
        window_s=(hi - lo) * 1e-9, busy_s=tr.busy_ns(trace) * 1e-9,
        program_s={k: v * 1e-9 for k, v in tr.program_ns(trace).items()},
        counters=probe.delta(),
        queue_waits_s=[a - due for a, due in probe.admits
                       if probe.inside(a) and not math.isnan(due)],
        decode_steps=[s for t, steps in probe.decode if probe.inside(t)
                      for s in steps],
        prefill=[(n, c) for t, n, c in probe.prefill if probe.inside(t)])
    values = layers.read(names, ctx)
    progs = [[f"program:{k}", v] for k, v in ctx.program_s.items()]
    ops = [[f"op:{k}", v * 1e-9] for k, v in tr.leaf_ops(trace).items()]
    breakdown = {
        "device_ops": (progs + ops)[:10],
        "idle_gaps": [[label, ns * 1e-9] for label, ns in tr.idle_gaps(trace)],
    }
    log(f"traced window {ctx.window_s:.3f} s, device busy {ctx.busy_s:.3f} s, "
        f"programs {json.dumps(ctx.program_s)}, counters "
        f"{json.dumps(ctx.counters)}")
    return values, breakdown, ctx


def serve_window(name: str, cfg: dict, spec: dict, *, seed: int,
                 seconds: float, trace: bool, devices, t_start: float,
                 logdir: str | None = None) -> dict:
    """Set-up and the measured window of one run.  Returns what the run
    reports from them; the program's state is freed on return."""
    import jax

    from bench import drive, model, program, traffic, weights
    from bench.probe import Probe

    t_enter = time.perf_counter()
    d, serving = model.dense(cfg), cfg["serving"]
    pcfg = program.model_config(d, name)
    grid = jax.block_until_ready(weights.make_grid(d, seed))
    t_weights = time.perf_counter()
    program.check_layout(pcfg, grid)
    server = program.build_server(pcfg, grid, serving)
    del grid
    t_build = time.perf_counter()
    warm_s = program.warm(server)
    plan = traffic.plan(spec, instances=d.instances, vocab=d.vocab,
                        seconds=seconds, seed=seed)
    probe = None
    if trace:
        probe = Probe(server)
        probe.install()
        shutil.rmtree(logdir, ignore_errors=True)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s: program imports "
        f"{t_enter - t_start:.3f} s, weights {t_weights - t_enter:.3f} s, "
        f"server {t_build - t_weights:.3f} s, warm-up {warm_s:.3f} s")

    counter = CompileCounter()
    window = drive.run(server, plan, seconds, probe=probe, logdir=logdir)
    compiles = counter.stop()
    out = drive.end_to_end(window)
    log(f"compilations in the window: {compiles}")
    log(f"requests due {out['attempted']}, finished {out['finished']}, "
        f"failed {out['failed']}; tokens received {out['tokens']}; "
        f"ITL samples {out['itl_samples']}; sends late by p99 "
        f"{out['send_late_p99_s'] * 1e3:.3f} ms, max "
        f"{out['send_late_max_s'] * 1e3:.3f} ms")
    log("client-side: " + json.dumps(out))
    mem = memory_peak(devices)
    # the program's state goes before anything else runs on the device
    del server
    if probe is not None:
        probe.server = None
    gc.collect()
    return {"model": d, "window": window, "e2e": out, "setup_s": setup_s,
            "memory_peak_bytes": mem, "probe": probe, "compiles": compiles}


def reference_check(cfg: dict, run: dict, seed: int,
                    control: bool = False) -> dict:
    """The served-token gaps of the run's sample (``bench/check.py``)."""
    from bench import check, weights

    t0 = time.perf_counter()
    w = run["window"]
    samples = check.sample(w.records, w.t_close, seed)
    grid = weights.make_grid(run["model"], seed)
    gaps = check.served_gaps(cfg["architecture"], run["model"], grid, samples,
                             cfg["serving"]["max_context"], control=control)
    del grid
    log(f"reference over {gaps['requests']} requests, {gaps['tokens']} served "
        f"tokens in {time.perf_counter() - t0:.3f} s")
    return gaps


def run_cell(name: str, cfg: dict, spec: dict, *, seed: int, seconds: float,
             trace: bool, devices, e2e: dict, per_layer: dict,
             t_start: float, logdir: str | None = None) -> dict:
    """One run of a cell; returns the result object."""
    from bench import check

    if trace:
        logdir = logdir or str(ROOT / ".bench_trace" / f"{name}.{seed}")
    run = serve_window(name, cfg, spec, seed=seed, seconds=seconds,
                       trace=trace, devices=devices, t_start=t_start,
                       logdir=logdir)
    out = run["e2e"]
    breakdown = None
    if trace:
        values, breakdown, ctx = _layer_metrics(
            list(per_layer), run["probe"], logdir, run["model"],
            cfg["serving"], devices)
        shutil.rmtree(logdir, ignore_errors=True)
        metrics = {k: {"value": v, "unit": per_layer[k]}
                   for k, v in values.items()}
    else:
        values = {"ttft_p90_ms": out["ttft_p90_s"] * 1e3,
                  "itl_p95_ms": out["itl_p95_s"] * 1e3,
                  "tokens_per_s": out["tokens_per_s"],
                  "setup_s": run["setup_s"]}
        metrics = {k: {"value": values[k], "unit": u} for k, u in e2e.items()
                   if k in values and not math.isnan(values[k])}

    gaps = reference_check(cfg, run, seed)
    limit = cfg["check"]["served_logit_gap"]
    correct = check.passes(gaps["served_gap"], gaps["requests"], limit)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": run["memory_peak_bytes"]}
    if trace:
        device.update(busy_s=ctx.busy_s, window_s=ctx.window_s)
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {"served_logit_gap": {"value": gaps["served_gap"],
                                             "limit": limit}}
    log(f"run took {time.perf_counter() - T_START:.3f} s")
    log(f"served_logit_gap {gaps['served_gap']!r} limit {limit!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec, cell, cfg, traffic_spec = load_cell(args.workload)
    try:
        devices = accelerator(cell["chips"])
    except NoAccelerator as e:
        log(f"error: {e}")
        return 3
    t_chip = time.perf_counter()
    log(f"chip found {t_chip - T_START:.3f} s after start")
    enable_compile_cache()
    result = run_cell(
        args.workload, cfg, traffic_spec, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), devices=devices,
        e2e=cell_metrics(spec, args.workload, "end_to_end"),
        per_layer=cell_metrics(spec, args.workload, "per_layer"),
        t_start=t_chip)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
