#!/usr/bin/env python3
"""Where a traced window's TTFT and device-idle time go, read from the
program's own step spans and request stamps.

The program's tracer (``src/repro/serving/obs/trace.py``), when it is on,
opens ``serve.*`` host spans inside every engine step on the profiler's
clock and stamps each request at its boundaries: ``enqueue`` (the
frontend's submit epoch), ``submit`` (in the scheduler's queue),
``admit`` (in a prefill lane), ``prefill_done`` (its slot scattered) and
``first_token`` (the host unroll emitted it).  This module reduces those
records over a traced window:

* the four TTFT phases, each over the requests whose phase ended in the
  window: inbox (enqueue -> submit), pending (submit -> admit), prefill
  (admit -> prefill_done) and first block (prefill_done -> first_token);
* ``step_idle_ms``: device-idle time in the window per ``serve.step``
  span that starts in it;
* ``host_gaps``: the longest device-idle gaps, each labelled by the
  innermost ``serve.*`` span covering its midpoint, or ``between steps``
  where it falls between two ``serve.step`` spans and no frontend span
  covers it (the executor hand-off); and the share of all idle time under
  each label, each instant under the innermost span covering it;
* the TTFT residual: the client's TTFT less the four phases, per request
  whose first token the client received in the window.

``bench/run.py`` does not start the program's tracer, so its result
lines carry none of these.  Run a cell's traced window with it here:

    python3 bench/phases.py --workload <cell> --seeds <n>[,<n>...] \\
        --seconds 51 --program-tracer 1,0

Each run prints one JSON line: the run's traced-path per-layer metrics
(as ``bench/run.py --trace 1`` reads them, plus ``device_idle_share``),
and, with the program's tracer on, the readings above.  The reductions
load no TPU library and are tested on synthetic and recorded traces.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
# run as a script, sys.path[0] is bench/ itself; import the package instead
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)

from bench import trace_reduce as tr                          # noqa: E402

# each TTFT phase: (first stamp, last stamp); a request counts in the
# window where its last stamp falls
PHASES = {"inbox": ("enqueue", "submit"),
          "pending": ("submit", "admit"),
          "prefill": ("admit", "prefill_done"),
          "first_block": ("prefill_done", "first_token")}
METRICS = {"inbox_wait_p90_ms": "inbox", "pending_wait_p90_ms": "pending",
           "prefill_wall_p90_ms": "prefill",
           "first_block_p90_ms": "first_block"}
BETWEEN = "between steps"


def serve_spans(path: str) -> list[list]:
    """``[name, start_ns, duration_ns]`` of the program's ``serve.*`` host
    spans in the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData

    return [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for ln in plane.lines for ev in ln.events
            if ev.name.startswith("serve.")]


def stamps(events, epoch: float) -> dict[int, dict[str, float]]:
    """Request id -> stage -> stamp on the tracer's raw clock, from its
    events (``RequestEvent``s; device-call events are skipped)."""
    out: dict[int, dict[str, float]] = {}
    for ev in events:
        if hasattr(ev, "stage"):
            out.setdefault(ev.rid, {})[ev.stage] = ev.t + epoch
    return out


def phase_waits(by_rid: dict, lo: float, hi: float) -> dict[str, list]:
    """Each phase's waits in seconds, over the requests whose phase
    ended in ``[lo, hi]`` and that carry both of its stamps."""
    out = {}
    for name, (a, b) in PHASES.items():
        out[name] = [s[b] - s[a] for s in by_rid.values()
                     if a in s and b in s and lo <= s[b] <= hi]
    return out


def phase_metrics(waits: dict[str, list]) -> dict[str, float]:
    """The per-layer metrics of the phases: each p90 in ms, left out
    where its phase has no wait in the window."""
    return {metric: 1e3 * float(np.percentile(waits[phase], 90))
            for metric, phase in METRICS.items() if waits[phase]}


def idle_intervals(trace: dict) -> list[tuple[int, int]]:
    """The window's intervals in which no device ran a program."""
    lo, hi = tr.window(trace)
    busy = tr.union((max(s, lo), min(s + d, hi))
                    for _, s, d, _ in trace["programs"])
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def label(mid: float, spans: list[list]) -> str:
    """The innermost ``serve.*`` span covering ``mid``; else ``between
    steps`` where ``serve.step`` spans lie on both sides of it; else
    ``no span``."""
    inside = [s for s in spans if s[1] <= mid <= s[1] + s[2]]
    if inside:
        return min(inside, key=lambda s: s[2])[0]
    steps = [s for s in spans if s[0] == "serve.step"]
    if (any(s[1] + s[2] < mid for s in steps)
            and any(s[1] > mid for s in steps)):
        return BETWEEN
    return "no span"


def host_gaps(trace: dict, spans: list[list],
              n: int = 10) -> list[tuple[str, int]]:
    """The ``n`` longest device-idle gaps of the window, longest first,
    each as (label, ns)."""
    gaps = sorted(idle_intervals(trace), key=lambda g: g[0] - g[1])[:n]
    return [(label((a + b) / 2, spans), b - a) for a, b in gaps]


def idle_by_label(trace: dict, spans: list[list]) -> dict[str, float]:
    """Share (%) of the window's device-idle time under each label, each
    instant of a gap under the innermost span covering it (a gap
    typically runs through several spans: the end of one step, the
    frontend, the start of the next)."""
    by: dict[str, int] = {}
    for a, b in idle_intervals(trace):
        cuts = sorted({a, b} | {x for s in spans for x in (s[1], s[1] + s[2])
                                if a < x < b})
        for x, y in zip(cuts, cuts[1:]):
            key = label((x + y) / 2, spans)
            by[key] = by.get(key, 0) + y - x
    total = sum(by.values())
    return {k: 100.0 * v / total for k, v in
            sorted(by.items(), key=lambda kv: -kv[1])} if total else {}


def step_idle_ms(trace: dict, spans: list[list]) -> float | None:
    """Device-idle ms in the window per ``serve.step`` span starting in
    it; None without such a span."""
    lo, hi = tr.window(trace)
    n = sum(1 for s in spans if s[0] == "serve.step" and lo <= s[1] < hi)
    if n == 0:
        return None
    return 1e-6 * ((hi - lo) - tr.busy_ns(trace)) / n


def ttft_residuals(records, rid_due: dict[int, float], by_rid: dict,
                   lo: float, hi: float) -> list[tuple[float, float, float]]:
    """Per request whose first token the client received in ``[lo, hi]``:
    (client TTFT less the four phases, enqueue - due, client's first
    token - the program's first_token stamp), in seconds."""
    by_due = {due: rid for rid, due in rid_due.items() if due is not None}
    out = []
    for rec in records:
        rid = by_due.get(rec.due)
        s = by_rid.get(rid, {})
        if (not rec.times or not lo <= rec.times[0] <= hi
                or "enqueue" not in s or "first_token" not in s):
            continue
        phases = s["first_token"] - s["enqueue"]
        out.append((rec.times[0] - rec.due - phases, s["enqueue"] - rec.due,
                    rec.times[0] - s["first_token"]))
    return out


def excerpt(trace: dict, spans: list[list], seconds: float) -> dict:
    """``seconds`` from the middle of the window of ``trace``: the
    programs and host spans that overlap it, with ``bench.window`` cut
    to it and the ``serve.*`` spans under ``serve`` (operations left
    out).  What the tests of this module read as a recorded trace."""
    lo, hi = tr.window(trace)
    a = (lo + hi) // 2 - int(seconds * 5e8)
    b = a + int(seconds * 1e9)
    cut = lambda rows, i: [r for r in rows if r[i] < b and r[i] + r[i + 1] > a]
    return {"devices": trace["devices"],
            "programs": cut(trace["programs"], 1), "ops": {},
            "spans": [["bench.window", a, b - a]]
            + [s for s in cut(trace["spans"], 1) if s[0] != "bench.window"],
            "serve": cut(spans, 1)}


def _probe_class(program_tracer: bool):
    """The benchmark's probe, which also maps the program's request ids
    to the clients' due times and, if asked, starts the program's tracer
    before the traffic does (so a request submitted before the traced
    window still carries the stamps of its later phases)."""
    from bench.probe import Probe

    class ProgramProbe(Probe):
        def install(self) -> None:
            super().install()
            self.rid_due: dict[int, float] = {}
            submit = self.server.try_submit

            def try_submit(req, **kw):
                out = submit(req, **kw)
                if isinstance(out, int):
                    self.rid_due[out] = self.due.get(id(req))
                return out

            self.server.try_submit = try_submit
            self.tracer = self.server.tracer
            if program_tracer:
                self.tracer.start()

    return ProgramProbe


def traced_run(name: str, cfg: dict, spec: dict, *, seed: int,
               seconds: float, program_tracer: bool, devices,
               logdir: str, save: str | None = None) -> dict:
    """One traced window of a cell, with or without the program's
    tracer; returns the readings (the result line's ``metrics`` and
    more)."""
    import shutil
    import time

    from bench import drive, probe, run

    real = probe.Probe
    # bench/run.py's window, with the probe above in place of its own
    probe.Probe = _probe_class(program_tracer)
    try:
        out = run.serve_window(name, cfg, spec, seed=seed, seconds=seconds,
                               trace=True, devices=devices,
                               t_start=time.perf_counter(), logdir=logdir)
    finally:
        probe.Probe = real
    pr = out["probe"]
    pr.tracer.stop()
    metrics, _, ctx = run._layer_metrics(
        ["queue_wait_p90_ms", "prefill_us_per_token", "decode_step_ms",
         "decode_roofline", "decode_mfu", "device_idle_share"],
        pr, logdir, out["model"], cfg["serving"], devices)
    path = tr.find_xplane(logdir)
    trace, spans = tr.extract(path, tr.load_table()), serve_spans(path)
    shutil.rmtree(logdir, ignore_errors=True)
    e2e = drive.end_to_end(out["window"])
    result = {"workload": name, "seed": seed,
              "program_tracer": int(program_tracer), "metrics": metrics,
              "ttft_p90_ms": 1e3 * e2e["ttft_p90_s"],
              "itl_p95_ms": 1e3 * e2e["itl_p95_s"],
              "window_s": ctx.window_s, "busy_s": ctx.busy_s,
              "programs": ctx.program_s, "serve_spans": len(spans)}
    if program_tracer:
        by_rid = stamps(pr.tracer.events(), pr.tracer.epoch)
        waits = phase_waits(by_rid, pr.t_open, pr.t_close)
        metrics.update(phase_metrics(waits))
        idle = step_idle_ms(trace, spans)
        if idle is not None:
            metrics["step_idle_ms"] = idle
        res = ttft_residuals(out["window"].records, pr.rid_due, by_rid,
                             pr.t_open, pr.t_close)
        result.update(
            phase_counts={k: len(v) for k, v in waits.items()},
            host_gaps=[[lb, ns * 1e-9] for lb, ns in host_gaps(trace, spans)],
            idle_by_label=idle_by_label(trace, spans),
            ttft_residual_ms=[[1e3 * x for x in r] for r in res],
            ttft_residual_within_0_25ms=(
                sum(0 <= r[0] <= 0.025 for r in res) / len(res)
                if res else None),
            dropped_events=pr.tracer.dropped)
        if save:
            import json

            Path(save).parent.mkdir(parents=True, exist_ok=True)
            Path(save).write_text(json.dumps(excerpt(trace, spans, 1.5)))
    return result


def main(argv=None) -> int:
    import argparse
    import json

    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one traced window each")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--program-tracer", default="1",
                    help="comma-separated 0/1: each seed runs once per entry")
    ap.add_argument("--save-excerpt", default=None, metavar="PATH",
                    help="write 1.5 s of the first run's trace with the "
                         "program's tracer on here (see excerpt)")
    args = ap.parse_args(argv)

    spec, cell, cfg, traffic_spec = run.load_cell(args.workload)
    try:
        devices = run.accelerator(cell["chips"])
    except run.NoAccelerator as e:
        run.log(f"error: {e}")
        return 3
    run.enable_compile_cache()
    modes = [bool(int(x)) for x in args.program_tracer.split(",")]
    save = args.save_excerpt
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        # alternate which side runs first from seed to seed
        for on in (modes if i % 2 == 0 else modes[::-1]):
            res = traced_run(
                args.workload, cfg, traffic_spec, seed=seed,
                seconds=args.seconds, program_tracer=on, devices=devices,
                logdir=str(ROOT / ".bench_trace" / f"phases.{seed}.{int(on)}"),
                save=save if on else None)
            if on:
                save = None
            print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
