"""Observability tests (ISSUE 6): step tracing, Prometheus exposition,
and the HTTP debug surface.

The load-bearing contracts:

* tracing OFF is free — call sites guard on ``tracer.enabled``, so the
  disabled path runs NO tracer code at all (no event or span
  construction, no locks, no clock reads inside the tracer) — asserted
  by making every tracer method explode and draining a full workload,
* tracing ON is invisible to results — traced greedy streams are
  bit-identical to untraced ones (dense + recurrent, no-mesh and an
  8-device mesh subprocess) — and to the schedule: a traced drain makes
  exactly the host synchronisations an untraced one does,
* a traced step opens the ``serve.*`` spans, in order and nested, and
  every request's stamps run enqueue <= submit <= admit <= prefill_done
  <= first_token <= finish,
* ``export_chrome()`` emits loadable Chrome-trace JSON: ``X`` slices
  for device calls with dispatch/occupancy args (settle and gap on
  decode blocks), request phase spans correlated by request id, ``i``
  instants at terminal stages,
* the Prometheus rendering parses line-by-line (format 0.0.4) and its
  label escaping round-trips,
* ``ServerMetrics.snapshot()`` carries the cumulative device-call and
  compiled-shape counters (and the latter survives ``reset_metrics``),
* the HTTP layer negotiates /metrics on Accept, exposes
  /debug/trace{,/start,/stop} + /metrics/reset, and /healthz flips to
  503 when the driver task dies,

and the §6.9 accounting/SLO/flight layer (ISSUE 10):

* accounting OFF and flight unarmed are free (bombed-methods proof,
  same as the tracer's),
* accounting ON conserves — per-tenant attributed time re-sums to
  settled device wall (under chunked prefill, K=8 multi-step decode,
  AND across a supervised driver crash with replay) — and never
  changes greedy streams,
* log-bucketed histograms bound percentile error by the bucket growth
  factor and expose valid Prometheus ``histogram`` families
  (monotone cumulative ``le`` buckets ending at +Inf == _count),
* SLO objectives evaluate ok/burning/violated from cumulative budget
  + recent burn, surfaced on /v1/slo, /healthz and /v1/models,
* crash/watchdog/quarantine incidents freeze a ``flight/v1`` JSON
  artifact that round-trips from disk.
"""
import asyncio
import json
import math
import os
import re
import subprocess
import sys
import textwrap
import threading

import pytest

import jax

from repro import api
from repro.configs import registry
from repro.serving import (
    AsyncEngine,
    FlightRecorder,
    MultiModelServer,
    Request,
    SLOConfig,
    start_http_server,
)
from repro.serving.obs import (
    LogHistogram,
    RequestEvent,
    Tracer,
    evaluate_availability,
    evaluate_objective,
    render_prometheus,
    worst_state,
)
from repro.serving.obs import trace as trace_mod
from repro.serving.obs.prometheus import escape_label
from repro.serving.obs.slo import HIST_GROWTH

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build(arch, m=2):
    cfg = registry.get_smoke_config(arch).with_(num_instances=m)
    params = api.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _server(cfg, params, **kw):
    kw.setdefault("slots_per_instance", 2)
    kw.setdefault("max_context", 48)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("prefill_chunk", 4)
    return MultiModelServer(cfg, params, **kw)


def _reqs():
    return [
        Request(instance=0, prompt=[1, 2, 3], max_new_tokens=4),
        Request(instance=1, prompt=[4, 5], max_new_tokens=4),
        Request(instance=0, prompt=[7], max_new_tokens=3),
        Request(instance=1, prompt=[3, 3, 3, 3, 3], max_new_tokens=3),
    ]


# ---------------------------------------------------------------------------
# tracing off: literally no tracer code on the hot path
# ---------------------------------------------------------------------------


def test_tracing_off_runs_no_tracer_code(monkeypatch):
    """With capture off, a full drain (submit, admit, prefill, scatter,
    decode, finish, cancel), synchronous and through the async frontend,
    must never enter the tracer: every recording method and the span
    helper are replaced with a bomb."""
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params)

    def boom(*a, **k):
        raise AssertionError("tracer code ran while capture was off")

    monkeypatch.setattr(server.tracer, "device_call", boom)
    monkeypatch.setattr(server.tracer, "request_event", boom)
    monkeypatch.setattr(server.tracer, "span", boom)
    monkeypatch.setattr(server.tracer, "_append", boom)
    ids = [server.submit(r) for r in _reqs()]
    # exercise the cancel call sites too (queued cancel)
    extra = server.submit(Request(instance=0, prompt=[9, 9], max_new_tokens=2))
    server.cancel(extra)
    results = server.run_until_drained()
    assert {r.request_id for r in results} == set(ids)
    assert all(r.status == "ok" for r in results)

    async def run():
        async with AsyncEngine(server) as engine:
            streams = [await engine.submit(r) for r in _reqs()]
            return [await s.result() for s in streams]

    assert all(r.status == "ok" for r in asyncio.run(run()))
    assert len(server.tracer) == 0


# ---------------------------------------------------------------------------
# tracing on: results are bit-identical
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "xlstm-1.3b"])
def test_traced_greedy_identical_to_untraced(arch):
    cfg, params = _build(arch)
    server = _server(cfg, params)

    def drain():
        ids = [server.submit(r) for r in _reqs()]
        res = {r.request_id: r.tokens for r in server.run_until_drained()}
        return [res[i] for i in ids]

    want = drain()
    server.tracer.start()
    got = drain()
    server.tracer.stop()
    assert got == want
    assert len(server.tracer) > 0


def test_traced_async_streams_identical_to_untraced_sync():
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params)
    for r in _reqs():
        server.submit(r)
    want = sorted(r.tokens for r in server.run_until_drained())

    async def run():
        engine = AsyncEngine(server)
        await engine.set_tracing(True)

        async def client(r):
            s = await engine.submit(r)
            toks = [t async for t in s]
            assert (await s.result()).tokens == toks
            return toks

        out = await asyncio.gather(*(client(r) for r in _reqs()))
        stopped = await engine.set_tracing(False)
        await engine.aclose()
        return out, stopped

    got, stopped = asyncio.run(run())
    assert sorted(got) == want
    assert stopped["tracing"] is False
    assert stopped["summary"]["decode_steps"] > 0


@pytest.mark.slow
def test_traced_streams_identical_under_mesh():
    """Tracing must be result-invisible on the sharded path too: an
    8-CPU-device (data=2, model=4) mesh drain with capture on equals
    the untraced no-mesh baseline, and the capture still carries
    decode/prefill/scatter events (subprocess harness as in
    test_serving_sharded.py)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro.launch.compat import make_host_mesh
        import numpy as np
        from repro import api
        from repro.configs import registry
        from repro.models import common as C
        from repro.serving import MultiModelServer, Request

        assert len(jax.devices()) == 8, jax.devices()
        mesh = make_host_mesh((2, 4))
        M = 2

        def build(arch):
            cfg1 = registry.get_smoke_config(arch).with_(
                num_instances=1, dtype="float32", param_dtype="float32")
            cfg = cfg1.with_(num_instances=M)
            keys = jax.random.split(jax.random.PRNGKey(0), M)
            merged = C.merge_instances(
                [api.init(cfg1, k) for k in keys], api.axes(cfg1))
            return cfg, merged

        def mk_reqs(cfg, n=5, max_new=4):
            rng = np.random.default_rng(0)
            return [Request(instance=i % M,
                            prompt=rng.integers(1, cfg.vocab_size,
                                size=int(rng.integers(2, 8))).tolist(),
                            max_new_tokens=max_new) for i in range(n)]

        def drain(server, reqs, traced):
            if traced:
                server.tracer.start()
            for r in reqs:
                server.submit(r)
            out = {r.request_id: r.tokens for r in server.run_until_drained()}
            if traced:
                server.tracer.stop()
            return out

        for arch in ("tinyllama-1.1b", "xlstm-1.3b"):
            cfg, merged = build(arch)
            plain = MultiModelServer(cfg, merged, slots_per_instance=2,
                                     max_context=64, prefill_chunk=4)
            want = drain(plain, mk_reqs(cfg), traced=False)
            assert all(want.values())
            meshed = MultiModelServer(cfg, merged, slots_per_instance=2,
                                      max_context=64, prefill_chunk=4,
                                      mesh=mesh)
            got = drain(meshed, mk_reqs(cfg), traced=True)
            assert got == want, (arch, got, want)
            s = meshed.tracer.summary()
            assert s["decode_steps"] > 0 and s["prefill_chunks"] > 0
            assert s["scatters"] > 0
            print(arch, "traced mesh streams OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "xlstm-1.3b traced mesh streams OK" in r.stdout


# ---------------------------------------------------------------------------
# chrome-trace export schema
# ---------------------------------------------------------------------------


def test_export_chrome_schema_and_json_roundtrip():
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params)
    server.tracer.start()
    for r in _reqs():
        server.submit(r)
    server.run_until_drained()
    server.tracer.stop()
    trace = json.loads(json.dumps(server.tracer.export_chrome()))

    assert trace["displayTimeUnit"] == "ms"
    assert trace["otherData"]["dropped_events"] == 0
    events = trace["traceEvents"]
    assert isinstance(events, list) and events

    device = [e for e in events if e["ph"] == "X" and e["pid"] == 0]
    spans = [e for e in events if e["ph"] == "X" and e["pid"] == 1]
    instants = [e for e in events if e["ph"] == "i"]
    meta = [e for e in events if e["ph"] == "M"]
    assert {e["name"] for e in device} == {"decode", "prefill_chunk",
                                           "scatter"}
    for e in device:
        assert e["ts"] >= 0 and e["dur"] >= 0
        args = e["args"]
        for k in ("step", "dispatch_ms", "active_slots", "slot_capacity",
                  "occupancy"):
            assert k in args, (e["name"], k)
        assert 0.0 <= args["occupancy"] <= 1.0
        # only decode blocks settle (at the engine's own device_get):
        # chunks and scatters are recorded at dispatch
        settles = e["name"] == "decode"
        assert ("settled_ms" in args) == settles, e
        assert ("gap_ms" in args) == settles, e
        if not settles:
            assert e["dur"] == pytest.approx(1e3 * args["dispatch_ms"])
    decode_args = [e["args"] for e in device if e["name"] == "decode"]
    assert any(a["active_slots"] > 0 for a in decode_args)
    assert all(a["slot_capacity"] == server.m * server.b
               for a in decode_args)

    # every request leaves spans on its own track, ending in a terminal
    # instant; every one of them ran the whole lifecycle, so each names
    # all five phases, in order
    rids = {e["tid"] for e in spans}
    assert len(rids) == len(_reqs())
    assert {e["name"] for e in instants} == {"finish:ok"}
    by_rid = {}
    for e in spans:
        by_rid.setdefault(e["tid"], []).append(e["name"])
    for v in by_rid.values():
        assert v == ["inbox", "queued", "prefill", "first_block",
                     "decode"], by_rid
    # process/thread naming metadata for the two trace processes
    assert {(e["name"], e.get("pid")) for e in meta} >= {
        ("process_name", 0), ("process_name", 1), ("thread_name", 0)}


def test_tracer_ring_bounds_memory_and_counts_drops():
    tr = Tracer(capacity=2, clock=lambda: 0.0)
    tr.start()
    for i in range(5):
        tr.device_call("decode", 0.0, 0.0, 0.0, step=i)
    assert len(tr) == 2
    assert tr.dropped == 3
    assert tr.export_chrome()["otherData"]["dropped_events"] == 3
    tr.start()                      # restart clears the window
    assert len(tr) == 0 and tr.dropped == 0


def test_summary_aggregates_from_synthetic_events():
    tr = Tracer(clock=lambda: 0.0)
    tr.start()                                    # epoch = 0.0
    tr.device_call("decode", 1.00, 1.01, 1.05, step=0, active=2, capacity=4)
    tr.device_call("prefill_chunk", 1.06, 1.07, step=1,
                   lanes_busy=1, lanes=4, valid_frac=0.5, tokens=8)
    tr.device_call("scatter", 1.08, 1.09, step=1)
    tr.device_call("decode", 1.10, 1.11, 1.16, step=1, active=4, capacity=4,
                   tokens=4)
    s = tr.summary()
    assert s["device_calls"] == 4
    assert s["decode_steps"] == 2
    assert s["prefill_chunks"] == 1
    assert s["scatters"] == 1
    # gaps and settle over decode blocks only: 0 (first), 1.10 - 1.05
    assert s["dispatch_overhead_ms"]["p95"] == pytest.approx(50.0)
    assert s["mean_dispatch_gap_ms"] == pytest.approx(25.0)
    assert s["dispatch_overhead_per_token_ms"] == pytest.approx(12.5)
    assert s["settled_ms"]["p50"] == pytest.approx(50.0)
    assert s["settled_ms"]["p99"] == pytest.approx(60.0)
    assert s["mean_grid_occupancy"] == pytest.approx(0.75)
    assert s["idle_slot_token_steps"] == 2
    assert s["mean_prefill_lane_occupancy"] == pytest.approx(0.25)
    assert s["mean_chunk_validity"] == pytest.approx(0.5)
    chunk = next(e for e in tr.events() if e.kind == "prefill_chunk")
    assert chunk.t_settled is None and chunk.gap_s is None


def test_request_spans_from_synthetic_lifecycle():
    times = iter([0.0, 1.0, 2.0, 3.0, 4.0])
    tr = Tracer(clock=lambda: next(times))
    tr.start()                                    # epoch = 0.0
    tr.request_event(7, "submit", instance=1)
    tr.request_event(7, "admit", instance=1)
    tr.request_event(7, "prefill_done", instance=1)
    tr.request_event(7, "finish", instance=1, status="ok")
    ev = tr.export_chrome()["traceEvents"]
    spans = {e["name"]: e for e in ev if e["ph"] == "X"}
    assert set(spans) == {"queued", "prefill", "decode"}
    assert spans["queued"]["ts"] == pytest.approx(1e6)
    assert spans["queued"]["dur"] == pytest.approx(1e6)
    assert spans["decode"]["dur"] == pytest.approx(1e6)
    assert [e["name"] for e in ev if e["ph"] == "i"] == ["finish:ok"]


def test_request_phases_from_synthetic_stamps():
    """The frontend's enqueue stamp is taken before the tracer sees the
    request (``t=``), and a reader puts stamps back on the raw clock
    with ``epoch``; consecutive stamps become the five phase spans."""
    times = iter([10.0, 12.0, 13.0, 14.5, 15.0, 17.0])
    tr = Tracer(clock=lambda: next(times))
    tr.start()                                    # epoch = 10.0
    assert tr.epoch == 10.0
    tr.request_event(3, "enqueue", instance=0, t=11.0)
    for stage in ("submit", "admit", "prefill_done", "first_token"):
        tr.request_event(3, stage, instance=0)
    tr.request_event(3, "finish", instance=0, status="ok")
    stamps = {e.stage: e.t + tr.epoch for e in tr.events()}
    assert stamps == {"enqueue": 11.0, "submit": 12.0, "admit": 13.0,
                      "prefill_done": 14.5, "first_token": 15.0,
                      "finish": 17.0}
    ev = tr.export_chrome()["traceEvents"]
    spans = [(e["name"], e["ts"], e["dur"]) for e in ev if e["ph"] == "X"]
    assert spans == [("inbox", 1e6, 1e6), ("queued", 2e6, 1e6),
                     ("prefill", 3e6, 1.5e6), ("first_block", 4.5e6, 0.5e6),
                     ("decode", 5e6, 2e6)]


# ---------------------------------------------------------------------------
# tracing on: serve.* spans, request stamps, no extra host synchronisation
# ---------------------------------------------------------------------------

# each engine span and the span it is opened inside
SPAN_PARENT = {
    "serve.step": None,
    "serve.admit": "serve.step",
    "serve.prefill": "serve.step",
    "serve.prefill.chunk": "serve.prefill",
    "serve.prefill.wait": "serve.prefill",
    "serve.scatter": "serve.step",
    "serve.decode.prepare": "serve.step",
    "serve.decode.dispatch": "serve.step",
    "serve.decode.wait": "serve.step",
    "serve.decode.unroll": "serve.step",
}
STEP_ORDER = ["serve.admit", "serve.prefill", "serve.scatter",
              "serve.decode.prepare", "serve.decode.dispatch",
              "serve.decode.wait", "serve.decode.unroll"]


@pytest.fixture
def spans(monkeypatch):
    """Swap the tracer's TraceAnnotation for a recording stand-in; yields
    the list of spans entered, each knowing its thread's enclosing span."""
    log, local = [], threading.local()

    class Recorder:
        def __init__(self, name, **tags):
            self.name, self.tags, self.children = name, dict(tags), []

        def set_metadata(self, **tags):
            self.tags.update(tags)

        def __enter__(self):
            stack = local.__dict__.setdefault("stack", [])
            self.parent = stack[-1] if stack else None
            if self.parent is not None:
                self.parent.children.append(self)
            stack.append(self)
            log.append(self)
            return self

        def __exit__(self, *exc):
            assert local.stack.pop() is self

    monkeypatch.setattr(trace_mod, "TraceAnnotation", Recorder)
    return log


def test_traced_drain_opens_step_spans_in_order_and_nested(spans):
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params, decode_steps=4)
    server.tracer.start()
    for r in _reqs():
        server.submit(r)
    server.run_until_drained()
    assert {sp.name for sp in spans} == set(SPAN_PARENT)
    for sp in spans:
        parent = sp.parent.name if sp.parent is not None else None
        assert parent == SPAN_PARENT[sp.name], (sp.name, parent)
    steps = [sp for sp in spans if sp.name == "serve.step"]
    assert [sp.tags["step"] for sp in steps] == list(range(len(steps)))
    for sp in steps:
        names = [c.name for c in sp.children]
        assert names == sorted(names, key=STEP_ORDER.index), names
        assert len(names) == len(set(names)), names
        # a step that decoded is tagged with its horizon
        assert ("k" in sp.tags) == ("serve.decode.dispatch" in names)
    assert {sp.tags["k"] for sp in steps if "k" in sp.tags} <= {1, 2, 4}
    for sp in spans:
        if sp.name == "serve.prefill":
            kids = [c.name for c in sp.children]
            assert kids[-1] == "serve.prefill.wait", kids
            assert set(kids[:-1]) == {"serve.prefill.chunk"}, kids
        if sp.name == "serve.prefill.chunk":
            assert 1 <= sp.tags["lanes"] <= server.prefill.lanes
            assert 1 <= sp.tags["tokens"] <= (sp.tags["lanes"]
                                               * server.prefill.chunk)


def test_traced_frontend_spans_wrap_commands_and_delivery(spans):
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params)

    async def run():
        async with AsyncEngine(server) as engine:
            await engine.set_tracing(True)
            streams = [await engine.submit(r) for r in _reqs()]
            return [await s.result() for s in streams]

    assert all(r.status == "ok" for r in asyncio.run(run()))
    front = [sp for sp in spans if sp.name.startswith("serve.frontend.")]
    assert {sp.name for sp in front} == {"serve.frontend.commands",
                                         "serve.frontend.deliver"}
    # frontend spans run on the event loop's thread, outside any step
    assert all(sp.parent is None for sp in front)
    n_steps = sum(sp.name == "serve.step" for sp in spans)
    n_deliver = sum(sp.name == "serve.frontend.deliver" for sp in front)
    assert n_steps > 0 and n_deliver == n_steps


@pytest.mark.parametrize("arch,decode_steps", [("tinyllama-1.1b", 1),
                                               ("tinyllama-1.1b", 8),
                                               ("xlstm-1.3b", 8)])
def test_tracing_adds_no_host_synchronisation(monkeypatch, arch,
                                              decode_steps):
    """A traced drain waits on the device exactly where an untraced one
    does (the engine's device_get, one settle per prefill advance):
    tracing must not serialise work an untraced step overlaps."""
    cfg, params = _build(arch)
    server = _server(cfg, params, decode_steps=decode_steps)
    counts = {"block_until_ready": 0, "device_get": 0}
    for name in counts:
        real = getattr(jax, name)

        def counted(*a, _name=name, _real=real, **k):
            counts[_name] += 1
            return _real(*a, **k)

        monkeypatch.setattr(jax, name, counted)

    def drain():
        for k in counts:
            counts[k] = 0
        for r in _reqs():
            server.submit(r)
        out = sorted(r.tokens for r in server.run_until_drained())
        return dict(counts), out

    off, want = drain()
    server.tracer.start()
    on, got = drain()
    assert got == want
    assert off["device_get"] > 0 and off["block_until_ready"] > 0
    assert on == off
    assert server.tracer.summary()["prefill_chunks"] > 0


def _stamps_in_order(tracer, rids):
    order = ("enqueue", "submit", "admit", "prefill_done", "first_token",
             "finish")
    stamps: dict = {}
    for ev in tracer.events():
        if isinstance(ev, RequestEvent):
            stamps.setdefault(ev.rid, {})[ev.stage] = ev.t
    assert set(stamps) == set(rids)
    for rid in rids:
        assert set(stamps[rid]) == set(order), stamps[rid]
        ts = [stamps[rid][s] for s in order]
        assert ts == sorted(ts), (rid, stamps[rid])


def test_request_stamps_ordered_sync():
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params, decode_steps=4)
    server.tracer.start()
    ids = [server.submit(r) for r in _reqs()]
    results = server.run_until_drained()
    assert all(r.status == "ok" for r in results)
    _stamps_in_order(server.tracer, ids)


def test_request_stamps_ordered_through_frontend():
    """Through AsyncEngine the enqueue stamp is the client's epoch,
    taken before the driver applies the submit command."""
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params, decode_steps=4)

    async def run():
        async with AsyncEngine(server) as engine:
            await engine.set_tracing(True)
            streams = await asyncio.gather(
                *(engine.submit(r) for r in _reqs()))
            return [await s.result() for s in streams]

    results = asyncio.run(run())
    assert all(r.status == "ok" for r in results)
    _stamps_in_order(server.tracer, [r.request_id for r in results])


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------

# one sample line: name{labels} value — label values are quoted strings
# with \\ \" \n escapes; value is a float, integer, NaN or +/-Inf
_SAMPLE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?:\{((?:[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*",?)*)\})?'
    r' (NaN|[+-]Inf|[+-]?[0-9.eE+-]+)$')


def test_prometheus_exposition_parses_line_by_line():
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params)
    for r in _reqs():
        server.submit(r)
    server.run_until_drained()
    text = render_prometheus(server.metrics.snapshot())

    typed = {}
    samples = {}
    for line in text.strip().split("\n"):
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            _, _, name, typ = line.split(" ", 3)
            assert typ in ("counter", "gauge", "summary", "histogram"), line
            typed[name] = typ
            continue
        m = _SAMPLE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        samples.setdefault(m.group(1), []).append(m.group(3))
    # every sample was declared, every declared family has samples; a
    # histogram family F exposes F_bucket/F_sum/F_count sample names
    expect = set()
    for name, typ in typed.items():
        if typ == "histogram":
            expect |= {f"{name}_bucket", f"{name}_sum", f"{name}_count"}
        else:
            expect.add(name)
    assert set(samples) == expect
    gen = sum(r.max_new_tokens for r in _reqs())
    assert samples["repro_generated_tokens_total"] == [str(gen)]
    assert samples["repro_device_calls_total"][0].isdigit()
    assert int(samples["repro_device_calls_total"][0]) > 0
    assert samples["repro_prefill_compiled_shapes"] == ["1"]
    # per-instance families carry one sample per instance; summaries
    # carry one per quantile
    assert len(samples["repro_instance_completed_total"]) == server.m
    assert len(samples["repro_ttft_milliseconds"]) == 3
    assert typed["repro_instance_ttft_seconds"] == "histogram"


def test_prometheus_histogram_le_buckets_are_valid():
    """The real-histogram exposition contract (CI's observability job
    leans on this): per-instance ``le`` bounds strictly increase,
    cumulative counts never decrease, the family ends at ``le="+Inf"``
    whose value equals ``_count``, and ``_sum``/``_count`` are
    consistent with the recorded samples."""
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params)
    for r in _reqs():
        server.submit(r)
    server.run_until_drained()
    text = render_prometheus(server.metrics.snapshot())

    pat = re.compile(
        r'^repro_instance_ttft_seconds_bucket'
        r'\{instance="(\d+)",le="([^"]+)"\} (\d+)$')
    buckets = {}
    for line in text.strip().split("\n"):
        m = pat.match(line)
        if m:
            buckets.setdefault(int(m.group(1)), []).append(
                (m.group(2), int(m.group(3))))
    assert set(buckets) == set(range(server.m))
    counts = {}
    sums = {}
    for line in text.strip().split("\n"):
        m = re.match(r'^repro_instance_ttft_seconds_(count|sum)'
                     r'\{instance="(\d+)"\} (\S+)$', line)
        if m:
            (counts if m.group(1) == "count" else sums)[
                int(m.group(2))] = float(m.group(3))
    for i, rows in buckets.items():
        les = [float("inf") if le == "+Inf" else float(le)
               for le, _ in rows]
        cums = [c for _, c in rows]
        assert les == sorted(les) and len(set(les)) == len(les), i
        assert les[-1] == float("inf"), i
        assert cums == sorted(cums), i
        assert cums[-1] == counts[i], i
        assert counts[i] > 0              # every instance served a TTFT
        assert sums[i] > 0


def test_prometheus_label_escaping_roundtrips():
    assert escape_label('a\\b"c\nd') == 'a\\\\b\\"c\\nd'
    nasty = {'path': 'a\\b"c\nd', 'plain': 'ok'}
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params)
    text = render_prometheus(server.metrics.snapshot(), extra_labels=nasty)
    line = next(l for l in text.split("\n")
                if l.startswith("repro_generated_tokens_total{"))
    m = _SAMPLE.match(line)
    assert m, line
    # unescape the label block and recover the original values
    labels = dict(re.findall(r'([a-zA-Z_]+)="((?:[^"\\]|\\.)*)"', m.group(2)))
    unescape = lambda s: (s.replace("\\n", "\n").replace('\\"', '"')
                          .replace("\\\\", "\\"))
    assert unescape(labels["path"]) == nasty["path"]
    assert labels["plain"] == "ok"


# ---------------------------------------------------------------------------
# snapshot counters + reset semantics
# ---------------------------------------------------------------------------


def test_snapshot_device_call_and_compiled_shape_counters():
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params)
    for r in _reqs():
        server.submit(r)
    results = server.run_until_drained()
    snap = server.metrics.snapshot()
    assert snap["scatter_calls"] == len(results)
    assert snap["device_calls"] == (snap["decode_steps"]
                                    + snap["prefill_batches"]
                                    + snap["scatter_calls"])
    assert snap["device_calls"] > 0
    assert snap["prefill_compiled_shapes"] == 1   # tail folding: one shape
    # the compiled-shape gauge reads through to the live prefill runtime,
    # so a reset window still reports the true cumulative count
    server.reset_metrics()
    snap2 = server.metrics.snapshot()
    assert snap2["generated_tokens"] == 0
    assert snap2["device_calls"] == 0
    assert snap2["prefill_compiled_shapes"] == 1


# ---------------------------------------------------------------------------
# HTTP surface
# ---------------------------------------------------------------------------


async def _req_http(port, method, path, headers=None, payload=None):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    body = b"" if payload is None else json.dumps(payload).encode()
    extra = "".join(f"{k}: {v}\r\n" for k, v in (headers or {}).items())
    writer.write(
        f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
        f"Content-Length: {len(body)}\r\n{extra}\r\n".encode() + body)
    await writer.drain()
    raw = await reader.read()
    writer.close()
    await writer.wait_closed()
    head, _, rest = raw.partition(b"\r\n\r\n")
    head = head.decode("latin-1")
    status = int(head.split()[1])
    ctype = next((l.split(":", 1)[1].strip() for l in head.split("\r\n")
                  if l.lower().startswith("content-type")), "")
    return status, ctype, rest


def test_http_observability_routes():
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params)

    async def run():
        async with AsyncEngine(server) as engine:
            http = await start_http_server(engine, port=0)
            port = http.sockets[0].getsockname()[1]

            st, _, body = await _req_http(port, "GET", "/healthz")
            h = json.loads(body)
            assert st == 200 and h["status"] == "ok"
            assert h["driver"] == "running"
            assert h["in_flight"] == 0 and h["queue_depths"] == [0, 0]
            assert h["tracing"] is False

            st, _, body = await _req_http(port, "POST", "/debug/trace/start")
            assert st == 200 and json.loads(body) == {"tracing": True}

            st, _, body = await _req_http(
                port, "POST", "/v1/completions",
                payload={"model": 0, "prompt": [1, 2, 3], "max_tokens": 4})
            assert st == 200
            toks = json.loads(body)["choices"][0]["tokens"]
            assert len(toks) == 4

            st, ct, body = await _req_http(port, "GET", "/debug/trace")
            trace = json.loads(body)
            assert st == 200 and ct == "application/json"
            assert any(e.get("name") == "decode"
                       for e in trace["traceEvents"])

            st, _, body = await _req_http(port, "POST", "/debug/trace/stop")
            stop = json.loads(body)
            assert st == 200 and stop["tracing"] is False
            assert stop["summary"]["decode_steps"] >= 4

            # Accept negotiation: text/plain -> Prometheus, default JSON
            st, ct, body = await _req_http(port, "GET", "/metrics",
                                           headers={"Accept": "text/plain"})
            assert st == 200
            assert ct == "text/plain; version=0.0.4; charset=utf-8"
            assert b"# TYPE repro_generated_tokens_total counter" in body
            st, ct, body = await _req_http(port, "GET", "/metrics")
            assert ct == "application/json"
            snap = json.loads(body)
            assert snap["generated_tokens"] == 4

            st, _, _ = await _req_http(port, "POST", "/metrics/reset")
            assert st == 200
            _, _, body = await _req_http(port, "GET", "/metrics")
            assert json.loads(body)["generated_tokens"] == 0

            # unconfigured SLO / flight recorder still answer (empty)
            st, _, body = await _req_http(port, "GET", "/v1/slo")
            assert st == 200 and json.loads(body) == {"configured": False}
            st, _, body = await _req_http(port, "GET", "/debug/flight")
            fl = json.loads(body)
            assert st == 200 and fl["enabled"] is False
            assert fl["count"] == 0 and fl["dumps"] == []

            # wrong methods answer 405, not 404
            for method, path in (("GET", "/metrics/reset"),
                                 ("GET", "/debug/trace/start"),
                                 ("POST", "/debug/trace"),
                                 ("POST", "/healthz"),
                                 ("POST", "/v1/slo"),
                                 ("POST", "/debug/flight")):
                st, _, _ = await _req_http(port, method, path)
                assert st == 405, (method, path)

            http.close()
            await http.wait_closed()

    asyncio.run(run())


def test_healthz_503_when_driver_dies():
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params)

    async def run():
        engine = AsyncEngine(server)
        http = await start_http_server(engine, port=0)
        port = http.sockets[0].getsockname()[1]

        def explode():
            raise RuntimeError("injected step failure")

        server.step = explode
        stream = await engine.submit(
            Request(instance=0, prompt=[1, 2], max_new_tokens=2))
        res = await stream.result()
        # unsupervised driver death is a terminal engine failure, not a
        # client cancellation: the stream errors with the tokens it
        # already delivered (none here) — DESIGN.md §6.8
        assert res.status == "error"
        assert "driver failed" in res.error
        assert res.tokens == list(stream.emitted)

        st, _, body = await _req_http(port, "GET", "/healthz")
        h = json.loads(body)
        assert st == 503
        assert h["status"] == "error" and h["driver"] == "failed"
        assert h["instance_health"] == ["healthy", "healthy"]

        http.close()
        await http.wait_closed()
        # the failure already reached every waiter; aclose() returns
        # without re-raising and without hanging
        await asyncio.wait_for(engine.aclose(), 10)

    asyncio.run(run())


def test_run_in_step_gap_without_running_driver():
    """reset/tracing toggles must work before any request ever started
    the driver (direct-call fallback)."""
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params)

    async def run():
        engine = AsyncEngine(server)
        on = await engine.set_tracing(True)
        off = await engine.set_tracing(False)
        await engine.reset_metrics()
        await engine.aclose()
        return on, off

    on, off = asyncio.run(run())
    assert on == {"tracing": True}
    assert off["tracing"] is False


# ---------------------------------------------------------------------------
# log-bucketed histograms + SLO evaluation (§6.9)
# ---------------------------------------------------------------------------


def test_loghistogram_percentile_error_bound_and_merge():
    """The histogram replaces the biased sliding windows: over the full
    sample set, every reported percentile is >= the exact one (bucket
    upper bound, never under-reports) and within one growth factor of
    it.  merge() is bucket-exact."""
    import random

    rng = random.Random(0)
    vals = [rng.uniform(1e-3, 2.0) for _ in range(5000)]
    h = LogHistogram()
    for v in vals:
        h.record(v)
    s = sorted(vals)
    for q in (0.5, 0.95, 0.99):
        exact = s[max(0, math.ceil(q * len(s)) - 1)]
        got = h.percentile(q)
        assert exact <= got <= exact * HIST_GROWTH * 1.0001, (q, exact, got)
    assert h.count == len(vals)
    assert h.sum == pytest.approx(sum(vals))

    a, b = LogHistogram(), LogHistogram()
    for v in vals[:2000]:
        a.record(v)
    for v in vals[2000:]:
        b.record(v)
    a.merge(b)
    assert a.counts == h.counts
    assert a.percentile(0.99) == h.percentile(0.99)


def test_loghistogram_inf_bucket_and_frac_le():
    h = LogHistogram()
    h.record(1e-6)          # below the ladder -> first bucket
    h.record(500.0)         # above the ladder -> +Inf bucket
    assert h.counts[0] == 1 and h.counts[-1] == 1
    les, cums = zip(*h.buckets())
    assert les[-1] == math.inf and cums[-1] == 2
    assert list(cums) == sorted(cums)
    # conservative: mid-bucket thresholds credit only whole buckets,
    # and a +Inf-bucket sample is never credited to a finite threshold
    assert h.frac_le(1.0) == 0.5
    assert h.frac_le(1e3) == 0.5
    # +Inf percentile falls back to the largest finite bound
    assert h.percentile(0.99) == LogHistogram.les[-1]
    assert LogHistogram().percentiles() is None


def test_slo_objective_states_and_burn_rate():
    good = LogHistogram()
    for _ in range(1000):
        good.record(0.010)                     # 10 ms, threshold 200 ms
    ok = evaluate_objective(good, [0.010] * 50, 200.0, target=0.99)
    assert ok["state"] == "ok"
    assert ok["bad_frac"] == 0.0 and ok["burn_rate"] == 0.0
    assert ok["budget_remaining"] == pytest.approx(1.0)

    # cumulative fine, recent window failing fast -> burning
    burning = evaluate_objective(good, [0.900] * 10 + [0.010] * 90,
                                 200.0, target=0.99)
    assert burning["state"] == "burning"
    assert burning["burn_rate"] == pytest.approx(10.0)

    # cumulative budget blown -> violated regardless of recent
    bad = LogHistogram()
    for _ in range(90):
        bad.record(0.010)
    for _ in range(10):
        bad.record(0.900)
    violated = evaluate_objective(bad, [0.010] * 50, 200.0, target=0.99)
    assert violated["state"] == "violated"
    assert violated["budget_remaining"] < 0

    assert worst_state(["ok", "burning", "ok"]) == "burning"
    assert worst_state(["burning", "violated"]) == "violated"
    assert worst_state([]) == "ok"

    avail = evaluate_availability(99, 1, target=0.99)
    assert avail["state"] == "ok"
    assert evaluate_availability(50, 50)["state"] == "violated"


# ---------------------------------------------------------------------------
# tenant accounting: zero-cost off, conserved + result-invisible on
# ---------------------------------------------------------------------------


def test_accounting_and_flight_off_run_no_code(monkeypatch):
    """Accounting disabled (the default) and no flight dir: a full
    drain — submit, queue wait, chunked prefill, scatter, decode,
    finish — must never enter the ledger or the recorder (every method
    is a bomb), same proof as the tracer's."""
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params)

    def boom(*a, **k):
        raise AssertionError("accounting/flight code ran while disabled")

    for name in ("note_decode", "note_prefill", "note_scatter",
                 "note_queue_wait", "note_replay", "_interfere",
                 "snapshot", "conservation"):
        monkeypatch.setattr(server.accounting, name, boom)
    monkeypatch.setattr(server.flight, "dump", boom)
    ids = [server.submit(r) for r in _reqs()]
    results = server.run_until_drained()
    assert {r.request_id for r in results} == set(ids)
    assert all(r.status == "ok" for r in results)
    assert server.accounting.enabled is False
    assert len(server.flight) == 0
    # quarantine hook only wires up when the recorder is armed
    assert server.health.on_quarantine is None


def test_accounted_streams_bit_identical_and_conserved():
    """Accounting + tracing + SLO on, under chunked prefill AND K=8
    multi-step decode: greedy streams bit-identical to the plain run,
    and the ledger conserves (attributed time re-sums to settled wall
    within float error — far inside the 1% acceptance bound)."""
    cfg, params = _build("tinyllama-1.1b")

    def drain(**kw):
        server = _server(cfg, params, prefill_chunk=4, decode_steps=8, **kw)
        if kw:
            server.accounting.start()
            server.tracer.start()
        ids = [server.submit(r) for r in _reqs()]
        res = {r.request_id: r.tokens for r in server.run_until_drained()}
        return server, [res[i] for i in ids]

    _, want = drain()
    server, got = drain(slo=SLOConfig(ttft_ms=200.0, itl_ms=100.0))
    assert got == want

    cons = server.accounting.conservation()
    assert cons["settled_s"] > 0
    assert cons["rel_err"] < 1e-6, cons
    snap = server.metrics.snapshot()
    acct = snap["accounting"]
    assert acct["enabled"] is True
    assert acct["conservation_rel_err"] < 1e-6
    assert set(acct["per_tenant"]) == {"0", "1"}
    for t in acct["per_tenant"].values():
        assert t["decode_s"] > 0 and t["prefill_s"] > 0
    # every device call the metrics counted was attributed
    assert acct["device_calls"] == snap["device_calls"]
    # the SLO block rides the same snapshot
    assert snap["slo"]["configured"] is True
    assert len(snap["slo"]["instances"]) == server.m
    for inst in snap["slo"]["instances"]:
        assert set(inst["objectives"]) == {"ttft", "itl", "availability"}
        assert inst["state"] in ("ok", "burning", "violated")


def test_interference_report_under_backlog():
    """With more requests than slots, tenants queue behind each other:
    the head-of-line report must attribute each waiter's delay to the
    occupants, and queue-wait accrues."""
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params, slots_per_instance=1)
    server.accounting.start()
    for _ in range(3):                       # backlog on both instances
        for r in _reqs():
            server.submit(r)
    server.run_until_drained()
    snap = server.accounting.snapshot()
    assert snap["interference"], "no interference recorded under backlog"
    waited = {int(w) for w in snap["interference"]}
    assert waited <= {0, 1}
    for acc in snap["interference"].values():
        assert all(s > 0 for s in acc.values())
    assert sum(t["queue_wait_s"] for t in snap["per_tenant"].values()) > 0
    assert snap["conservation_rel_err"] < 1e-6


# ---------------------------------------------------------------------------
# flight recorder + conservation across a supervised crash
# ---------------------------------------------------------------------------


def test_flight_dump_and_conservation_under_driver_crash(tmp_path):
    """A supervised driver crash mid-run: the flight recorder freezes
    the incident to disk (schema round-trip), conservation holds across
    the recovery (replayed calls are attributed like any other), and
    the replay view account is charged."""
    from repro.serving import FaultInjector, FaultSpec, Supervisor

    cfg, params = _build("tinyllama-1.1b")
    inj = FaultInjector([FaultSpec(site="driver", at_call=3)])
    server = _server(cfg, params, prefill_chunk=4, faults=inj,
                     flight=FlightRecorder(str(tmp_path)),
                     slo=SLOConfig(ttft_ms=200.0))
    server.accounting.start()
    server.tracer.start()
    inj.arm()

    async def main():
        engine = AsyncEngine(server)
        sup = Supervisor(engine, backoff_base_s=0.001)
        async with sup:
            async def client(r):
                s = await engine.submit(r)
                toks = [t async for t in s]
                return toks, await s.result()

            out = await asyncio.gather(*(client(r) for r in _reqs()))
        return out, sup

    out, sup = asyncio.run(main())
    assert sup.restarts == 1
    assert all(res.status == "ok" and res.tokens == toks
               for toks, res in out)

    # conservation survives the crash + replay (acceptance: < 1%)
    snap = server.accounting.snapshot()
    assert snap["conservation_rel_err"] < 0.01, snap
    assert sum(t["replay_tokens"] for t in snap["per_tenant"].values()) > 0
    assert sum(t["replay_s"] for t in snap["per_tenant"].values()) > 0

    # the dump landed on disk and round-trips with the full schema
    assert len(server.flight) >= 1
    files = sorted(tmp_path.glob("flight-*.json"))
    assert files
    rec = json.loads(files[0].read_text())
    assert rec["schema"] == "flight/v1"
    assert rec["seq"] == 1
    assert rec["reason"].startswith("crash:")
    assert rec["extra"]["in_flight"] == len(_reqs())
    assert isinstance(rec["queue_depths"], list)
    assert rec["trace_events"], "trace tail missing from the dump"
    kinds = {ev["event"] for ev in rec["trace_events"]}
    assert kinds <= {"DeviceCallEvent", "RequestEvent"} and kinds
    m = rec["metrics"]
    assert m["slo"]["configured"] is True
    assert m["accounting"]["enabled"] is True
    # the in-memory ring serves the same record
    assert server.flight.latest()[0]["seq"] == 1


def test_quarantine_hook_fires_flight_dump(tmp_path):
    """health.py's quarantine transition is a flight trigger: the hook
    is wired only when the recorder is armed, and firing it dumps."""
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params, flight=FlightRecorder(str(tmp_path)))
    assert server.health.on_quarantine is not None
    server.health.on_quarantine(1)
    assert len(server.flight) == 1
    rec = server.flight.latest()[0]
    assert rec["reason"] == "quarantine: instance 1"
    assert rec["path"] and os.path.exists(rec["path"])


# ---------------------------------------------------------------------------
# SLO on the HTTP surface
# ---------------------------------------------------------------------------


def test_http_slo_routes_and_health_integration():
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params, slo=SLOConfig(ttft_ms=60_000.0,
                                                itl_ms=60_000.0))

    async def run():
        async with AsyncEngine(server) as engine:
            http = await start_http_server(engine, port=0)
            port = http.sockets[0].getsockname()[1]

            st, _, body = await _req_http(
                port, "POST", "/v1/completions",
                payload={"model": 0, "prompt": [1, 2, 3], "max_tokens": 4})
            assert st == 200

            st, _, body = await _req_http(port, "GET", "/v1/slo")
            rep = json.loads(body)
            assert st == 200 and rep["configured"] is True
            assert rep["config"]["ttft_ms"] == 60_000.0
            assert len(rep["instances"]) == server.m
            # thresholds are 60 s: a smoke drain cannot violate them
            assert rep["instances"][0]["state"] == "ok"
            assert rep["instances"][0]["objectives"]["ttft"]["count"] > 0

            st, _, body = await _req_http(port, "GET", "/healthz")
            h = json.loads(body)
            assert st == 200
            assert h["slo"] == ["ok", "ok"]
            assert h["instance_health"] == ["healthy", "healthy"]

            st, _, body = await _req_http(port, "GET", "/v1/models")
            models = json.loads(body)["data"]
            assert [mm["slo"] for mm in models] == ["ok", "ok"]
            assert [mm["health"] for mm in models] == ["healthy", "healthy"]

            http.close()
            await http.wait_closed()

    asyncio.run(run())
