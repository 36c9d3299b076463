"""The measured window: the traffic plan driven through the program's
``AsyncEngine`` by client tasks on one event loop, every time taken on the
client's side with ``time.perf_counter``.

Open loop: each request is sent at its due time, whatever the server does,
and is timed from when it was due.  Closed loop: each client sends its next
request when the previous reply is complete; a request is due when it is
sent.  When the window closes, the live requests are cancelled; what was
not received by then does not count as received.
"""
from __future__ import annotations

import asyncio
import dataclasses
import time

import numpy as np

from bench import program
from bench.traffic import Plan, Req


@dataclasses.dataclass
class Record:
    """One request as its client saw it."""
    req: Req
    due: float
    sent: float = float("nan")
    times: list[float] = dataclasses.field(default_factory=list)
    tokens: list[int] = dataclasses.field(default_factory=list)
    status: str = "pending"
    engine_tokens: list[int] | None = None


@dataclasses.dataclass
class Window:
    records: list[Record]
    t_open: float
    t_close: float


async def _sleep_until(t: float) -> None:
    delay = t - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)


async def _serve(engine, rec: Record, probe) -> None:
    req = program.Request(instance=rec.req.tenant, prompt=list(rec.req.prompt),
                          max_new_tokens=rec.req.max_new)
    if probe is not None:
        probe.due[id(req)] = rec.due
    rec.sent = time.perf_counter()
    try:
        stream = await engine.submit(req)
    except program.EngineClosed:        # sent as the window closed
        rec.status = "closed"
        return
    async for tok in stream:
        rec.times.append(time.perf_counter())
        rec.tokens.append(tok)
    res = await stream.result()
    rec.status, rec.engine_tokens = res.status, list(res.tokens)


async def _open_loop(engine, plan: Plan, t0: float, records, tasks, probe):
    for r in plan.requests:
        rec = Record(r, due=t0 + r.due)
        await _sleep_until(rec.due)
        records.append(rec)
        tasks.append(asyncio.ensure_future(_serve(engine, rec, probe)))


async def _client(engine, seq: tuple[Req, ...], t_end: float, records, probe):
    i = 0
    while time.perf_counter() < t_end:
        rec = Record(seq[i % len(seq)], due=time.perf_counter())
        i += 1
        records.append(rec)
        await _serve(engine, rec, probe)


async def _traced(engine, probe, start: float, stop: float, logdir: str):
    import jax

    loop = asyncio.get_running_loop()
    # host spans (TraceAnnotation) and device activity; no Python tracer,
    # which would slow every host function of the engine
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    await _sleep_until(start)
    await loop.run_in_executor(
        None, lambda: jax.profiler.start_trace(logdir,
                                               profiler_options=options))
    await engine.run_in_step_gap(probe.open)
    await _sleep_until(stop)
    await engine.run_in_step_gap(probe.close)
    await loop.run_in_executor(None, jax.profiler.stop_trace)


def trace_span(seconds: float) -> tuple[float, float]:
    """(start, stop) of the traced part of a window, in seconds after it
    opens: the middle of the window, at most 10 seconds of it."""
    length = min(10.0, 0.5 * seconds)
    start = 0.5 * (seconds - length)
    return start, start + length


async def _window(server, plan: Plan, seconds: float, probe, logdir):
    engine = program.AsyncEngine(server)
    records: list[Record] = []
    tasks: list[asyncio.Future] = []
    t0 = time.perf_counter()
    t_end = t0 + seconds
    if plan.loop == "open":
        drivers = [asyncio.ensure_future(
            _open_loop(engine, plan, t0, records, tasks, probe))]
    else:
        drivers = [asyncio.ensure_future(_client(engine, seq, t_end, records,
                                                 probe))
                   for seq in plan.clients]
    tracer = None
    if probe is not None:
        a, b = trace_span(seconds)
        tracer = asyncio.ensure_future(
            _traced(engine, probe, t0 + a, t0 + b, logdir))
    await _sleep_until(t_end)
    t_close = time.perf_counter()
    if tracer is not None:
        await tracer
    await engine.aclose(drain=False)
    await asyncio.gather(*drivers)
    await asyncio.gather(*tasks)
    return Window(records, t0, t_close)


def run(server, plan: Plan, seconds: float, *, probe=None,
        logdir: str | None = None) -> Window:
    return asyncio.run(_window(server, plan, seconds, probe, logdir))


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def end_to_end(w: Window) -> dict:
    """Client-side metrics of the window (seconds and counts, unscaled)."""
    close, span = w.t_close, w.t_close - w.t_open
    due = [r for r in w.records if r.due < close]
    ttft, gaps, tokens = [], [], 0
    for r in due:
        got = [t for t in r.times if t <= close]
        tokens += len(got)
        # a request with no first token by the close counts with its wait
        ttft.append((got[0] if got else close) - r.due)
        gaps.extend(np.diff(got).tolist())
    lateness = [r.sent - r.due for r in due if r.sent == r.sent]
    return {
        "attempted": len(due),
        "failed": sum(r.status not in ("ok", "cancelled", "pending", "closed")
                      for r in due),
        "finished": sum(r.status == "ok" and r.times[-1] <= close
                        for r in due if r.times),
        "tokens": tokens,
        "window_s": span,
        "ttft_p90_s": percentile(ttft, 90) if ttft else float("nan"),
        "itl_p95_s": percentile(gaps, 95) if gaps else float("nan"),
        "tokens_per_s": tokens / span,
        "itl_samples": len(gaps),
        "send_late_p99_s": percentile(lateness, 99) if lateness else 0.0,
        "send_late_max_s": max(lateness, default=0.0),
    }
