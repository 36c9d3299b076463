"""Instrumentation of a traced run, from the benchmark's own files.

``Probe.install`` wraps, on the server instance, the calls at each layer
boundary in ``jax.profiler.TraceAnnotation`` host spans (which land in the
profiler's trace beside the device's programs) and records what the
per-layer metrics need:

* ``bench.submit``    ``MultiModelServer.try_submit``, where an
                      ``AsyncEngine.submit`` lands between engine steps;
* ``bench.step``      ``MultiModelServer.step``, one engine step;
* ``bench.prefill``   ``server.prefill.advance``, the chunked-prefill calls
                      of a step, with the positions each lane advanced;
* ``bench.decode``    the engine's single ``_step`` dispatch callable (one
                      fused decode block), with the positions each live
                      lane decoded;
* admission           ``server.prefill.start``, which ``_admit`` calls once
                      per admitted request: the admission time.

Untraced runs install nothing: their end-to-end metrics are taken with
tracing off.
"""
from __future__ import annotations

import time

import jax
import numpy as np

class Probe:
    def __init__(self, server):
        self.server = server
        self.due: dict[int, float] = {}        # id(Request) -> due time
        self.admits: list[tuple[float, float]] = []      # (admitted, due)
        # per decode block: (t, [(instances live, lanes live, ctx sum)] per step)
        self.decode: list[tuple[float, list[tuple[int, int, int]]]] = []
        # per prefill advance: (t, tokens, ctx sum)
        self.prefill: list[tuple[float, int, int]] = []
        self.counters: list[dict] = []
        self.t_open = self.t_close = None
        self._window = None

    def install(self) -> None:
        s, ann = self.server, jax.profiler.TraceAnnotation

        def spanned(name, fn):
            def call(*a, **kw):
                with ann(name):
                    return fn(*a, **kw)
            return call

        s.step = spanned("bench.step", s.step)
        s.try_submit = spanned("bench.submit", s.try_submit)
        s._step = self._decode(spanned("bench.decode", s._step))
        s.prefill.advance = self._advance(
            spanned("bench.prefill", s.prefill.advance))
        start = s.prefill.start

        def admit(req):
            self.admits.append((time.perf_counter(),
                                self.due.get(id(req), float("nan"))))
            return start(req)

        s.prefill.start = admit

    def _decode(self, fn):
        s = self.server

        def call(*a, **kw):
            t = time.perf_counter()
            pos = s.pos.copy()
            out = fn(*a, **kw)
            emitted = np.asarray(jax.device_get(out[1]))      # (k, M, B)
            steps = []
            for j, live in enumerate(emitted):
                steps.append((int(live.any(axis=1).sum()), int(live.sum()),
                              int((pos[live] + j + 1).sum())))
            self.decode.append((t, steps))
            return out
        return call

    def _advance(self, fn):
        lanes = self.server.prefill._lanes

        def call(*a, **kw):
            t = time.perf_counter()
            before = [(l.req, l.next_pos) for l in lanes]
            out = fn(*a, **kw)
            tokens = ctx = 0
            for l, (req, a0) in zip(lanes, before):
                if req is None:
                    continue
                b0 = l.next_pos
                tokens += b0 - a0
                ctx += (b0 * (b0 + 1) - a0 * (a0 + 1)) // 2
            self.prefill.append((t, tokens, ctx))
            return out
        return call

    def _count(self) -> dict:
        m = self.server.metrics
        return {"decode_steps": m.decode_steps, "decode_tokens": m.decode_tokens,
                "prefill_tokens": m.prefill_tokens,
                "prefill_calls": self.server.prefill.device_calls}

    # open/close run on the engine's driver between steps
    # (AsyncEngine.run_in_step_gap), so counters are read whole
    def open(self) -> None:
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()
        self.counters.append(self._count())
        self.t_open = time.perf_counter()

    def close(self) -> None:
        self.t_close = time.perf_counter()
        self.counters.append(self._count())
        self._window.__exit__(None, None, None)

    def delta(self) -> dict:
        a, b = self.counters
        return {k: b[k] - a[k] for k in a}

    def inside(self, t: float) -> bool:
        return self.t_open <= t <= self.t_close
