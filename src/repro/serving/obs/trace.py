"""Step-level tracing for the fused serving engine (DESIGN.md §6.5).

:class:`Tracer` records two things while it is on:

* **step spans on the profiler's clock** — :meth:`Tracer.span` opens a
  ``jax.profiler.TraceAnnotation`` (``serve.step``, ``serve.admit``,
  ``serve.prefill``, ``serve.decode.wait``, ...), so a ``jax.profiler``
  session shows where each engine step spends its host time on the same
  clock as the device's programs, and a device-idle gap can be laid
  against the host work that caused it;
* **events in a ring buffer** — one per device call (fused decode
  block, prefill chunk, slot scatter) with its dispatch time, and, for
  decode blocks, the time their tokens reached the host and the **gap**
  since the previous block's tokens did; plus grid occupancy, prefill
  lanes busy and chunk validity.  Every request leaves stamps at the
  program's boundaries (enqueue → submit → admit → prefill_done →
  first_token → finish/cancel), correlated by request id, so its TTFT
  splits into inbox, queued, prefill and first-block waits.

Turning it on adds no host synchronisation: a prefill chunk or scatter
is recorded when it is dispatched, and only decode blocks, which the
engine's own ``device_get`` settles in any case, carry a settled time.
A traced run therefore runs the schedule an untraced one does; the
device time of every call comes from the profiler's device trace.

Off by default and **free when off**: every call site guards on
``tracer.enabled`` before touching the tracer, so the disabled path
constructs no event or span objects, takes no locks, and reads no
clocks (tests assert that no tracer method runs).  When on, events
append to a bounded ``deque`` under a lock (the async frontend runs
steps on an executor thread while ``GET /debug/trace`` exports from the
event loop), so capture cost is O(1) per call and memory is capped by
``capacity``.  Stamps are ``time.perf_counter`` readings less
:attr:`Tracer.epoch`, so a reader can put them back on the clock the
clients use.

Exports:

* :meth:`Tracer.export_chrome` — Chrome-trace / Perfetto JSON
  (``chrome://tracing`` or https://ui.perfetto.dev): device calls on a
  ``device`` process (one track per call kind), request phases on a
  ``requests`` process (one track per request id),
* :meth:`Tracer.summary` — aggregates: decode dispatch-gap p50/p95,
  mean grid occupancy, idle-slot token-steps, prefill-lane occupancy,
  chunk validity.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation

DEFAULT_CAPACITY = 65536

# request stamps, in lifecycle order; consecutive stamps a request has
# become its phase spans (PHASES), a terminal stage also an instant
STAGES = ("enqueue", "submit", "admit", "prefill_done", "first_token",
          "finish", "cancel")
TERMINAL = ("finish", "cancel")
PHASES = {("enqueue", "submit"): "inbox",
          ("submit", "admit"): "queued",
          ("admit", "prefill_done"): "prefill",
          ("prefill_done", "first_token"): "first_block",
          ("first_token", "finish"): "decode",
          # stamps a request lacks (capture started mid-request, a
          # failure in an earlier phase) close with the next one it has
          ("prefill_done", "finish"): "decode",
          ("submit", "finish"): "request",
          ("admit", "finish"): "serve"}
# resilience stages (DESIGN.md §6.8): each occurrence renders as its
# own instant (a request can requeue more than once, a driver can
# restart more than once — these never collapse into lifecycle spans)
RECOVERY = ("requeue", "restart", "shed", "quarantine")

# what a call site enters when tracing is off: one shared no-op context
NOSPAN = contextlib.nullcontext()


@dataclasses.dataclass
class DeviceCallEvent:
    """One device call: a fused decode block, a prefill chunk call, or a
    prefill->grid slot scatter."""
    kind: str                  # "decode" | "prefill_chunk" | "scatter"
    t0: float                  # dispatch begin (tracer clock)
    t_dispatch: float          # dispatch returned (async call issued)
    t_settled: float | None    # decode: its tokens reached the host
    gap_s: float | None        # decode: host gap since the last block settled
    step: int                  # engine step counter at the call
    active: int = 0            # decoding (M, B) slots at the call
    capacity: int = 0          # M * B
    lanes_busy: int = 0        # prefill lanes mid-admission
    lanes: int = 0             # total prefill lanes
    valid_frac: float = 1.0    # real positions / padded positions (chunks)
    tokens: int = 0            # real tokens this call advanced
    pending: int = 0           # queued requests at the call
    decode_steps: int = 1      # scan steps fused into this call (decode
                               # blocks, DESIGN.md §6.6; 1 otherwise)


@dataclasses.dataclass
class RequestEvent:
    """One request stamp (a STAGES or RECOVERY entry), by request id."""
    rid: int
    stage: str
    t: float                   # clock reading less the tracer's epoch
    instance: int = -1
    status: str | None = None  # terminal stages: ok/cancelled/expired/...


class Tracer:
    """Step spans and a ring buffer of events; disabled until :meth:`start`.

    Call sites MUST guard on ``tracer.enabled`` — the methods themselves
    assume capture is on (that keeps the disabled hot path at one
    attribute read per guard)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 clock=time.perf_counter):
        self.enabled = False
        self.capacity = capacity
        self.clock = clock
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=capacity)
        self._epoch = 0.0          # clock at start(); event times relative
        self._last_settled: float | None = None
        self.dropped = 0           # events evicted by the ring bound

    # -- capture lifecycle ---------------------------------------------------

    def start(self) -> None:
        """Begin (or restart) capture; the ring and clock epoch reset so
        a fresh capture never mixes with a previous window."""
        with self._lock:
            self._events.clear()
            self.dropped = 0
            self._epoch = self.clock()
            self._last_settled = None
            self.enabled = True

    def stop(self) -> None:
        self.enabled = False

    @property
    def epoch(self) -> float:
        """The clock reading at :meth:`start`: an event's ``t`` (or
        ``t0``) plus this is the raw ``clock()`` reading it was taken at."""
        return self._epoch

    def __len__(self) -> int:
        return len(self._events)

    def _append(self, ev) -> None:
        with self._lock:
            if len(self._events) == self.capacity:
                self.dropped += 1
            self._events.append(ev)

    # -- recording (call only when ``enabled``) ------------------------------

    def span(self, name: str, **tags) -> TraceAnnotation:
        """A host span on the profiler's clock, for a ``with`` block.
        Keyword tags become the span's stats in the trace; more can be
        added once known with ``set_metadata``."""
        return TraceAnnotation(name, **tags)

    def device_call(self, kind: str, t0: float, t_dispatch: float,
                    t_settled: float | None = None, *, step: int = 0,
                    active: int = 0, capacity: int = 0, lanes_busy: int = 0,
                    lanes: int = 0, valid_frac: float = 1.0, tokens: int = 0,
                    pending: int = 0, decode_steps: int = 1) -> None:
        """Record one device call; timestamps are raw ``clock()`` reads
        (the tracer rebases them onto its epoch).  ``t_settled`` is given
        for decode blocks only, whose tokens the engine waits for."""
        gap = settled = None
        if t_settled is not None:
            last = self._last_settled
            self._last_settled = t_settled
            gap = (t0 - last) if last is not None else 0.0
            settled = t_settled - self._epoch
        self._append(DeviceCallEvent(
            kind, t0 - self._epoch, t_dispatch - self._epoch, settled,
            gap_s=gap, step=step, active=active, capacity=capacity,
            lanes_busy=lanes_busy, lanes=lanes, valid_frac=valid_frac,
            tokens=tokens, pending=pending, decode_steps=decode_steps,
        ))

    def request_event(self, rid: int, stage: str, *, instance: int = -1,
                      status: str | None = None,
                      t: float | None = None) -> None:
        """Stamp ``stage`` for request ``rid`` now, or at ``t`` (a raw
        ``clock()`` reading taken earlier, e.g. by the frontend)."""
        self._append(RequestEvent(
            rid, stage, (self.clock() if t is None else t) - self._epoch,
            instance, status))

    # -- export --------------------------------------------------------------

    def events(self) -> list:
        """The captured events, oldest first (a copy)."""
        with self._lock:
            return list(self._events)

    def export_chrome(self) -> dict:
        """The capture as Chrome-trace JSON (the ``traceEvents`` array
        format Perfetto and ``chrome://tracing`` load directly).

        Device calls render as complete ("X") slices on pid 0, one tid
        per call kind, from dispatch to settle for decode blocks and over
        the dispatch for the others, with occupancy in ``args``; request
        stamps render on pid 1, one tid per request id, as one slice per
        phase between consecutive stamps (inbox / queued / prefill /
        first_block / decode) plus an instant ("i") event at terminal
        stages."""
        us = lambda t: t * 1e6
        kinds: dict[str, int] = {}
        events: list[dict] = [
            {"name": "process_name", "ph": "M", "pid": 0,
             "args": {"name": "device"}},
            {"name": "process_name", "ph": "M", "pid": 1,
             "args": {"name": "requests"}},
        ]
        marks: dict[int, dict[str, RequestEvent]] = {}
        for ev in self.events():
            if isinstance(ev, DeviceCallEvent):
                tid = kinds.setdefault(ev.kind, len(kinds))
                end = ev.t_dispatch if ev.t_settled is None else ev.t_settled
                args = {
                    "step": ev.step,
                    "dispatch_ms": 1e3 * (ev.t_dispatch - ev.t0),
                    "active_slots": ev.active,
                    "slot_capacity": ev.capacity,
                    "occupancy": (ev.active / ev.capacity
                                  if ev.capacity else 0.0),
                    "lanes_busy": ev.lanes_busy,
                    "lanes": ev.lanes,
                    "valid_frac": ev.valid_frac,
                    "tokens": ev.tokens,
                    "pending": ev.pending,
                    "decode_steps": ev.decode_steps,
                }
                if ev.t_settled is not None:
                    args["settled_ms"] = 1e3 * (ev.t_settled - ev.t0)
                    args["gap_ms"] = 1e3 * ev.gap_s
                events.append({
                    "name": ev.kind, "ph": "X", "cat": "device",
                    "pid": 0, "tid": tid,
                    "ts": us(ev.t0), "dur": max(us(end - ev.t0), 0.0),
                    "args": args,
                })
            elif ev.stage in RECOVERY:
                # rendered immediately (not via marks): every
                # occurrence is its own instant, and rid -1 (driver
                # restarts) is not a request lifecycle
                events.append({
                    "name": (f"{ev.stage}:{ev.status}" if ev.status
                             else ev.stage),
                    "ph": "i", "cat": "resilience", "pid": 1,
                    "tid": ev.rid, "ts": us(ev.t), "s": "t",
                    "args": {"request_id": ev.rid,
                             "instance": ev.instance},
                })
            else:
                marks.setdefault(ev.rid, {})[ev.stage] = ev
        for tid, kind in sorted((v, k) for k, v in kinds.items()):
            events.append({"name": "thread_name", "ph": "M", "pid": 0,
                           "tid": tid, "args": {"name": kind}})
        for rid, stages in marks.items():
            order = [s for s in STAGES if s in stages]
            for a, b in zip(order, order[1:]):
                ea, eb = stages[a], stages[b]
                events.append({
                    "name": ("cancelled" if b == "cancel"
                             else PHASES.get((a, b), f"{a}->{b}")),
                    "ph": "X", "cat": "request", "pid": 1, "tid": rid,
                    "ts": us(ea.t), "dur": max(us(eb.t - ea.t), 0.0),
                    "args": {"request_id": rid, "instance": eb.instance
                             if eb.instance >= 0 else ea.instance},
                })
            for s in TERMINAL:
                if s in stages:
                    ev = stages[s]
                    events.append({
                        "name": f"{s}:{ev.status or 'ok'}", "ph": "i",
                        "cat": "request", "pid": 1, "tid": rid,
                        "ts": us(ev.t), "s": "t",
                        "args": {"request_id": rid, "status": ev.status},
                    })
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped}}

    def summary(self) -> dict:
        """Aggregate the capture: the figures BENCH_serve.json records
        and ``perf_delta --serve`` diffs across PRs."""
        # local import: metrics.py imports obs.slo at module scope, so a
        # module-level import here would close an import cycle through
        # the obs package __init__
        from repro.serving.metrics import percentiles
        calls = [e for e in self.events()
                 if isinstance(e, DeviceCallEvent)]
        decodes = [e for e in calls if e.kind == "decode"]
        chunks = [e for e in calls if e.kind == "prefill_chunk"]
        # host time between decode blocks (the first of a capture has no
        # predecessor: gap 0 by construction, harmless in the percentiles)
        gaps = [e.gap_s for e in decodes]
        occ = [e.active / e.capacity for e in decodes if e.capacity]
        decode_tokens = sum(e.tokens for e in decodes)
        out = {
            "device_calls": len(calls),
            "decode_steps": len(decodes),   # decode device calls (blocks)
            # multi-step decode (DESIGN.md §6.6): scan steps fused into
            # those calls, and the per-TOKEN dispatch cost — the figure
            # K-fold amortization actually improves (per-CALL overhead
            # stays flat while each call yields up to K*occupancy tokens)
            "decode_scan_steps": sum(e.decode_steps for e in decodes),
            "mean_decode_steps_per_call": (
                sum(e.decode_steps for e in decodes) / len(decodes)
                if decodes else 0.0),
            "dispatch_overhead_per_token_ms": (
                1e3 * sum(gaps) / decode_tokens
                if decode_tokens else None),
            "prefill_chunks": len(chunks),
            "scatters": sum(1 for e in calls if e.kind == "scatter"),
            # host time between decode blocks — the per-step overhead the
            # megakernel/multi-step-decode work must attack
            "dispatch_overhead_ms": percentiles(gaps),
            "mean_dispatch_gap_ms": (
                1e3 * sum(gaps) / len(gaps) if gaps else 0.0),
            "settled_ms": percentiles(
                [e.t_settled - e.t0 for e in decodes]),
            # the utilization claim: decoding slots / grid capacity
            "mean_grid_occupancy": sum(occ) / len(occ) if occ else 0.0,
            # slot-steps the fused program computed for nobody (an idle
            # lane still rides every fused step)
            "idle_slot_token_steps": sum(
                e.capacity - e.active for e in decodes),
            "mean_prefill_lane_occupancy": (
                sum(e.lanes_busy / e.lanes for e in chunks if e.lanes)
                / len(chunks) if chunks else 0.0),
            "mean_chunk_validity": (
                sum(e.valid_frac for e in chunks) / len(chunks)
                if chunks else 0.0),
            "dropped_events": self.dropped,
        }
        return out
