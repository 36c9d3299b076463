"""Multi-model serving engine — the paper's deployment scenario.

M fine-tuned instances of one architecture are NetFuse-merged and served
from a single fused program.  The engine owns a fixed (M, B) slot grid
of per-slot decode state and composes four subsystems:

* ``scheduler.py`` — policy-driven admission (fifo / round-robin /
  token-budget fairness) over per-instance request queues (different
  tasks have different input streams — paper §2.1),
* ``prefill.py`` — the unified chunked-prefill runtime: every prompt
  (any family, any length) streams through the family's chainable
  ``api.prefill_chunk`` in fixed-size chunks — ONE compiled shape per
  family (the final partial chunk is padded and masked per position:
  tail folding, DESIGN.md §6.3) — with up to ``prefill_lanes`` requests
  sharing one donated carry tree via an on-device weight-row gather.
  The engine grants the runtime a per-step ``chunk_budget``, so prefill
  work interleaves with decode steps instead of stalling the grid while
  a long prompt admits,
* ``sampling.py`` — greedy/temperature/top-k sampling over the whole
  (M, B) logits grid, fused into the SAME jitted program as the decode
  step: an engine step is exactly ONE device call, with zero per-slot
  host round-trips,
* ``metrics.py`` — per-instance throughput/latency/queue counters.

Multi-step decode (DESIGN.md §6.6): the fused device call is a
``lax.scan`` of up to ``decode_steps`` (K) decode+sample steps over the
whole grid — ONE dispatch returns a (K, M, B) token block, amortizing
per-launch overhead K-fold on top of the paper's M-fold merge.  Stop
conditions live on-device: the scan carries a per-slot alive mask, and
a lane that hits EOS / ``max_new_tokens`` / ``max_context`` mid-block
freezes — its token and position stop advancing and its cache writes
are masked (``tree_select_slots``, mirroring the tail-folding ``valid``
machinery) — so K=1 and K>1 greedy streams are bit-identical per
request.  The historical one-call-per-*step* invariant is thus now
one-call-per-*block*: ``step()`` still makes exactly one fused decode
dispatch, but unrolls the block on the host so per-token ``on_token``
callbacks, metrics, scheduler accounting and finish detection keep
their per-token semantics.  An adaptive policy shrinks the horizon
(k=1 while prefill lanes are in flight; the largest power of two that
no decoding slot overshoots while requests wait in queue) so
multi-step decode never starves the chunked-prefill interleave or
holds freed slots hostage — at most log2(K)+1 compiled block shapes.

Mesh-parametric execution: pass ``mesh=`` (and optionally ``rules=``) to
run the WHOLE serving path — slot surgery, chunked prefill, the fused
decode+sample step, metrics — under an explicit ``jax.sharding.Mesh``
with the instances/batch axes data-parallel and heads/cache_seq tensor-
parallel (the logical-axis rules in ``launch/shardings.py``).  Params
are ``jax.device_put`` once at init and the grid cache is built in
place, both with per-leaf ``NamedSharding``; every jit traces under the mesh + rules context so
the model zoo's ``constrain`` calls and the shard-safe slot surgery
(``models/common.tree_take_slot``/``tree_put_slot``) pin layouts — no
host gathers anywhere in the steady state.  ``mesh=None`` (default) is
bit-for-bit today's single-device path.

Every servable family works at slot granularity: uniform-KVCache stacks
(dense / moe / vlm / audio) and recurrent-state families (ssm / hybrid)
both go through the axes-driven slot surgery in ``api.take_state`` /
``api.put_state``, so slots finish independently (EOS / max_new_tokens)
and are refilled from the queues — continuous batching at slot
granularity; the decode path masks stale cache positions and idle slots
simply sample into a discarded lane.

The loop is synchronous and single-caller by design; concurrent clients,
per-request token streams, cancellation and HTTP live one layer up in
``serving/frontend`` (the ``AsyncEngine`` owns this engine's step loop
on a background driver and consumes the ``on_token`` hook, ``cancel``
and ``try_submit`` — DESIGN.md §6.4).
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro import api
from repro.launch.compat import mesh_context
from repro.models import common as C
from repro.serving.metrics import ServerMetrics
from repro.serving.obs.accounting import TenantAccounting
from repro.serving.obs.flight import FlightRecorder
from repro.serving.obs.trace import NOSPAN, Tracer
from repro.serving.prefill import ChunkedPrefill
from repro.serving.resilience.faults import FaultInjector
from repro.serving.resilience.health import HealthMonitor
from repro.serving.sampling import make_grid_sampler
from repro.serving.scheduler import Request, Result, Scheduler, make_scheduler

SERVABLE_FAMILIES = ("dense", "moe", "vlm", "audio", "ssm", "hybrid")


class MultiModelServer:
    """Continuous-batching decode over an (M, B) slot grid."""

    def __init__(
        self,
        cfg,
        params,                    # merged params (instances axis = M)
        *,
        slots_per_instance: int,
        max_context: int,
        eos_id: int | None = None,
        temperature: float = 0.0,
        top_k: int = 0,
        seed: int = 0,
        scheduler: str | Scheduler = "fifo",
        prefill_chunk: int = 32,
        prefill_lanes: int = 4,
        chunk_budget: int = 4,
        tail_fold: bool = True,
        decode_steps: int = 1,
        adaptive_horizon: bool = True,
        donate: bool | None = None,
        mesh=None,
        rules=None,
        tracer: Tracer | None = None,
        faults: FaultInjector | None = None,
        health: HealthMonitor | None = None,
        policy=None,
        accounting=None,
        flight=None,
        slo=None,
    ):
        assert cfg.family in SERVABLE_FAMILIES, cfg.family
        if cfg.family == "hybrid":
            from repro.models import hybrid as H
            need = H.min_serving_context(cfg)
            assert max_context >= need, (
                f"hybrid serving needs max_context >= meta+window = {need}, "
                f"got {max_context}"
            )
        self.cfg = cfg
        self.m = cfg.num_instances
        self.b = slots_per_instance
        self.max_context = max_context
        self.eos_id = eos_id

        from repro.launch.shardings import default_serve_rules
        self.mesh = mesh
        self.rules = default_serve_rules(mesh, rules)

        self.scheduler = (
            make_scheduler(scheduler, self.m, mesh=mesh, rules=self.rules)
            if isinstance(scheduler, str) else scheduler
        )
        # per-instance SLO objectives (§6.9) ride the metrics layer:
        # evaluation is lazy (snapshot-time only), so a configured SLO
        # costs nothing per token
        self.slo = slo
        self.metrics = ServerMetrics(self.m, mesh=mesh, slo=slo)
        # step tracer (DESIGN.md §6.5): always attached, OFF by default —
        # every hot-path call site guards on ``tracer.enabled``, so the
        # disabled path reads one attribute and constructs nothing
        self.tracer = tracer if tracer is not None else Tracer()
        # per-tenant attribution (§6.9): same discipline — always
        # attached, OFF until .start(), every site guards on .enabled
        self.accounting = (accounting if accounting is not None
                           else TenantAccounting(self.m))
        self.accounting.m = self.m
        # crash flight recorder (§6.9): enabled iff a directory is set
        self.flight = flight if flight is not None else FlightRecorder()
        # fault injection (DESIGN.md §6.8): same discipline as the tracer
        # — always attached, disarmed by default, and every call site
        # guards on ``faults.armed`` so the disarmed path runs zero
        # injector code
        self.faults = faults if faults is not None else FaultInjector()
        # per-instance health states (always on — plain counters)
        self.health = health if health is not None else HealthMonitor(self.m)
        # overload brownout policy (optional: None = no shedding/capping)
        self.policy = policy
        # terminal Results produced while an exception was propagating
        # (e.g. a donated scatter failure) — delivered on the next step
        self._pending_failures: list[Result] = []
        self.prefill = ChunkedPrefill(
            cfg, max_context=max_context, chunk=prefill_chunk,
            lanes=prefill_lanes, metrics=self.metrics,
            mesh=mesh, rules=self.rules,
            tail_fold=tail_fold, donate=donate, tracer=self.tracer,
            accounting=self.accounting,
        )
        self.metrics.compiled_shapes_fn = \
            lambda: self.prefill.compiled_shapes
        self.chunk_budget = max(1, chunk_budget)

        self.params = params
        self.cache = self._fresh_cache()
        self._grid_shard = self._rep_shard = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.launch.shardings import tree_shardings
            # per-leaf NamedSharding for params, device_put ONCE —
            # everything downstream consumes committed, rules-conformant
            # arrays
            self.params = jax.device_put(
                params, tree_shardings(self.rules, api.axes(cfg), params)
            )
            self._grid_shard = NamedSharding(
                mesh, self.rules.spec(("instances", "batch"), (self.m, self.b))
            )
            self._rep_shard = NamedSharding(mesh, P())
        self.pos = np.zeros((self.m, self.b), np.int32)
        self.cur_tok = np.zeros((self.m, self.b), np.int32)
        self.slot_busy = np.zeros((self.m, self.b), bool)
        # reserved for a request still prefilling: busy (not admittable)
        # but not yet decoding — the fused grid step treats it as an
        # idle lane until the chunk runtime delivers its cache rows
        self.slot_prefilling = np.zeros((self.m, self.b), bool)
        self._reserved: dict[int, tuple[int, int]] = {}   # request_id -> slot
        self.active: list[list[Request | None]] = [
            [None] * self.b for _ in range(self.m)
        ]
        self.generated: dict[int, list[int]] = {}
        self.steps = 0
        self._req_counter = 0
        # per-token emission hook for streaming frontends: called as
        # on_token(request_id, token, finished) for every decoding slot
        # right after the fused step's tokens land on the host — the
        # async frontend buffers these and fans them out to per-request
        # streams.  Host-side only; the device program never changes
        self.on_token = None
        self._seed = seed
        self._key = jax.random.PRNGKey(seed)
        if mesh is not None:
            self._key = jax.device_put(self._key, self._rep_shard)
        self.metrics.health_fn = self.health.snapshot
        self.metrics.accounting_fn = self.accounting.snapshot
        # interference attribution: the accounting layer asks the
        # scheduler who is waiting at each settled device call
        self.accounting.queued_fn = self.scheduler.queued_instances
        # flight recorder on fresh quarantine transitions (§6.9); the
        # supervisor hooks crash/watchdog/give-up itself
        if self.flight.enabled:
            self.health.on_quarantine = lambda i: self.flight.dump(
                f"quarantine: instance {i}", server=self)

        self._sample = make_grid_sampler(temperature, top_k)
        # temperature<=0 sampling is key-independent argmax, so the
        # megakernel path may fuse it on-device (decode_step_sample: one
        # final-norm+logits+argmax kernel instead of a (M,B,V) logits
        # round-trip through the XLA sampler)
        self._greedy = temperature <= 0
        self._cache_ax = api.cache_axes(cfg)
        self.decode_steps = max(1, int(decode_steps))
        self.adaptive_horizon = adaptive_horizon

        # one compiled block program per horizon k actually used (full K
        # plus the adaptive policy's smaller powers of two: <= log2(K)+1)
        self._block_fns: dict[int, callable] = {}

        def _dispatch(params, cache, tok, pos, key, alive, remaining, k):
            fn = self._block_fns.get(k)
            if fn is None:
                fn = self._block_fns[k] = self._make_block(k)
            return fn(params, cache, tok, pos, key, alive, remaining)

        # ONE callable invoked exactly once per engine step — tests wrap
        # it to count device dispatches; it routes to the per-k jit
        self._step = _dispatch
        donate = self.prefill.donate
        self._scatter = jax.jit(
            lambda grid, src, i, mm, bb: api.put_state(
                cfg, grid, api.take_state(cfg, src, i, 0), mm, bb
            ),
            donate_argnums=(0,) if donate else (),
        )

    def _make_block(self, k: int):
        """Build the jitted K-step fused decode+sample block: a
        ``lax.scan`` of ``k`` decode steps over the (M, B) grid inside
        one device call, with on-device stop handling.

        Carry: (tok, pos, cache, key, alive, remaining).  Each scan step
        decodes + samples the whole grid, then masks dead lanes: their
        token/position/budget freeze (``jnp.where``) and — for k > 1 —
        their cache writes are reverted (``tree_select_slots``), so a
        lane stopping mid-block leaves cache and position exactly as the
        one-call-per-token protocol would.  Stop mirrors the host finish
        logic bit-for-bit: budget exhausted (remaining), EOS, or
        position reaching ``max_context - 1``.  Returns the (k, M, B)
        token block, the (k, M, B) emitted mask (alive at entry of each
        scan step — exactly the tokens the host unroll consumes), the
        (k, M, B) finite-logits mask (the NaN/Inf guard: False where an
        instance's logits went non-finite, so the host can quarantine
        that row instead of streaming garbage; the fused megakernel
        path never materializes logits, so it reports all-True), the
        cache, and the advanced key (one split per scan step, so K=1
        reproduces the historical per-call split sequence)."""
        cfg, eos_id, max_context = self.cfg, self.eos_id, self.max_context
        sample, cache_ax = self._sample, self._cache_ax
        # greedy + megakernel: decode and sample fused on-device; the key
        # split below still runs so the key sequence (and thus any
        # temperature>0 rerun from a checkpointed key) is path-invariant
        fused_sample = self._greedy and getattr(cfg, "use_pallas_kernels", False)

        def _block_impl(params, cache, tok, pos, key, alive, remaining):
            def body(carry, _):
                tok, pos, cache, key, alive, remaining = carry
                if fused_sample:
                    picked, new_cache = api.decode_step_sample(
                        cfg, params, cache, tok[..., None], pos
                    )
                    ok = jnp.ones_like(alive)
                else:
                    logits, new_cache = api.decode_step(
                        cfg, params, cache, tok[..., None], pos
                    )
                    ok = jnp.all(
                        jnp.isfinite(logits), axis=-1
                    ).reshape(alive.shape)
                if k > 1:
                    # freeze stopped lanes' state between scan steps (at
                    # k == 1 every junk write is overwritten by scatter
                    # before the slot decodes again — the historical
                    # protocol — so the masking would be dead weight)
                    new_cache = C.tree_select_slots(
                        alive, new_cache, cache, cache_ax
                    )
                # pin the grid cache to the rules' layout across steps
                # (no-op without active rules), so donation reuses the
                # buffers and the layout never drifts from the
                # init-time device_put
                new_cache = C.constrain_tree(new_cache, cache_ax)
                key, sub = jax.random.split(key)
                nxt = jnp.where(
                    alive, picked if fused_sample else sample(logits, sub), tok
                )
                new_pos = jnp.where(alive, pos + 1, pos)
                new_rem = jnp.where(alive, remaining - 1, remaining)
                stop = (new_rem <= 0) | (new_pos >= max_context - 1)
                if eos_id is not None:
                    stop = stop | (nxt == eos_id)
                new_carry = (nxt, new_pos, new_cache, key,
                             alive & ~stop, new_rem)
                return new_carry, (nxt, alive, ok)

            carry = (tok, pos, cache, key, alive, remaining)
            (_, _, cache, key, _, _), (toks, emitted, oks) = jax.lax.scan(
                body, carry, None, length=k
            )
            return toks, emitted, oks, cache, key

        # donate the grid cache so decode updates in place instead of
        # copying the whole (M, B, max_context) grid (skipped on CPU,
        # where XLA can't honor it and jit warns; ``donate=`` overrides —
        # the donation-parity tests force it on to prove the donated
        # program never reads an invalidated buffer)
        return jax.jit(
            _block_impl,
            donate_argnums=(1,) if self.prefill.donate else (),
        )

    def _fresh_cache(self):
        """An empty grid cache.  On a mesh it is built in place with its
        per-leaf NamedSharding: built whole on one device first, it can
        exceed that device's memory."""
        make = lambda: api.make_cache(self.cfg, self.m, self.b,
                                      self.max_context)
        if self.mesh is None:
            return make()
        from repro.launch.shardings import tree_shardings
        shardings = tree_shardings(self.rules, api.cache_axes(self.cfg),
                                   jax.eval_shape(make))
        with self._ctx():
            return jax.jit(make, out_shardings=shardings)()

    def _ctx(self):
        """Mesh + rules context for every trace/dispatch (no-op without a
        mesh — jit still traces, just with no active rules)."""
        return mesh_context(self.mesh, self.rules)

    # -- request admission ---------------------------------------------------

    def validate(self, req: Request) -> str | None:
        """The ONE admission-validation path: every reason a request can
        never be served is decided here, before it touches a queue, so
        both submit flavors (raise vs terminal Result) agree exactly."""
        if not 0 <= req.instance < self.m:
            return f"instance {req.instance} out of range [0, {self.m})"
        if not req.prompt:
            return "empty prompt"
        # chunked prefill is length-agnostic: anything whose positions
        # (learned prefix + prompt) fit the serving context is accepted;
        # past that the cache physically cannot hold the prompt
        if len(req.prompt) > self.prefill.max_prompt_len():
            return (
                f"prompt of {len(req.prompt)} tokens exceeds the serving "
                f"context: at most {self.prefill.max_prompt_len()} prompt "
                f"tokens fit max_context={self.max_context}"
            )
        if req.max_new_tokens < 1:
            return f"max_new_tokens must be >= 1, got {req.max_new_tokens}"
        return None

    def try_submit(self, req: Request, *,
                   submit_time: float | None = None) -> int | Result:
        """Queue ``req`` and return its request_id, or — when validation
        fails — return a terminal ``Result(status="rejected")`` instead
        of raising.  Rejected requests still get a request_id and a
        Result, exactly like cancelled/expired ones, so a frontend can
        answer every submission with the same terminal object.

        ``submit_time`` lets a frontend that queues commands ahead of
        the engine (AsyncEngine) pass the CLIENT's clock, so
        TTFT/latency include backpressure parking and command-queue
        wait; without it the stamp is taken here (always — a reused
        Request object never carries a stale epoch into the metrics)."""
        req.request_id = self._req_counter
        self._req_counter += 1
        req.submit_time = (
            submit_time if submit_time is not None else time.perf_counter()
        )
        err = self.validate(req)
        if err is not None:
            self.metrics.note_reject(req.instance)
            return Result(
                req.request_id, req.instance, [],
                prompt_len=len(req.prompt) if req.prompt else 0,
                status="rejected", error=err,
            )
        # a quarantined instance row 503s only its own tenant: the other
        # M-1 instances keep admitting (DESIGN.md §6.8)
        if not self.health.admissible(req.instance):
            self.metrics.note_reject(req.instance)
            return Result(
                req.request_id, req.instance, [],
                prompt_len=len(req.prompt),
                status="unavailable",
                error=f"instance {req.instance} is quarantined "
                      f"({self.health.state(req.instance)}); retry later",
            )
        if self.policy is not None:
            self.policy.cap_request(req)     # brownout: shorter answers
        self.scheduler.submit(req)
        self.metrics.note_submit(req.instance)
        if self.tracer.enabled:
            # enqueue: when the client handed the request over (the
            # frontend's epoch); submit: when it reached the queue
            self.tracer.request_event(req.request_id, "enqueue",
                                      instance=req.instance,
                                      t=req.submit_time)
            self.tracer.request_event(req.request_id, "submit",
                                      instance=req.instance)
        return req.request_id

    def submit(self, req: Request) -> int:
        out = self.try_submit(req)
        if isinstance(out, Result):
            raise ValueError(out.error)
        return out

    # -- cancellation / eviction ---------------------------------------------

    def cancel(self, request_id: int, *, status: str = "cancelled") -> Result | None:
        """Abort a request wherever it is in its lifecycle and return its
        terminal Result (partial tokens included), or None if it is not
        live (already finished, rejected, or unknown).

        * queued      — removed from its scheduler queue (never charged),
        * prefilling  — its prefill lane is evicted and its reserved grid
                        slot freed; both are reusable on the next step,
        * decoding    — its slot is freed (the fused grid step treats it
                        as an idle lane; its stale cache rows are masked)
                        and refilled from the queues on the next step.

        Host-side bookkeeping only: no device call, no new compiled
        shape, and the one-device-call-per-step invariant is untouched.
        """
        req = self.scheduler.cancel(request_id)
        if req is not None:                      # still queued
            self.metrics.note_cancel(req.instance, queued=True,
                                     request_id=request_id)
            if self.tracer.enabled:
                self.tracer.request_event(request_id, "cancel",
                                          instance=req.instance, status=status)
            return Result(
                request_id, req.instance, [], prompt_len=len(req.prompt),
                latency_s=time.perf_counter() - req.submit_time,
                status=status,
            )
        if request_id in self._reserved:         # mid-prefill
            m, b = self._reserved.pop(request_id)
            req = self.active[m][b]
            self.prefill.abort(request_id)
            self.slot_busy[m, b] = False
            self.slot_prefilling[m, b] = False
            self.active[m][b] = None
            self.metrics.note_cancel(m, queued=False, request_id=request_id)
            if self.tracer.enabled:
                self.tracer.request_event(request_id, "cancel",
                                          instance=m, status=status)
            return Result(
                request_id, m, [], prompt_len=len(req.prompt),
                latency_s=time.perf_counter() - req.submit_time,
                status=status,
            )
        for m in range(self.m):                  # mid-decode
            for b in range(self.b):
                req = self.active[m][b]
                if req is not None and req.request_id == request_id:
                    gen = self.generated.pop(request_id, [])
                    self.slot_busy[m, b] = False
                    self.active[m][b] = None
                    self.metrics.note_cancel(m, queued=False,
                                             request_id=request_id)
                    if self.tracer.enabled:
                        self.tracer.request_event(request_id, "cancel",
                                                  instance=m, status=status)
                    return Result(
                        request_id, m, gen, prompt_len=len(req.prompt),
                        latency_s=time.perf_counter() - req.submit_time,
                        status=status,
                    )
        return None

    def _admit(self):
        """Move pending requests into prefill lanes, reserving a grid
        slot for each (the slot starts decoding once its chunks land)."""
        lanes = self.prefill.free_lanes()
        # a quarantined row offers zero free slots: the scheduler stops
        # admitting to it, its queue simply waits out the quarantine
        free = {
            i: (int(self.b - self.slot_busy[i].sum())
                if self.health.admissible(i) else 0)
            for i in range(self.m)
        }
        if lanes == 0 or not any(free.values()) \
                or self.scheduler.total_pending() == 0:
            return
        admits = self.scheduler.select(free, limit=lanes)
        for req in admits:
            m = req.instance
            b = next(bb for bb in range(self.b) if not self.slot_busy[m, bb])
            self.slot_busy[m, b] = True
            self.slot_prefilling[m, b] = True
            self._reserved[req.request_id] = (m, b)
            self.active[m][b] = req
            self.prefill.start(req)
            self.metrics.note_admit(m, len(req.prompt))
            if self.accounting.enabled and req.submit_time > 0:
                wait = time.perf_counter() - req.submit_time
                if wait >= 0:
                    self.accounting.note_queue_wait(m, wait)
            if self.tracer.enabled:
                self.tracer.request_event(req.request_id, "admit",
                                          instance=m)

    def _fail_slot(self, req: Request, m: int, b: int, exc,
                   *, poisoned: bool = False) -> Result:
        """Terminally fail an admitted request and restore its slot/lane
        bookkeeping — a failed device call either frees the slot or
        fails the request, never leaks either (exception-safe ``step``,
        DESIGN.md §6.8)."""
        rid = req.request_id
        self._reserved.pop(rid, None)
        if self.slot_prefilling[m, b]:
            self.prefill.abort(rid)
        self.slot_busy[m, b] = False
        self.slot_prefilling[m, b] = False
        self.active[m][b] = None
        gen = self.generated.pop(rid, [])
        if poisoned:
            before = self.health.state(m)
            self.health.note_poisoned(m)
        else:
            before = self.health.state(m)
            self.health.note_failure(m)
        self.metrics.note_failed(m, request_id=rid)
        if self.tracer.enabled:
            self.tracer.request_event(rid, "finish", instance=m,
                                      status="error")
            if before != "quarantined" and self.health.state(m) == \
                    "quarantined":
                self.tracer.request_event(
                    rid, "quarantine", instance=m,
                    status="poisoned" if poisoned else "failures")
        return Result(
            rid, m, gen, prompt_len=len(req.prompt),
            latency_s=time.perf_counter() - req.submit_time,
            status="error", error=f"{type(exc).__name__}: {exc}",
        )

    def _fail_prefilling(self, exc) -> list[Result]:
        """A chunked-prefill pass failed.  Chunks are lane-batched into
        one device call, so the failure cannot be attributed to a single
        lane: every mid-prefill request fails terminally and the lane
        runtime is rebuilt (the failed call may have invalidated the
        donated chunk carry)."""
        failures = []
        rids = sorted(
            rid for rid, (m, b) in self._reserved.items()
            if self.slot_prefilling[m, b]
        )
        for rid in rids:
            m, b = self._reserved[rid]
            failures.append(self._fail_slot(self.active[m][b], m, b, exc))
        self.prefill.reset()
        return failures

    def _finish_prefills(self, completed) -> list[Result]:
        """Scatter completed prefill lanes into their reserved slots and
        flip them to decoding.  Returns terminal Results for requests
        whose scatter failed (their slots are freed, not leaked)."""
        tr = self.tracer
        acct = self.accounting
        failures: list[Result] = []
        for req, out in completed:
            m, b = self._reserved[req.request_id]
            if tr.enabled or acct.enabled:
                t0 = time.perf_counter()
            try:
                if self.faults.armed:
                    self.faults.on_call("scatter")
                with self._ctx():
                    self.cache = self._scatter(
                        self.cache, out.cache, out.index, m, b)
            except Exception as exc:
                failures.append(self._fail_slot(req, m, b, exc))
                if self.prefill.donate:
                    # the failed donated call may have invalidated the
                    # grid cache buffer — not locally recoverable; the
                    # supervisor rebuilds it via reset_serving_state
                    self._pending_failures.extend(failures)
                    raise
                continue
            self._reserved.pop(req.request_id)
            self.metrics.note_scatter()
            if tr.enabled:
                # dispatch only: the scatter's device time is in the
                # profiler's device trace, and settling here would
                # serialise what an untraced step overlaps
                tr.device_call(
                    "scatter", t0, time.perf_counter(), step=self.steps,
                    capacity=self.m * self.b,
                    active=int((self.slot_busy
                                & ~self.slot_prefilling).sum()),
                )
                tr.request_event(req.request_id, "prefill_done",
                                 instance=m)
            if acct.enabled:
                # settle so the attributed time is real execution, not
                # dispatch (timing-only: this step's decode consumes the
                # scatter anyway, so numerics are untouched); a scatter
                # admits exactly one request: whole wall to its tenant
                jax.block_until_ready(self.cache)
                acct.note_scatter(time.perf_counter() - t0, m)
            self.pos[m, b] = out.pos
            self.cur_tok[m, b] = out.last_token
            self.slot_prefilling[m, b] = False
            self.generated[req.request_id] = []
        return failures

    # -- engine step ----------------------------------------------------------

    def _decode_horizon(self) -> int:
        """Steps the next fused block runs (the adaptive-horizon policy,
        DESIGN.md §6.6).  Full ``decode_steps`` when the engine is in
        pure-decode steady state; shrunk to keep the host loop
        responsive when there is admission work to interleave:

        * lanes mid-prefill -> 1, so chunk-budgeted prefill keeps its
          per-step interleave with decode (TTFT is not held behind a
          K-step block),
        * requests waiting in queue -> the largest power of two no
          decoding slot overshoots (its remaining budget), so a slot
          about to finish frees up and refills promptly instead of
          riding out junk steps while the backlog waits.

        Powers of two keep the compiled-shape count at log2(K)+1."""
        K = self.decode_steps
        if K <= 1 or not self.adaptive_horizon:
            return K
        if self.prefill.in_flight():
            return 1
        if self.scheduler.total_pending() > 0:
            rem = [
                self.active[m][b].max_new_tokens
                - len(self.generated[self.active[m][b].request_id])
                for m in range(self.m) for b in range(self.b)
                if self.slot_busy[m, b] and not self.slot_prefilling[m, b]
            ]
            cap = min([K] + rem) if rem else 1
            k = 1
            while k * 2 <= cap:
                k *= 2
            return k
        return K

    def step(self) -> list[Result]:
        """Admit pending requests into prefill lanes, advance prefill by
        at most ``chunk_budget`` device calls, run ONE fused k-step
        decode+sample block over the whole (M, B) grid, unroll its
        (k, M, B) tokens on the host, collect finished slots.
        Prefilling slots ride the grid as idle (masked) lanes, so long
        prompts admit without stalling decode.  With the tracer on, each
        phase is a ``serve.*`` span inside ``serve.step`` (tagged with
        the step number and the horizon k)."""
        tr = self.tracer
        trace_on = tr.enabled
        with (tr.span("serve.step", step=self.steps) if trace_on
              else NOSPAN) as span:
            out: list[Result] = self._pending_failures
            self._pending_failures = []
            if self.policy is not None:
                out.extend(self._apply_policy())
            with tr.span("serve.admit") if trace_on else NOSPAN:
                self._admit()
            if self.prefill.in_flight():
                t0 = time.perf_counter()
                try:
                    if self.faults.armed:
                        self.faults.on_call("prefill")
                    completed = self.prefill.advance(
                        self.params, self.chunk_budget, step=self.steps)
                except Exception as exc:
                    out.extend(self._fail_prefilling(exc))
                    completed = []
                stall = time.perf_counter() - t0
                # decode-ready slots sat idle for this long while
                # admission chunks ran — the quantity the chunk budget
                # bounds
                if (self.slot_busy & ~self.slot_prefilling).any():
                    self.metrics.note_admission_stall(stall)
                with tr.span("serve.scatter") if trace_on else NOSPAN:
                    out.extend(self._finish_prefills(completed))
            decoding = self.slot_busy & ~self.slot_prefilling
            if decoding.any():
                out.extend(self._decode(decoding, span))
            self.health.note_step()
        return out

    def _decode(self, decoding, span) -> list[Result]:
        """One fused decode block over the slots in ``decoding`` and the
        host unroll of its tokens; returns the requests it finished.
        ``span`` is the step's trace span (None with the tracer off)."""
        tr = self.tracer
        trace_on = span is not None
        with tr.span("serve.decode.prepare") if trace_on else NOSPAN:
            k = self._decode_horizon()
            # per-slot decode budget for the on-device stop mask: a lane
            # whose budget (or EOS / context) hits mid-block freezes there
            remaining = np.zeros((self.m, self.b), np.int32)
            for m in range(self.m):
                for b in range(self.b):
                    if decoding[m, b]:
                        req = self.active[m][b]
                        remaining[m, b] = (
                            req.max_new_tokens
                            - len(self.generated[req.request_id])
                        )
            if self.mesh is not None:
                # one host->device transfer each, straight to the grid
                # sharding
                def grid_put(x):
                    return jax.device_put(x, self._grid_shard)
            else:
                grid_put = jnp.asarray
            tok_dev, pos_dev = grid_put(self.cur_tok), grid_put(self.pos)
            alive_dev, rem_dev = grid_put(decoding), grid_put(remaining)
            # fault hook BEFORE the dispatch: an injected raise/stall
            # lands while host state is still consistent (no half-applied
            # block), so a supervisor reset + requeue replays cleanly
            poison = (
                self.faults.on_call("decode") if self.faults.armed else ()
            )
        if trace_on:
            span.set_metadata(k=k)
        t0 = time.perf_counter()
        with tr.span("serve.decode.dispatch") if trace_on else NOSPAN:
            with self._ctx():
                toks, emitted, oks, self.cache, self._key = self._step(
                    self.params, self.cache, tok_dev, pos_dev, self._key,
                    alive_dev, rem_dev, k,
                )
        # jit return = host dispatch done (device still computing): the
        # per-call cost a K-step block amortizes K-fold
        t_dispatch = time.perf_counter()
        self.steps += 1
        # device_get blocks until the fused block's tokens land: the
        # settled timestamp is end-to-end device-call wall time
        with tr.span("serve.decode.wait") if trace_on else NOSPAN:
            toks, emitted, oks = jax.device_get((toks, emitted, oks))
        t_settled = time.perf_counter()
        with tr.span("serve.decode.unroll") if trace_on else NOSPAN:
            return self._unroll(k, decoding, toks, emitted, oks, poison,
                                t0, t_dispatch, t_settled)

    def _unroll(self, k, decoding, toks, emitted, oks, poison,
                t0, t_dispatch, t_settled) -> list[Result]:
        """Host unroll of a (k, M, B) token block: every per-token hook
        (metrics, scheduler accounting, on_token streaming, finish
        detection) fires per token, exactly as k separate one-token
        steps would — only the dispatch count changed."""
        toks, emitted = np.asarray(toks), np.asarray(emitted)
        oks = np.array(oks)
        for i in poison:
            # injected NaN: flip the guard for row i exactly as real
            # non-finite logits would (real NaN in the cache would
            # poison every later step — the guard flip is the faithful,
            # recoverable stand-in)
            oks[:, i, :] = False
        block_tokens = int(emitted.sum())
        self.metrics.note_decode_call(steps=k, tokens=block_tokens,
                                      wall_s=t_settled - t0,
                                      dispatch_s=t_dispatch - t0)
        tr = self.tracer
        trace_on = tr.enabled
        if trace_on:
            tr.device_call(
                "decode", t0, t_dispatch, t_settled,
                step=self.steps,
                active=int(decoding.sum()),
                capacity=self.m * self.b,
                lanes_busy=self.prefill.in_flight(),
                lanes=self.prefill.lanes,
                tokens=block_tokens,
                pending=self.scheduler.total_pending(),
                decode_steps=k,
            )
        acct = self.accounting
        acct_on = acct.enabled
        if acct_on:
            # split this call's settled wall across the tenants occupying
            # the grid, slot-weighted; empty slots bill to idle (§6.9)
            acct.note_decode(
                t_settled - t0,
                [int(c) for c in decoding.sum(axis=1)],
                self.m * self.b,
            )
            replay_counts: dict[int, int] = {}

        done: list[Result] = []
        for j in range(k):
            for m in range(self.m):
                for b in range(self.b):
                    # `decoding` is the block-entry mask; slot_busy drops
                    # when a lane finishes mid-unroll, after which its
                    # remaining rows are device-frozen junk — skip them
                    if not (decoding[m, b] and self.slot_busy[m, b]):
                        continue
                    req = self.active[m][b]
                    if not oks[j, m, b]:
                        # NaN/Inf guard tripped for this row: fail the
                        # request and quarantine the instance — the
                        # other M-1 rows stream on untouched
                        done.append(self._fail_slot(
                            req, m, b,
                            RuntimeError("non-finite logits "
                                         "(NaN/Inf token guard)"),
                            poisoned=True,
                        ))
                        continue
                    t = int(toks[j, m, b])
                    gen = self.generated[req.request_id]
                    # recovery replay (DESIGN.md §6.8): the first
                    # ``emit_skip`` tokens were already delivered to the
                    # client before a crash — greedy decode regenerates
                    # them bit-identically, and the engine suppresses
                    # their re-emission so the client-visible stream has
                    # no duplicates
                    replay = len(gen) < req.emit_skip
                    if replay:
                        exp = req.replay_expect
                        if exp is not None and exp[len(gen)] != t:
                            self.metrics.replay_mismatches += 1
                        self.metrics.note_replay(m)
                        if acct_on:
                            replay_counts[m] = replay_counts.get(m, 0) + 1
                    else:
                        first = not gen and not req.emit_skip
                        self.metrics.note_token(
                            m, first=first,
                            submit_time=req.submit_time,
                            request_id=req.request_id,
                        )
                        if first and trace_on:
                            tr.request_event(req.request_id, "first_token",
                                             instance=m)
                    self.scheduler.note_generated(m, 1)
                    gen.append(t)
                    self.pos[m, b] += 1
                    self.cur_tok[m, b] = t
                    hit_eos = self.eos_id is not None and t == self.eos_id
                    finished = (
                        len(gen) >= req.max_new_tokens
                        or hit_eos
                        or int(self.pos[m, b]) >= self.max_context - 1
                    )
                    if self.on_token is not None and not replay:
                        self.on_token(req.request_id, t, finished)
                    if finished:
                        done.append(Result(
                            req.request_id, m, gen,
                            prompt_len=len(req.prompt),
                            latency_s=time.perf_counter() - req.submit_time,
                            finish_reason="stop" if hit_eos else "length",
                        ))
                        self.metrics.note_complete(m, req.submit_time,
                                                   request_id=req.request_id)
                        self.health.note_success(m)
                        if trace_on:
                            tr.request_event(req.request_id, "finish",
                                             instance=m, status="ok")
                        self.slot_busy[m, b] = False
                        self.active[m][b] = None
                        del self.generated[req.request_id]
        if acct_on and replay_counts:
            # replay view (§6.8/§6.9): token-weighted share of this
            # call's wall spent regenerating already-delivered tokens
            acct.note_replay(replay_counts, t_settled - t0, block_tokens)
        return done

    # -- overload brownout (DESIGN.md §6.8) -----------------------------------

    def _apply_policy(self) -> list[Result]:
        """One step's brownout bookkeeping: feed queue depth to the
        degraded-mode hysteresis and shed queued requests older than the
        policy's age cutoff (their clients have likely given up)."""
        pol = self.policy
        pol.note_depth(self.scheduler.total_pending())
        if pol.shed_age_s is None:
            return []
        now = time.perf_counter()
        out = []
        for req in self.scheduler.shed_older_than(now - pol.shed_age_s):
            pol.shed_total += 1
            self.metrics.note_shed(req.instance)
            if self.tracer.enabled:
                self.tracer.request_event(req.request_id, "shed",
                                          instance=req.instance)
            out.append(Result(
                req.request_id, req.instance, [],
                prompt_len=len(req.prompt),
                latency_s=now - req.submit_time, status="shed",
                error=f"queued longer than {pol.shed_age_s}s under "
                      f"overload; retry later",
            ))
        return out

    # -- crash recovery (DESIGN.md §6.8) --------------------------------------

    def reset_serving_state(self) -> list[tuple[Request, list[int]]]:
        """Post-crash recovery: tear the serving state back to empty —
        fresh grid cache, zeroed slot bookkeeping, cleared prefill
        lanes, reseeded sampling key — WITHOUT touching compiled
        programs, the request-id counter, or cumulative metrics.
        Returns every live (queued, prefilling, or decoding) request
        with its generated-token prefix, sorted by request_id, for the
        supervisor to ``requeue``."""
        live: list[tuple[Request, list[int]]] = []
        for m in range(self.m):
            for b in range(self.b):
                req = self.active[m][b]
                if req is not None:
                    live.append(
                        (req, list(self.generated.get(req.request_id, []))))
                self.active[m][b] = None
        for req in self.scheduler.drain_all():
            live.append((req, []))
        live.sort(key=lambda t: t[0].request_id)
        self._reserved.clear()
        self.generated.clear()
        self._pending_failures = []
        self.pos[:] = 0
        self.cur_tok[:] = 0
        self.slot_busy[:] = False
        self.slot_prefilling[:] = False
        self.prefill.reset()
        self.metrics.reset_queue_depths()
        self.cache = self._fresh_cache()
        key = jax.random.PRNGKey(self._seed)
        if self.mesh is not None:
            key = jax.device_put(key, self._rep_shard)
        self._key = key
        return live

    def requeue(self, req: Request, *,
                emitted: list[int] | None = None) -> int:
        """Re-enter a recovered request under its ORIGINAL request_id
        and submit_time (no re-validation — it was validated once).
        ``emitted`` is the token prefix the client already received:
        greedy decode regenerates it bit-identically (a greedy stream
        depends only on its own prompt) and the engine suppresses its
        re-emission via ``emit_skip``, so the client-visible stream
        resumes exactly where it broke — no duplication, no loss."""
        assert req.request_id >= 0, "requeue() needs a submitted request"
        if emitted:
            req.emit_skip = len(emitted)
            req.replay_expect = list(emitted)
        else:
            req.emit_skip = 0
            req.replay_expect = None
        self.scheduler.submit(req)
        self.metrics.note_requeue(req.instance)
        if self.tracer.enabled:
            self.tracer.request_event(req.request_id, "requeue",
                                      instance=req.instance)
        return req.request_id

    def reset_metrics(self) -> ServerMetrics:
        """Fresh counters/sample windows (e.g. after a compile warmup,
        so recorded percentiles carry no warmup outliers); re-points
        every subsystem holding the metrics object."""
        old = self.metrics
        self.metrics = ServerMetrics(self.m, mesh=self.mesh, slo=old.slo)
        self.metrics.compiled_shapes_fn = \
            lambda: self.prefill.compiled_shapes
        self.metrics.health_fn = self.health.snapshot
        self.metrics.resilience_fn = old.resilience_fn
        self.metrics.accounting_fn = self.accounting.snapshot
        self.prefill.metrics = self.metrics
        return self.metrics

    def busy(self) -> bool:
        """Any live work: queued, prefilling, or decoding requests (what
        the async frontend's driver polls between steps)."""
        return bool(
            self.slot_busy.any() or self.prefill.in_flight() > 0
            or self.scheduler.total_pending() > 0
        )

    def run_until_drained(self, max_steps: int = 10_000) -> list[Result]:
        out: list[Result] = []
        for _ in range(max_steps):
            out.extend(self.step())
            if not self.busy():
                return out
        raise RuntimeError("serving did not drain")
