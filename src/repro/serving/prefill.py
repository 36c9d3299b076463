"""Unified chunked prefill for serving admission — one length-agnostic
path for EVERY family, interleavable with decode.

The old admission layer was three divergent paths (padded length-bucket
batches for KV families, a state-carrying chunk loop for ssm, exact
per-prompt-length compiles for hybrid) with a documented MoE capacity
caveat.  This runtime replaces all of them: every prompt — dense, moe,
vlm, audio, ssm AND hybrid — streams through the family's chainable
``api.prefill_chunk`` (DESIGN.md §6.2) in fixed-size chunks, so

* admission compiles exactly ONE shape per family — the chunk; the old
  single-token tail loop is folded into one padded final chunk with
  per-position validity masks (``tail_fold``), so a mixed-length lane
  batch drains in ``ceil(L_max/chunk)`` device calls with zero
  per-token tail calls (``compiled_shapes``/``device_calls`` assert
  this in tests),
* up to ``lanes`` requests prefill together in ONE carry tree, each
  riding the instances axis of the merged program via an on-device
  weight-row gather (``gather_instances``); per-lane traced offsets let
  lanes sit at different prompt depths inside the same compiled call,
* progress is incremental: the engine grants a per-step chunk *budget*,
  so a 4k prompt no longer stalls the decode grid — partially-prefilled
  lanes coexist with decoding slots (true continuous batching),
* exactness is positional, not padded: chunk queries attend over
  [cache-so-far, chunk] with ring/meta/window validity encoded in one
  kv-position mask, recurrent state threads through the carry, and moe
  routing carries per-expert counts + real-length capacities so chunked
  routing equals the exact-length pass.

Lane lifecycle: ``start`` binds a request to a free lane; each jitted
call takes (valid, fresh) lane masks — ``fresh`` re-initializes a
lane's carry rows in-graph (no extra compiled shape for resets),
``valid`` gates which lanes actually advance.  Completed lanes are
handed to the engine as :class:`PrefillOut` rows of the shared carry
tree and scattered into their grid slots.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from repro import api
from repro.launch.compat import mesh_context
from repro.models import common as C
from repro.models.common import constrain_tree, gather_instances
from repro.serving.obs.trace import NOSPAN
from repro.serving.scheduler import Request

KV_FAMILIES = ("dense", "moe", "vlm", "audio")
SERVABLE = KV_FAMILIES + ("ssm", "hybrid")

DEFAULT_CHUNK = 32
DEFAULT_LANES = 4


@dataclasses.dataclass
class PrefillOut:
    """One admitted request's prefill product.

    ``cache`` is a cache/state tree whose instances axis holds this
    request at row ``index`` (all completed lanes of one advance() share
    the same tree).  The engine scatters row ``index`` into the
    request's grid slot, then seeds decode at ``pos`` with
    ``last_token`` — the last prompt token is (re)decoded by the first
    fused grid step, so sampling stays fully on-device and prefill never
    extracts per-request logits."""
    cache: Any
    index: int
    pos: int
    last_token: int


@dataclasses.dataclass
class _Lane:
    req: Request | None = None
    next_pos: int = 0          # next absolute position to process
    total: int = 0             # positions to prefill = prefix + len(prompt) - 1
    fresh: bool = False        # carry rows need re-init before first work


class ChunkedPrefill:
    def __init__(
        self,
        cfg,
        *,
        max_context: int,
        chunk: int = DEFAULT_CHUNK,
        lanes: int = DEFAULT_LANES,
        metrics=None,
        mesh=None,
        rules=None,
        tail_fold: bool = True,
        donate: bool | None = None,
        tracer=None,
        accounting=None,
    ):
        if cfg.family not in SERVABLE:
            raise ValueError(f"family {cfg.family!r} is not servable")
        self.cfg = cfg
        self.family = cfg.family
        self.max_context = max_context
        self.metrics = metrics
        # step tracer (engine-owned; None for standalone use) — call
        # sites guard on ``tracer.enabled`` so the off path is free
        self.tracer = tracer
        # per-tenant attribution (§6.9), same off-is-free discipline
        self.accounting = accounting
        self.lanes = max(1, lanes)
        # tail folding: pad the final chunk to the full chunk width with
        # per-position validity masks instead of issuing up to chunk-1
        # single-token tail calls — ONE compiled shape, ceil(L/chunk)
        # device calls per admission (off = the two-shape chunk+tail path,
        # kept for A/B benchmarking)
        self.tail_fold = tail_fold
        # donate the lane carry through the jitted chunk step so chunk
        # calls update the carry buffers in place instead of materializing
        # a second copy per call (mirrors engine.py's grid-cache donation;
        # skipped on CPU, where XLA can't honor it and jit warns)
        self.donate = (jax.default_backend() != "cpu") if donate is None else donate
        # a chunk must map to distinct cache slots, so clamp it to the
        # narrowest ring the family keeps (hybrid SWA ring / sliding
        # window); full-context caches don't wrap during prefill
        ring = self._min_ring_width()
        self.chunk = max(1, min(chunk, ring if ring else chunk))
        self.prefix = api.prefill_prefix_len(cfg)
        if self.max_prompt_len() <= 0:
            raise ValueError(
                f"max_context={max_context} leaves no room for prompt "
                f"tokens after the {self.prefix}-position learned prefix"
            )
        self._axes = api.axes(cfg)
        self._carry_axes = api.chunk_carry_axes(cfg)
        from repro.launch.shardings import default_serve_rules
        self.mesh = mesh
        self.rules = default_serve_rules(mesh, rules)
        with mesh_context(self.mesh, self.rules):
            self._carry = api.init_chunk_carry(cfg, self.lanes, 1, max_context)
        if mesh is not None:
            from repro.launch.shardings import tree_shardings
            self._carry = jax.device_put(
                self._carry,
                tree_shardings(self.rules, self._carry_axes, self._carry),
            )
        # pristine carry for zero-work completions (single-token prompts
        # of prefix-less families scatter fresh init state, no device
        # call).  A deep copy, NOT an alias: the chunk step donates the
        # live carry, which would invalidate an aliased zero carry
        self._zero_carry = jax.tree.map(jnp.copy, self._carry)
        if mesh is not None:
            from repro.launch.shardings import tree_shardings
            self._zero_carry = jax.device_put(
                self._zero_carry,
                tree_shardings(self.rules, self._carry_axes, self._zero_carry),
            )
        self._lanes = [_Lane() for _ in range(self.lanes)]
        self._fns: dict[int, Any] = {}      # chunk width -> jitted step
        self._static = self._static_inputs()
        self._tail_turn = False             # chunk/tail round alternation
        self.device_calls = 0               # total chunk/tail device calls
        self.admitted = 0                   # lanes ever started

    # -- geometry ------------------------------------------------------------

    def _min_ring_width(self) -> int:
        cfg = self.cfg
        if cfg.family == "hybrid":
            from repro.models import hybrid as H
            # the ACTUAL ring width of the SWA group cache: make_cache
            # clips the cache to max_context, so a context below
            # meta+window leaves a narrower ring than the window itself
            s_cache = min(H.NUM_META_TOKENS + H.swa_window(cfg), self.max_context)
            return max(s_cache - H.NUM_META_TOKENS, 1)
        if cfg.family in ("dense", "moe", "vlm") and cfg.sliding_window:
            return cfg.sliding_window
        return 0

    def max_prompt_len(self) -> int:
        """Longest admissible prompt: every position (learned prefix +
        prompt tokens) must fit the serving context."""
        return self.max_context - self.prefix

    @property
    def compiled_shapes(self) -> int:
        """Distinct compiled prefill shapes — 1 with tail folding (the
        chunk), at most 2 without (chunk + single-token tail)."""
        return len(self._fns)

    # -- lane bookkeeping ----------------------------------------------------

    def free_lanes(self) -> int:
        return sum(1 for l in self._lanes if l.req is None)

    def in_flight(self) -> int:
        return sum(1 for l in self._lanes if l.req is not None)

    def start(self, req: Request) -> None:
        """Bind a request to a free lane (its chunks run on subsequent
        ``advance`` calls)."""
        for lane in self._lanes:
            if lane.req is None:
                lane.req = req
                lane.next_pos = 0
                lane.total = self.prefix + len(req.prompt) - 1
                lane.fresh = True
                self.admitted += 1
                return
        raise RuntimeError("no free prefill lane")

    def abort(self, request_id: int) -> bool:
        """Evict a mid-flight request from its lane (client cancel /
        disconnect / deadline expiry).  The lane is free for the very
        next ``start``; its carry rows are left as-is — binding a new
        request sets ``fresh``, which re-initializes the rows in-graph,
        so no device call and no extra compiled shape is spent on the
        eviction."""
        for lane in self._lanes:
            if lane.req is not None and lane.req.request_id == request_id:
                lane.req = None
                return True
        return False

    # -- static per-call inputs ----------------------------------------------

    def _static_inputs(self) -> dict:
        cfg, k = self.cfg, self.lanes
        dt = jnp.dtype(cfg.dtype)
        if cfg.family == "vlm":
            return {"image_embeds": jnp.zeros(
                (k, 1, cfg.num_image_patches, cfg.vision_embed_dim), dt)}
        if cfg.family == "audio":
            return {"frames": jnp.zeros(
                (k, 1, cfg.num_audio_frames, cfg.d_model), dt)}
        return {}

    def _fn(self, c: int):
        if c not in self._fns:
            cfg = self.cfg

            def fn(params, idx, tokens, carry, offset, valid, fresh, extras):
                sub = gather_instances(params, self._axes, idx)
                init = api.init_chunk_carry(cfg, self.lanes, 1, self.max_context)
                carry = C.tree_select_lanes(fresh, init, carry, self._carry_axes)
                batch = {"tokens": tokens, **self._static, **extras}
                new = api.prefill_chunk(cfg, sub, batch, carry, offset)
                new = C.tree_select_lanes(valid, new, carry, self._carry_axes)
                return constrain_tree(new, self._carry_axes)

            # donate the carry (arg 3): the chunk step then updates the
            # lane caches in place instead of allocating a full second
            # copy of the (lanes, 1, max_context) tree per call
            self._fns[c] = jax.jit(
                fn, donate_argnums=(3,) if self.donate else ()
            )
        return self._fns[c]

    # -- the chunk pump ------------------------------------------------------

    def advance(self, params, budget: int,
                step: int = 0) -> list[tuple[Request, PrefillOut]]:
        """Run up to ``budget`` chunk device calls; return the requests
        whose prefill completed (with their PrefillOut rows of the shared
        carry tree).  Under donation the returned rows alias the live
        carry, which the NEXT advance updates in place — consume (scatter)
        them before advancing again, as the engine does.  ``step`` tags
        trace events with the engine's step counter."""
        tr = self.tracer
        trace_on = tr is not None and tr.enabled
        with tr.span("serve.prefill") if trace_on else NOSPAN:
            return self._advance(params, budget, step, trace_on)

    def _advance(self, params, budget: int, step: int,
                 trace_on: bool) -> list[tuple[Request, PrefillOut]]:
        done: list[tuple[Request, PrefillOut]] = []
        # zero-work lanes (single-token prompts of prefix-less families)
        # complete immediately from the pristine init carry — their grid
        # slot needs fresh state, never a device call
        zero_done: list[tuple[Request, PrefillOut]] = []
        for i, lane in enumerate(self._lanes):
            if lane.req is not None and lane.total == 0:
                zero_done.append((lane.req, PrefillOut(
                    cache=self._zero_carry["cache"], index=i, pos=0,
                    last_token=lane.req.prompt[-1],
                )))
                lane.req = None
        stepped = False
        t0 = time.perf_counter()
        with mesh_context(self.mesh, self.rules):
            while budget > 0:
                busy = [i for i, l in enumerate(self._lanes) if l.req is not None]
                if not busy:
                    break
                if self.tail_fold:
                    # folded: EVERY lane with work advances together; a
                    # lane with < chunk left rides a padded final chunk
                    # whose junk suffix is masked per position — one
                    # compiled shape, ceil(L_max/chunk) calls total
                    workable = [i for i in busy
                                if self._lanes[i].total > self._lanes[i].next_pos]
                    if not workable:
                        break
                    c, fold = self.chunk, True
                else:
                    chunkable = [i for i in busy
                                 if self._lanes[i].total - self._lanes[i].next_pos >= self.chunk]
                    tailable = [i for i in busy
                                if 0 < self._lanes[i].total - self._lanes[i].next_pos < self.chunk]
                    if not chunkable and not tailable:
                        break
                    # alternate chunk and tail rounds when both kinds of
                    # work exist: under continuous long-prompt arrivals a
                    # lane one token from completion must not be starved
                    # behind lanes that always have a full chunk left
                    run_tail = bool(tailable) and (self._tail_turn or not chunkable)
                    self._tail_turn = not run_tail
                    workable = tailable if run_tail else chunkable
                    c, fold = (1 if run_tail else self.chunk), False
                with (self.tracer.span("serve.prefill.chunk",
                                       lanes=len(workable))
                      if trace_on else NOSPAN) as span:
                    self._step(params, workable, c, fold=fold, step=step,
                               span=span)
                stepped = True
                budget -= 1
                for i in busy:
                    lane = self._lanes[i]
                    if lane.req is not None and lane.next_pos >= lane.total:
                        done.append((lane.req, PrefillOut(
                            cache=None, index=i, pos=lane.total,
                            last_token=lane.req.prompt[-1],
                        )))
                        lane.req = None
        if stepped:
            # settle the async dispatch so the engine's admission-stall
            # timer measures device execution, not just dispatch (the
            # scatter/decode it times against depend on this carry anyway)
            with self.tracer.span("serve.prefill.wait") if trace_on else NOSPAN:
                jax.block_until_ready(self._carry)
            if self.metrics is not None:
                self.metrics.note_prefill_wall(time.perf_counter() - t0)
        for _, out in done:
            out.cache = self._carry["cache"]
        return zero_done + done

    def _step(self, params, workable: list[int], c: int, fold: bool = False,
              step: int = 0, span=None) -> None:
        """One chunk device call over the ``workable`` lanes.  ``span`` is
        the call's trace span (None with the tracer off), tagged here with
        the real tokens it advances."""
        k = self.lanes
        toks = np.zeros((k, 1, c), np.int32)
        inst = np.zeros((k,), np.int32)
        offset = np.zeros((k, 1), np.int32)
        valid = np.zeros((k,), bool)
        fresh = np.zeros((k,), bool)
        pvalid = np.zeros((k, 1, c), bool)
        tokens_done = 0
        # lane bookkeeping is STAGED and committed only after the device
        # call returns: an exception mid-call leaves every lane exactly
        # as it was (exception-safe step; the engine fails or requeues
        # the requests, never resumes from half-advanced positions)
        staged: list[tuple[_Lane, int]] = []
        for i, lane in enumerate(self._lanes):
            if lane.req is None:
                continue
            inst[i] = lane.req.instance
            offset[i, 0] = lane.next_pos
            fresh[i] = lane.fresh
            if i in workable:
                valid[i] = True
                # folded final chunks advance only their real remainder;
                # the junk suffix (token 0) is masked per position
                adv = min(c, lane.total - lane.next_pos) if fold else c
                pvalid[i, 0, :adv] = True
                for j in range(adv):
                    p = lane.next_pos + j
                    if p >= self.prefix:
                        toks[i, 0, j] = lane.req.prompt[p - self.prefix]
                tokens_done += adv
                staged.append((lane, adv))
        extras = {}
        if fold:
            extras["valid"] = jnp.asarray(pvalid)
        if self.family == "moe":
            from repro.models import moe
            limit = np.zeros((k, 1), np.int32)
            for i, lane in enumerate(self._lanes):
                if lane.req is not None and lane.total > 0:
                    limit[i, 0] = moe.capacity(self.cfg, lane.total)
            extras["moe_limit"] = jnp.asarray(limit)
        trace_on = span is not None
        if trace_on:
            span.set_metadata(tokens=tokens_done)
        acct = self.accounting
        acct_on = acct is not None and acct.enabled
        if trace_on or acct_on:
            t0 = time.perf_counter()
        self._carry = self._fn(c)(
            params, jnp.asarray(inst), jnp.asarray(toks), self._carry,
            jnp.asarray(offset), jnp.asarray(valid), jnp.asarray(fresh), extras,
        )
        self.device_calls += 1
        # the call landed: commit lane advances, and clear ``fresh`` on
        # every bound lane (the call re-initialized all fresh rows
        # in-graph, workable or not)
        for lane, adv in staged:
            lane.next_pos += adv
        for lane in self._lanes:
            if lane.req is not None:
                lane.fresh = False
        if trace_on:
            # dispatch only: the chunk's device time is in the profiler's
            # device trace; the one settle is at the end of ``advance``
            self.tracer.device_call(
                "prefill_chunk", t0, time.perf_counter(),
                step=step, lanes_busy=self.in_flight(), lanes=self.lanes,
                valid_frac=tokens_done / (len(workable) * c) if workable else 1.0,
                tokens=tokens_done,
            )
        if acct_on:
            # settling per chunk is an accounting-ON cost: it buys the
            # true per-call device time.  Lane-weighted attribution: each
            # busy lane charges its tenant wall/lanes; unoccupied lanes
            # are shared idle
            jax.block_until_ready(self._carry)
            acct.note_prefill(
                time.perf_counter() - t0,
                [int(inst[i]) for i in workable], self.lanes)
        if self.metrics is not None:
            self.metrics.note_prefill_batch(len(workable), tokens_done)

    def reset(self) -> None:
        """Crash recovery (DESIGN.md §6.8): evict every lane and rebuild
        the live carry from the pristine zero copy — a failed donated
        chunk call may have invalidated the carry buffers.  Compiled
        chunk programs and cumulative counters are kept."""
        for lane in self._lanes:
            lane.req = None
            lane.fresh = False
        carry = jax.tree.map(jnp.copy, self._zero_carry)
        if self.mesh is not None:
            from repro.launch.shardings import tree_shardings
            carry = jax.device_put(
                carry, tree_shardings(self.rules, self._carry_axes, carry))
        self._carry = carry
        self._tail_turn = False

    # -- convenience (tests / non-interleaved callers) -----------------------

    def run(self, params, reqs) -> list[PrefillOut]:
        """Prefill the given requests to completion (no interleaving);
        one PrefillOut per request, in submission order.  Requests are
        fed through the lanes in waves of ``self.lanes``.

        Under donation a returned carry is only valid until the next
        ``advance`` (which updates it in place) — the engine scatters
        each wave immediately; here later waves would invalidate earlier
        rows, so donated multi-wave runs snapshot each wave's cache."""
        outs: dict[int, PrefillOut] = {}
        pending = list(enumerate(reqs))
        started: dict[int, int] = {}      # id(req) -> original index
        while pending or self.in_flight():
            while pending and self.free_lanes():
                i, r = pending.pop(0)
                started[id(r)] = i
                self.start(r)
            wave = self.advance(params, budget=1_000_000)
            if self.donate and (pending or self.in_flight()):
                snap = None
                for _, out in wave:
                    if out.cache is self._carry["cache"]:
                        if snap is None:
                            snap = jax.tree.map(jnp.copy, out.cache)
                        out.cache = snap
            for req, out in wave:
                outs[started[id(req)]] = out
        return [outs[i] for i in range(len(reqs))]
