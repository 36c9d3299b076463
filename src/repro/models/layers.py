"""Fusion-aware neural-net primitives.

All ops here are the NetFuse *input-weight-local* counterparts operating
in instance-axis form: activations ``(M, B, ...)``, weights with leading
``M``.  With M=1 they reduce to the ordinary ops; with M>1 each instance's
inputs only ever touch that instance's weights (paper §3.1).

Attention is a chunked online-softmax ("flash") implementation: queries
are processed in static chunks (python loop at trace time), keys/values
streamed with ``lax.scan`` — S×S score matrices are never materialized,
which is what makes the 32k-prefill and 512k-decode shapes lowerable.
Masking is positional: ``q_pos``/``kv_pos`` arrays encode causality,
sliding windows and ring-buffer cache validity in one rule.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import fused_ops
from repro.models.common import active_rules, constrain

# ---------------------------------------------------------------------------
# basic ops
# ---------------------------------------------------------------------------


def linear(x: jax.Array, w: jax.Array, b: jax.Array | None = None) -> jax.Array:
    """Merged matmul: x (M, ..., D) @ w (M, D, F)  [+ b (M, F)]."""
    y = jnp.einsum("m...d,mdf->m...f", x, w.astype(x.dtype))
    if b is not None:
        y = y + b.astype(y.dtype).reshape(b.shape[0], *([1] * (y.ndim - 2)), b.shape[-1])
    return y


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    """x (M, ..., D), scale (M, D). Stats in f32."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    m, d = scale.shape
    s = scale.astype(jnp.float32).reshape((m,) + (1,) * (x.ndim - 2) + (d,))
    return (y * s).astype(x.dtype)


def layer_norm(
    x: jax.Array, scale: jax.Array, bias: jax.Array | None, eps: float = 1e-5
) -> jax.Array:
    """Merged layer norm == group norm with G=M (instance-axis form)."""
    xf = x.astype(jnp.float32)
    y = fused_ops.merged_layer_norm(
        xf, scale.astype(jnp.float32),
        bias.astype(jnp.float32) if bias is not None else None, eps=eps,
    )
    return y.astype(x.dtype)


def embed(ids: jax.Array, table: jax.Array, dtype) -> jax.Array:
    """ids (M, B, S), table (M, V, D) -> (M, B, S, D)."""
    return fused_ops.merged_embedding(ids, table).astype(dtype)


def unembed(x: jax.Array, table_or_head: jax.Array) -> jax.Array:
    """Logits in f32: x (M,B,S,D), head (M,D,V) -> (M,B,S,V)."""
    return jnp.einsum(
        "mbsd,mdv->mbsv", x.astype(jnp.float32), table_or_head.astype(jnp.float32)
    )


def swiglu_mlp(x, wg, wu, wd):
    h = jax.nn.silu(linear(x, wg)) * linear(x, wu)
    h = constrain(h, "instances", "batch", None, "mlp")
    return linear(h, wd)


def gelu_mlp(x, w1, b1, w2, b2):
    h = jax.nn.gelu(linear(x, w1, b1))
    h = constrain(h, "instances", "batch", None, "mlp")
    return linear(h, w2, b2)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x (M,B,S,H,hd), pos (M,B,S) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * freqs          # (M,B,S,half)
    cos = jnp.cos(ang)[..., None, :].astype(x.dtype)          # (M,B,S,1,half)
    sin = jnp.sin(ang)[..., None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------------
# chunked online-softmax attention
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _pick_chunk(n: int, target: int) -> int:
    c = min(target, n)
    while n % c:
        c -= 1
    return c


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    kv_pos: jax.Array,
    *,
    window: int | jax.Array = 0,
    sink: int = 0,
    causal: bool = True,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
) -> jax.Array:
    """GQA attention without materializing S_q x S_kv.

    q: (M,B,Sq,H,hd); k,v: (M,B,Skv,KVH,hd); q_pos: (M,B,Sq) int32;
    kv_pos: (M,B,Skv) int32 with -1 marking invalid (empty cache) slots.
    Mask: valid & (kv_pos <= q_pos if causal) & (q_pos - kv_pos < window
    if window).  ``window`` may be a traced scalar (per-layer windows under
    lax.scan — hybrid models); ``sink`` exempts the first ``sink``
    positions from the window (attention sinks / Hymba meta tokens).

    Distribution (§Perf qwen1.5-prefill iterations): when sharding rules
    are active and Sq>1, the chunked streaming runs under ``jax.shard_map``
    over (batch axes, q-heads->"model") — GSPMD replicates while-loop
    operands whose head dims are sharded (every scan/slice formulation we
    tried re-gathered the KV per loop), so the scan must be device-local.
    KV heads ride along sharded when KVH divides the axis; otherwise each
    rank slices the kv-head group(s) backing its local q heads.  Decode
    (Sq=1) instead relies on GSPMD with the context-sharded cache: one KV
    block, softmax stats combined with tiny all-reduces.
    """
    rules = active_rules()
    m, b, sq, h, hd = q.shape
    kvh = k.shape[3]
    g = h // kvh
    if rules is not None and sq > 1:
        nm = dict(rules.mesh.shape).get("model", 1)
        h_l = h // nm if h % nm == 0 else 0
        aligned = h_l > 0 and (g % h_l == 0 or h_l % g == 0)
        q_spec = rules.spec(("instances", "batch", None, "heads", None), q.shape)
        if aligned and q_spec[3] == "model":
            kv_div = kvh % nm == 0
            if not kv_div:
                # Expand KV to query heads so the head dim shards fully
                # local (replicating whole KV per rank costs more HBM than
                # the g-fold expansion sliced 1/nm ways: per-rank bytes go
                # kvh·hd -> h_l·hd, a win whenever h_l < kvh·nm ... i.e.
                # always, since h_l·nm = h = g·kvh ≥ kvh).
                k = jnp.repeat(k, g, axis=3)
                v = jnp.repeat(v, g, axis=3)
            kv_spec = rules.spec(
                ("instances", "batch", None,
                 "heads" if not kv_div else "kv_heads", None),
                k.shape,
            )
            pos_spec = rules.spec(("instances", "batch", None), q_pos.shape)

            def body(q_l, k_l, v_l, qp_l, kp_l):
                return _flash_body(
                    q_l, k_l, v_l, qp_l, kp_l, window=window, sink=sink,
                    causal=causal, q_chunk=q_chunk, kv_chunk=kv_chunk,
                )

            return jax.shard_map(
                body, mesh=rules.mesh,
                in_specs=(q_spec, kv_spec, kv_spec, pos_spec, pos_spec),
                out_specs=q_spec, check_vma=False,
            )(q, k, v, q_pos, kv_pos)
    return _flash_body(
        q, k, v, q_pos, kv_pos, window=window, sink=sink, causal=causal,
        q_chunk=q_chunk, kv_chunk=kv_chunk,
    )


def _flash_body(
    q, k, v, q_pos, kv_pos, *, window, sink, causal, q_chunk, kv_chunk
) -> jax.Array:
    """Chunked online-softmax attention on (possibly shard_map-local)
    arrays — see flash_attention."""
    m, b, sq, h, hd = q.shape
    skv, kvh = k.shape[2], k.shape[3]
    g = h // kvh
    use_window = isinstance(window, jax.Array) or window > 0
    qc = _pick_chunk(sq, q_chunk)
    # Single-token decode: one KV block over the whole cache.  The scan's
    # per-chunk dynamic-slice would otherwise walk the cache's context dim,
    # which is sharded over "model" (cache_seq rule) — GSPMD can't partition
    # a loop-varying slice of a sharded dim and would all-gather the KV
    # every chunk (§Perf tinyllama-decode iteration).  With one block, the
    # score/attend einsums contract the *local* context shard and GSPMD
    # combines the softmax stats with tiny all-reduces.  At Sq=1 the score
    # tensor is only (M,B,H,Skv) so nothing needs streaming.
    kc = skv if sq == 1 else _pick_chunk(skv, kv_chunk)
    n_q, n_kv = sq // qc, skv // kc
    scale = 1.0 / math.sqrt(hd)

    qg = q.reshape(m, b, sq, kvh, g, hd)
    # Pre-chunk the KV stream once.  This body runs either with no rules
    # (plain CPU tests) or as the LOCAL program inside flash_attention's
    # shard_map — never under GSPMD with sharded head dims, where every
    # chunked formulation we tried (per-q-chunk slices, in-body
    # dynamic-slice, shared xs, nested scan) re-gathered or replicated the
    # KV per while loop (§Perf qwen1.5-prefill iterations).
    k_ch = k.reshape(m, b, n_kv, kc, kvh, hd)
    v_ch = v.reshape(m, b, n_kv, kc, kvh, hd)
    kp_ch = kv_pos.reshape(m, b, n_kv, kc)

    out_chunks = []
    for qi in range(n_q):
        q_blk = qg[:, :, qi * qc : (qi + 1) * qc]              # (M,B,qc,KVH,G,hd)
        qp_blk = q_pos[:, :, qi * qc : (qi + 1) * qc]          # (M,B,qc)
        # causal block skip: kv chunks beyond this q chunk can't attend.
        n_need = n_kv if not causal or sq == 1 or n_q == 1 else min(
            n_kv, ((qi + 1) * qc + kc - 1) // kc
        )

        def kv_step(carry, xs, q_blk=q_blk, qp_blk=qp_blk):
            m_prev, l_prev, acc = carry
            k_blk, v_blk, kp_blk = xs                          # (M,B,kc,KVH,hd), .., (M,B,kc)
            s = jnp.einsum(
                "mbqkgd,mbckd->mbkgqc", q_blk, k_blk, preferred_element_type=jnp.float32
            ) * scale                                          # (M,B,KVH,G,qc,kc)
            valid = (kp_blk >= 0)[:, :, None, :]               # (M,B,1,kc)
            if causal:
                valid = valid & (kp_blk[:, :, None, :] <= qp_blk[:, :, :, None])
            if use_window:
                in_win = (
                    qp_blk[:, :, :, None] - kp_blk[:, :, None, :] < window
                )
                if sink > 0:
                    in_win = in_win | (kp_blk[:, :, None, :] < sink)
                valid = valid & in_win
            mask = valid[:, :, None, None, :, :]               # (M,B,1,1,qc|1,kc)
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m_prev, s.max(axis=-1))        # (M,B,KVH,G,qc)
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + p.sum(axis=-1)
            pv = jnp.einsum(
                "mbkgqc,mbckd->mbkgqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32,
            )
            acc = acc * corr[..., None] + pv
            return (m_new, l_new, acc), None

        init = (
            jnp.full((m, b, kvh, g, qc), NEG_INF, jnp.float32),
            jnp.zeros((m, b, kvh, g, qc), jnp.float32),
            jnp.zeros((m, b, kvh, g, qc, hd), jnp.float32),
        )
        xs = (
            jnp.moveaxis(k_ch[:, :, :n_need], 2, 0),
            jnp.moveaxis(v_ch[:, :, :n_need], 2, 0),
            jnp.moveaxis(kp_ch[:, :, :n_need], 2, 0),
        )
        (m_f, l_f, acc), _ = lax.scan(kv_step, init, xs)
        o = acc / jnp.maximum(l_f, 1e-30)[..., None]           # (M,B,KVH,G,qc,hd)
        out_chunks.append(jnp.moveaxis(o, -2, 2))              # (M,B,qc,KVH,G,hd)
    out = jnp.concatenate(out_chunks, axis=2) if n_q > 1 else out_chunks[0]
    return out.reshape(m, b, sq, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    """Ring-buffer KV cache for one layer stack.

    k, v: (L, M, B, S_cache, KVH, hd).  ``S_cache`` is the full context
    for dense attention or the window size for sliding-window attention.
    Absolute positions of slots are reconstructed arithmetically from the
    decode position, so no position array is stored.
    """
    k: jax.Array
    v: jax.Array


def make_kv_cache(
    num_layers: int, m: int, b: int, s_cache: int, kvh: int, hd: int, dtype
) -> KVCache:
    shape = (num_layers, m, b, s_cache, kvh, hd)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def cache_slot_positions(pos: jax.Array, s_cache: int) -> jax.Array:
    """Absolute position held by each ring-buffer slot *after* writing the
    token at ``pos`` into slot ``pos % s_cache``.

    pos: (M,B) int32 -> (M,B,S_cache) int32, -1 where the slot is empty.
    """
    slots = jnp.arange(s_cache, dtype=jnp.int32)
    cur = pos[..., None] % s_cache
    base = pos[..., None] - cur                      # start of current wrap
    p = jnp.where(slots <= cur, base + slots, base - s_cache + slots)
    return jnp.where(p >= 0, p, -1)


def cache_positions_after(last_pos: jax.Array, s_cache: int, pin: int = 0) -> jax.Array:
    """Slot -> absolute-position map after writing every position up to
    ``last_pos`` (inclusive; -1 = nothing written yet), for a cache whose
    first ``pin`` slots are pinned (slot j holds position j forever —
    Hymba meta tokens) and whose remaining ``s_cache - pin`` slots ring
    over positions >= pin (``pin=0`` is the plain ring/full layout of
    :func:`cache_slot_positions`).

    last_pos: (M,B) int32 -> (M,B,S_cache) int32, -1 marking empty slots.
    This is the *mid-prompt* generalization of ``cache_slot_positions``:
    the chunked prefill uses it to label the cache as it stood BEFORE the
    chunk being processed (``last_pos = offset - 1``), including while
    the pinned prefix itself is still being filled.
    """
    slots = jnp.arange(s_cache, dtype=jnp.int32)
    last = last_pos[..., None]
    w = s_cache - pin
    pinned = jnp.where(slots <= last, slots, -1)
    if w <= 0:
        return pinned
    q = last - pin                                   # ring-relative last
    cur = q % w                                      # garbage when q < 0 (masked)
    base = q - cur
    i = slots - pin
    p = jnp.where(i <= cur, base + i, base - w + i) + pin
    ring = jnp.where((q >= 0) & (p >= pin), p, -1)
    return jnp.where(slots < pin, pinned, ring)


def cache_append_chunk(
    cache_layer: jax.Array, new: jax.Array, positions: jax.Array, pin: int = 0,
    valid: jax.Array | None = None,
) -> jax.Array:
    """Write a chunk of k/v rows into their cache slots.

    cache_layer: (M,B,S,KVH,hd); new: (M,B,C,KVH,hd); positions: (M,B,C)
    absolute positions.  Slot rule matches :func:`cache_positions_after`:
    position p lands at slot p when p < pin, else pin + (p - pin) % W.
    Positions inside one chunk must map to distinct slots (the serving
    runtime clamps the chunk size to the ring width), so the scatter has
    no duplicate indices.

    ``valid`` (M,B,C) bool masks the scatter: invalid rows (the junk
    suffix of a padded final chunk — tail folding) are routed to an
    out-of-range slot and dropped, so padding can neither occupy fresh
    slots nor wrap the ring over live entries.
    """
    m, b, s, kvh, hd = cache_layer.shape
    c = new.shape[2]
    w = max(s - pin, 1)
    slots = jnp.where(positions < pin, positions, pin + (positions - pin) % w)
    if valid is not None:
        slots = jnp.where(valid, slots, s)

    def upd(cl, x, sl):
        return cl.at[sl].set(x, mode="drop")

    out = jax.vmap(upd)(
        cache_layer.reshape(m * b, s, kvh, hd),
        new.astype(cache_layer.dtype).reshape(m * b, c, kvh, hd),
        slots.reshape(m * b, c).astype(jnp.int32),
    )
    return out.reshape(m, b, s, kvh, hd)


LANES = 128  # the minor dim of a TPU tile


def kv_stack(c: jax.Array) -> jax.Array:
    """A (..., S, KVH, hd) K/V cache in its physical order on a TPU.
    Where hd is not a multiple of the 128 lanes the TPU lays the cache
    out with S minor rather than pad hd, and the view is (..., KVH*hd,
    S); else the cache is row-major and returned as it is.  XLA makes
    the view a bitcast, and in-place writes of one token's slot into it
    need no relayout of the cache."""
    if c.shape[-1] % LANES == 0:
        return c
    return jnp.swapaxes(c.reshape(c.shape[:-2] + (-1,)), -1, -2)


def kv_unstack(c: jax.Array, kv_heads: int, head_dim: int) -> jax.Array:
    """Inverse of :func:`kv_stack`: back to (..., S, KVH, hd)."""
    if head_dim % LANES == 0:
        return c
    c = jnp.swapaxes(c, -1, -2)
    return c.reshape(c.shape[:-1] + (kv_heads, head_dim))


def cache_update_one(
    cache_k: jax.Array,
    cache_v: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    pos: jax.Array,
    slot: jax.Array | None = None,
    live: jax.Array | None = None,
    layer: jax.Array | int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Insert one token's k/v at slot pos % S (or an explicit slot) for
    every (m, b).

    cache_*: one layer's (M,B,S,KVH,hd); or, with ``layer``, every
    layer's stack, (L,M,B,S,KVH,hd) or the slots-minor :func:`kv_stack`
    view (L,M,B,KVH*hd,S) that the dense decode scan carries, written at
    that layer.  k_new/v_new: (M,B,1,KVH,hd); pos: (M,B).  ``live``
    (M,B) bool: a lane where it is False writes back what it had, so a
    stopped lane's cache stays bit for bit as it was.  Each lane reads
    and writes one slot of one layer (a window of up to 128 slots where
    S is minor); the rest of the cache is never touched.
    """
    if layer is None:
        ck, cv = cache_update_one(cache_k[None], cache_v[None], k_new,
                                  v_new, pos, slot, live, layer=0)
        return ck[0], cv[0]
    if slot is None:
        slot = pos % cache_k.shape[-1 if cache_k.ndim == 5 else 3]
    slot = slot.astype(jnp.int32)
    if live is None:
        live = jnp.ones(slot.shape, bool)
    layer = jnp.asarray(layer, jnp.int32)
    rows = (k_new[:, :, 0], v_new[:, :, 0])

    rules = active_rules()
    if rules is not None:
        return _cache_update_sharded(
            rules, cache_k, cache_v, *rows, slot, live, layer)
    return _write_slots((cache_k, cache_v), rows, layer, slot, live)


def _write_slots(caches, rows, layer, slot, live):
    """Write ``rows[i][m, b]`` (KVH, hd) at slot ``slot[m, b]`` of layer
    ``layer`` of ``caches[i]`` (as :func:`cache_update_one` takes them)
    where ``live[m, b]``.  One lane at a time, through a window of the
    slots that share the written one's tile: 128 slots where S is minor,
    else the one slot.  The whole window is written, its other slots
    with what they held."""
    m, b = slot.shape
    shape = caches[0].shape
    minor = len(shape) == 5                 # (L,M,B,KVH*hd,S)
    s = shape[-1] if minor else shape[3]
    w = min(s, LANES) if minor else 1
    size = (1, 1, 1) + ((shape[3], w) if minor else (1,) + shape[4:])
    slots = jnp.arange(w, dtype=jnp.int32)
    zero = jnp.int32(0)

    def one(j, caches):
        mi, bi = j // b, j % b
        sl = slot[mi, bi]
        start = jnp.minimum(sl // w * w, s - w)
        at = (layer, mi, bi) + ((zero, start) if minor else (start, zero, zero))
        hit = (slots == sl - start) & live[mi, bi]
        out = []
        for c, r in zip(caches, rows):
            r = r[mi, bi].astype(c.dtype)
            r, h = ((r.reshape(-1, 1), hit[None, :]) if minor
                    else (r[None], hit[:, None, None]))
            win = lax.dynamic_slice(c, at, size)
            out.append(lax.dynamic_update_slice(c, jnp.where(h, r, win), at))
        return tuple(out)

    return lax.fori_loop(0, m * b, one, tuple(caches))


def _cache_update_sharded(rules, ck, cv, k_new, v_new, slot, live, layer):
    """Ring-buffer insert when the cache's context dim is sharded
    (cache_seq -> "model", §Perf tinyllama-decode iteration).

    A write along a sharded dim at a data-dependent slot would make
    GSPMD replicate the whole cache; instead each device checks whether
    the slot falls inside its local context shard and writes its rows
    there (no collectives beyond broadcasting the 1-token k/v).  The
    merged KVH*hd dim of a slots-minor stack takes the kv heads' mesh
    axes unless the head dim is split too."""
    minor = ck.ndim == 5
    kvh, hd = k_new.shape[-2:]
    s_axis = 4 if minor else 3
    spec = rules.spec(
        ("layers", "instances", "batch", "cache_seq", "kv_heads", "kv_hd"),
        ck.shape[:3] + (ck.shape[s_axis], kvh, hd))
    if minor:
        f_ax = spec[4] if spec[5] is None else None
        cache_spec = P(*spec[:3], f_ax, spec[3])
        new_spec = P(spec[1], spec[2], f_ax, None)
    else:
        cache_spec = spec
        new_spec = P(spec[1], spec[2], spec[4], spec[5])
    grid_spec = P(spec[1], spec[2])
    seq_axes = spec[3]  # mesh axes carrying the context dim (or None)
    seq_axes = (
        (seq_axes,) if isinstance(seq_axes, str) else tuple(seq_axes or ())
    )

    def body(ck_l, cv_l, kn_l, vn_l, slot_l, live_l):
        s_l = ck_l.shape[s_axis]
        shard = jnp.int32(0)
        for a in seq_axes:
            shard = shard * rules.mesh.shape[a] + lax.axis_index(a)
        loc = slot_l - shard * s_l                 # (m_l, b_l) local slot
        ok = live_l & (loc >= 0) & (loc < s_l)
        return _write_slots((ck_l, cv_l), (kn_l, vn_l), layer,
                            jnp.clip(loc, 0, s_l - 1), ok)

    return jax.shard_map(
        body, mesh=rules.mesh,
        in_specs=(cache_spec, cache_spec, new_spec, new_spec, grid_spec,
                  grid_spec),
        out_specs=(cache_spec, cache_spec),
        check_vma=False,
    )(ck, cv, k_new, v_new, slot, live)


# ---------------------------------------------------------------------------
# full GQA attention block (projection + rope + attention + out-proj)
# ---------------------------------------------------------------------------


def gqa_attention(
    x: jax.Array,
    p: dict,
    *,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    rope_theta: float,
    positions: jax.Array,
    window: int | jax.Array = 0,
    sink: int = 0,
    causal: bool = True,
    cache: tuple[jax.Array, jax.Array] | None = None,
    decode_pos: jax.Array | None = None,
    cache_slot: jax.Array | None = None,
    cache_kv_pos: jax.Array | None = None,
    kv_override: tuple[jax.Array, jax.Array] | None = None,
    use_kernel: bool = False,
    live: jax.Array | None = None,
    cache_layer: jax.Array | None = None,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array] | None]:
    """Merged multi-instance GQA attention.

    x: (M,B,S,D). ``p`` holds wq (M,D,H*hd), wk/wv (M,D,KVH*hd),
    wo (M,H*hd,D) and optional bq/bk/bv.  Three modes:

    * train/prefill: cache is None — self-attention over x.
    * decode: cache=(k,v) for this layer, decode_pos (M,B) — S must be 1;
      returns the updated cache; a lane whose ``live`` is False keeps
      its cache as it was (:func:`cache_update_one`).  With
      ``cache_layer`` the cache is every layer's stack in the
      :func:`kv_stack` layout, written in place at that layer.
    * cross-attention: kv_override provides precomputed (k, v).
    """
    m, b, s, d = x.shape
    h, kvh, hd = num_heads, num_kv_heads, head_dim

    # constrain the FLAT projections before the head-split reshape: going
    # straight from a seq-sharded residual to a head-sharded 5-d tensor
    # trips SPMD's resharding fallback (full rematerialization); gathering
    # seq on the flat matmul output is the Megatron-SP transition point.
    q = constrain(
        linear(x, p["wq"], p.get("bq")), "instances", "batch", None, "heads_flat"
    ).reshape(m, b, s, h, hd)
    if kv_override is None:
        k = constrain(
            linear(x, p["wk"], p.get("bk")), "instances", "batch", None, "kv_flat"
        ).reshape(m, b, s, kvh, hd)
        v = constrain(
            linear(x, p["wv"], p.get("bv")), "instances", "batch", None, "kv_flat"
        ).reshape(m, b, s, kvh, hd)
    else:
        k, v = kv_override
    q = constrain(q, "instances", "batch", None, "heads", None)
    k = constrain(k, "instances", "batch", None, "kv_heads", None)
    v = constrain(v, "instances", "batch", None, "kv_heads", None)

    if rope_theta > 0 and kv_override is None:
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
    elif rope_theta > 0:
        q = rope(q, positions, rope_theta)

    new_cache = None
    if cache is not None:
        assert s == 1 and decode_pos is not None
        ck, cv = new_cache = cache_update_one(
            cache[0], cache[1], k, v, decode_pos, slot=cache_slot,
            live=live, layer=cache_layer)
        if cache_layer is not None:
            ck, cv = (kv_unstack(c[cache_layer], kvh, hd) for c in new_cache)
        s_cache = ck.shape[2]
        kv_pos = (
            cache_kv_pos if cache_kv_pos is not None
            else cache_slot_positions(decode_pos, s_cache)
        )                                                      # (M,B,S_cache)
        q_pos = decode_pos[..., None]                          # (M,B,1)
        if (
            use_kernel
            and not isinstance(window, jax.Array)
            and (window <= 0 or window >= s_cache)
        ):
            # plain-ring no-window decode (window >= S includes hybrid's
            # GLOBAL_WINDOW sentinel — the mask never bites once the
            # ring itself caps history at S): slots [0, min(pos+1, S))
            # are exactly the valid set, which is the flash-decode
            # kernel's kv_len prefix contract (kernels/decode_attn.py).
            # use_kernel=True is the caller asserting the plain-ring
            # layout (slot = pos % S, kv positions = slot positions)
            from repro.kernels import ops as _K
            from repro.models.common import active_rules

            kv_len = jnp.minimum(decode_pos + 1, s_cache).astype(jnp.int32)
            o = _K.decode_attention(
                q[:, :, 0], ck, cv, kv_len, rules=active_rules()
            )[:, :, None]
        else:
            o = flash_attention(
                q, ck, cv, q_pos, kv_pos, window=window, sink=sink, causal=True
            )
    else:
        q_pos = positions
        if kv_override is not None:
            kv_pos = jnp.broadcast_to(
                jnp.arange(k.shape[2], dtype=jnp.int32), (m, b, k.shape[2])
            )
        else:
            kv_pos = positions
        o = flash_attention(
            q, k, v, q_pos, kv_pos, window=window, sink=sink, causal=causal
        )

    o = o.reshape(m, b, s, h * hd)
    out = linear(o, p["wo"], p.get("bo"))
    out = constrain(out, "instances", "batch", "seq", "act_embed")
    return out, new_cache
