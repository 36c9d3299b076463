"""Pallas TPU kernel: chunked-prefill GQA flash attention over a KV cache.

The serving admission hot spot after tail folding: every chunk call
attends a C-token query block over ``[cache-before-chunk, chunk]``.
This extends ``decode_attn.py`` from q-len 1 to q-len C — the cache's S
axis streams through VMEM in blocks as the innermost grid axis, online-
softmax running (max, sum, acc) state lives in VMEM scratch across
S-steps (grid revisiting pattern), and the lane's chunk queries (C x
H*hd) are resident the whole time.  As in ``decode_attn.py``, blocks
keep every head (q as (C, H*hd), the cache as (S-block, KVH*hd)) and
heads are separated in-kernel by static lane slices.

Masking is ARITHMETIC, driven by the scalar-prefetched per-lane offsets
(the absolute position of each lane's first chunk token): slot j of a
pinned-prefix ring cache holds position j forever when j < pin (Hymba
meta tokens), else rings over positions >= pin — exactly
``layers.cache_positions_after(offset-1, S, pin)``; the appended chunk
rows (slots >= S_cache) sit at offset + (slot - S_cache).  One rule
covers causality, the sliding window, ring validity and the attention
sink, so the dense O((S+C)·C) position/mask tensors the XLA path
materializes per layer never exist here.

Grid: (M, B, T/bs) with T = S_cache + C.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode
from repro.kernels.decode_attn import seq_block

NEG_INF = -1e30


def _kernel(off_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            ns: int, bs: int, c: int, h: int, g: int, hd: int, s_cache: int,
            pin: int, window: int, sink: int, causal: bool):
    mi, bi, si = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                         # (C, H*hd)
    k = k_ref[0, 0].astype(jnp.float32)                         # (bs, KVH*hd)
    v = v_ref[0, 0].astype(jnp.float32)

    # positions from the lane offset alone
    off = off_ref[mi, bi]
    ci = jax.lax.broadcasted_iota(jnp.int32, (c, bs), 0)
    slot = si * bs + jax.lax.broadcasted_iota(jnp.int32, (c, bs), 1)
    q_pos = off + ci
    # cache slots: pinned prefix + ring over positions >= pin
    # (== layers.cache_positions_after(off - 1, s_cache, pin))
    last = off - 1
    pinned = jnp.where(slot <= last, slot, -1)
    w = s_cache - pin
    if w > 0:
        qq = last - pin
        cur = qq % w
        base = qq - cur
        i2 = slot - pin
        ring = jnp.where(i2 <= cur, base + i2, base - w + i2) + pin
        ring = jnp.where((qq >= 0) & (ring >= pin), ring, -1)
        cache_pos = jnp.where(slot < pin, pinned, ring)
    else:
        cache_pos = pinned
    # appended chunk rows ride at their own absolute positions
    p = jnp.where(slot < s_cache, cache_pos, off + slot - s_cache)

    valid = p >= 0
    if causal:
        valid = valid & (p <= q_pos)
    if window > 0:
        in_win = q_pos - p < window
        if sink > 0:
            in_win = in_win | (p < sink)
        valid = valid & in_win

    for hi in range(h):                      # static loop over q heads
        kh = hi // g
        qh = q[:, hi * hd:(hi + 1) * hd]                        # (C, hd)
        kv_cols = slice(kh * hd, (kh + 1) * hd)
        s = jnp.dot(qh, k[:, kv_cols].T)
        s = jnp.where(valid, s / math.sqrt(hd), NEG_INF)         # (C, bs)
        m_prev = m_ref[hi]                                      # (C, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[hi] = l_ref[hi] * corr + pexp.sum(axis=-1, keepdims=True)
        acc_ref[hi] = acc_ref[hi] * corr + jnp.dot(
            pexp, v[:, kv_cols])
        m_ref[hi] = m_new

    @pl.when(si == ns - 1)
    def _done():
        for hi in range(h):
            o_ref[0, 0, :, hi * hd:(hi + 1) * hd] = (
                acc_ref[hi] / jnp.maximum(l_ref[hi], 1e-30)
            ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "s_cache", "pin", "window", "sink", "causal", "block_s", "interpret"))
def chunk_prefill_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    offset: jax.Array,
    *,
    s_cache: int,
    pin: int = 0,
    window: int = 0,
    sink: int = 0,
    causal: bool = True,
    block_s: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """q: (M,B,C,H,hd); k,v: (M,B,T,KVH,hd) with T = s_cache + C — the
    pre-chunk cache concatenated with the chunk's own k/v; offset: (M,B)
    int32 absolute position of each lane's first chunk token.
    Returns (M,B,C,H,hd)."""
    m, b, c, h, hd = q.shape
    t, kvh = k.shape[2], k.shape[3]
    assert t == s_cache + c, (t, s_cache, c)
    bs = seq_block(block_s, t)
    ns = t // bs
    q_spec = pl.BlockSpec((1, 1, c, h * hd),
                          lambda mi, bi, si, off: (mi, bi, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, bs, kvh * hd),
                           lambda mi, bi, si, off: (mi, bi, si, 0))
    out = pl.pallas_call(
        functools.partial(
            _kernel, ns=ns, bs=bs, c=c, h=h, g=h // kvh, hd=hd,
            s_cache=s_cache, pin=pin, window=window, sink=sink,
            causal=causal,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m, b, ns),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((h, c, 1), jnp.float32),
                pltpu.VMEM((h, c, 1), jnp.float32),
                pltpu.VMEM((h, c, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((m, b, c, h * hd), q.dtype),
        interpret=interpret_mode(interpret),
    )(offset.astype(jnp.int32), q.reshape(m, b, c, h * hd),
      k.reshape(m, b, t, kvh * hd), v.reshape(m, b, t, kvh * hd))
    return out.reshape(m, b, c, h, hd)


def chunk_prefill_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    offset: jax.Array,
    *,
    rules,
    **kw,
) -> jax.Array:
    """``chunk_prefill_attention`` under ``shard_map`` on the rules' mesh.

    Serving layout mirrors ``decode_attention_sharded``: (M, B) lanes
    ride the data axes and KV-head groups ride "model" — q heads are
    kvh-major, so a contiguous H-split of KVH/n groups matches a
    contiguous KVH-split; each rank runs the kernel on its local block
    with the (replicated) lane offsets and writes its output shard.
    Exact with no collectives; interpret-mode fallback intact.  Falls
    back to the plain (GSPMD-partitioned) call when KVH doesn't divide
    the model axis.
    """

    m, b, c, h, hd = q.shape
    t, kvh = k.shape[2], k.shape[3]
    n_model = rules._axis_size(rules.mapping.get("kv_heads"))
    if n_model <= 1 or kvh % n_model or h % n_model:
        return chunk_prefill_attention(q, k, v, offset, **kw)

    q_spec = rules.spec(("instances", "batch", None, "kv_heads", None),
                        (m, b, c, h, hd))
    kv_spec = rules.spec(("instances", "batch", None, "kv_heads", None),
                         (m, b, t, kvh, hd))
    off_spec = rules.spec(("instances", "batch"), (m, b))
    return jax.shard_map(
        lambda ql, kl, vl, ol: chunk_prefill_attention(ql, kl, vl, ol, **kw),
        mesh=rules.mesh,
        in_specs=(q_spec, kv_spec, kv_spec, off_spec),
        out_specs=q_spec,
        check_vma=False,
    )(q, k, v, offset)
