"""Pallas TPU kernel: single-token GQA decode attention over a KV cache.

The serving hot spot after NetFuse merging: every fused decode step reads
each instance's KV cache once.  TPU adaptation of flash-decoding: the
cache's S axis is streamed through VMEM in blocks as the innermost grid
axis; online-softmax running (max, sum, acc) state lives in VMEM scratch
across S-steps (grid revisiting pattern), and the lane's q heads (H x hd)
are resident the whole time.

Every block keeps all kv heads: the cache is viewed as (M, B, S,
KVH*hd), so a block's last two dims are (S-block, KVH*hd) and the q/out
blocks' are the full (H, hd) — the TPU's (8, 128) tiling rule holds at
any head count or head width.  Heads are separated in-kernel by static
lane slices.

Grid: (M, B, S/bs).  Masking: prefix-valid cache of length
kv_len[m, b] (scalar-prefetch operand), block positions via iota.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def seq_block(block: int, dim: int) -> int:
    """Sequence-axis block: the largest divisor of ``dim`` that is at
    most ``block`` and a multiple of 8 (the TPU sublane tile), else the
    whole axis."""
    for b in range(min(block, dim) // 8 * 8, 0, -8):
        if dim % b == 0:
            return b
    return dim


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            ns: int, bs: int, kvh: int, g: int, hd: int):
    mi, bi, si = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # (H, hd)
    k = k_ref[0, 0].astype(jnp.float32)              # (bs, KVH*hd)
    v = v_ref[0, 0].astype(jnp.float32)
    pos = si * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    live = pos < len_ref[mi, bi]

    for kh in range(kvh):                            # static head loop
        rows = slice(kh * g, (kh + 1) * g)
        cols = slice(kh * hd, (kh + 1) * hd)
        s = jnp.dot(q[rows], k[:, cols].T)
        s = jnp.where(live, s / math.sqrt(hd), NEG_INF)  # (G, bs)
        m_prev = m_ref[rows]                         # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[rows] = l_ref[rows] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[rows] = acc_ref[rows] * corr + jnp.dot(p, v[:, cols])
        m_ref[rows] = m_new

    @pl.when(si == ns - 1)
    def _done():
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_len: jax.Array,
    *,
    block_s: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """q: (M,B,H,hd); k,v: (M,B,S,KVH,hd); kv_len: (M,B) int32.
    Returns (M,B,H,hd)."""
    m, b, h, hd = q.shape
    s, kvh = k.shape[2], k.shape[3]
    bs = seq_block(block_s, s)
    ns = s // bs
    lane = lambda mi, bi, si, ln: (mi, bi, 0, 0)
    kv_spec = pl.BlockSpec((1, 1, bs, kvh * hd),
                           lambda mi, bi, si, ln: (mi, bi, si, 0))
    return pl.pallas_call(
        functools.partial(_kernel, ns=ns, bs=bs, kvh=kvh, g=h // kvh, hd=hd),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m, b, ns),
            in_specs=[pl.BlockSpec((1, 1, h, hd), lane), kv_spec, kv_spec],
            out_specs=pl.BlockSpec((1, 1, h, hd), lane),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((m, b, h, hd), q.dtype),
        interpret=interpret_mode(interpret),
    )(kv_len.astype(jnp.int32), q, k.reshape(m, b, s, kvh * hd),
      v.reshape(m, b, s, kvh * hd))


def decode_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_len: jax.Array,
    *,
    rules,
    **kw,
) -> jax.Array:
    """``decode_attention`` under ``shard_map`` on the rules' mesh.

    Serving layout: (M, B) rides the data axes and KV-head groups ride
    "model" — the head-grouping recipe is ``tp_head_plan`` (shared with
    the decode-layer megakernel's shard_map variant).  "kv": each rank
    owns KVH/n kv heads plus their grouped q heads end-to-end (q heads
    are laid out kvh-major, so a contiguous H-split of KVH/n groups
    matches a contiguous KVH-split).  "expand" (GQA/MQA where the kv
    heads don't split): KV is expanded to one head per q head, so any
    H-split works — per-rank KV bytes go kvh*hd -> (h/n)*hd, still a
    strict reduction whenever n > g.  Exact with no collectives; falls
    back to the plain (GSPMD-partitioned) call only when the q heads
    themselves can't split.
    """
    from repro.kernels.decode_layer import tp_head_plan

    m, b, h, hd = q.shape
    s, kvh = k.shape[2], k.shape[3]
    n_model = rules._axis_size(rules.mapping.get("kv_heads"))
    plan = tp_head_plan(h, kvh, n_model)
    if plan is None:
        # q heads can't split — run data-local (heads replicated over
        # "model").  A bare pallas_call under GSPMD is not safe here:
        # the partitioner splits the (M, B) grid while the kernel
        # indexes the scalar-prefetched kv_len with global program ids
        q_rep = rules.spec(("instances", "batch", None, None), q.shape)
        kv_rep = rules.spec(("instances", "batch", None, None, None), k.shape)
        len_spec = rules.spec(("instances", "batch"), (m, b))
        return jax.shard_map(
            lambda ql, kl, vl, ll: decode_attention(ql, kl, vl, ll, **kw),
            mesh=rules.mesh,
            in_specs=(q_rep, kv_rep, kv_rep, len_spec),
            out_specs=q_rep,
            check_vma=False,
        )(q, k, v, kv_len)
    if plan == "expand":
        g = h // kvh
        k = jnp.repeat(k, g, axis=3)
        v = jnp.repeat(v, g, axis=3)

    q_spec = rules.spec(("instances", "batch", "kv_heads", None), (m, b, h, hd))
    kv_spec = rules.spec(
        ("instances", "batch", None, "kv_heads", None), k.shape
    )
    len_spec = rules.spec(("instances", "batch"), (m, b))
    return jax.shard_map(
        lambda ql, kl, vl, ll: decode_attention(ql, kl, vl, ll, **kw),
        mesh=rules.mesh,
        in_specs=(q_spec, kv_spec, kv_spec, len_spec),
        out_specs=q_spec,
        check_vma=False,
    )(q, k, v, kv_len)
