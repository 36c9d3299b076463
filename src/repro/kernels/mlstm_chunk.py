"""Pallas TPU kernel: chunkwise-parallel mLSTM sequence (xLSTM's matrix
memory) with the (C, n, m) state resident in VMEM across chunks.

Companion to kernels/slstm_cell.py (§Perf xlstm pair B): the lax.scan
formulation writes the (hd, hd) matrix memory to HBM at every chunk
boundary; here each grid program owns one (instance, head), carries the
state in VMEM scratch across the sequence-chunk grid axis (the
revisiting pattern), and streams q/k/v/gates in, h out.  The intra-chunk
part is the same masked-matmul form as repro.models.ssm._mlstm_chunk:

    b_t   = cumsum(lf);  g = cummax(li - b);  m_t = b + max(m0, g)
    D     = tril(exp(li_s + b_t - b_s - m_t))
    h     = [ (q k^T/√d · D) v + exp(b + m0 - m_t)·(q C0/√d) ] / denom
    C'    = exp(b_S + m0 - m_S)·C0 + (exp(li + b_S - b - m_S)·k)^T v

Grid: (M, H, S/cs).  Batch rides inside the block so every matmul is
(B·cs)-row MXU work.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _kernel(q_ref, k_ref, v_ref, lf_ref, li_ref,
            hs_ref, cf_ref, nf_ref, mf_ref,
            c_s, n_s, m_s, *, cs: int, ns: int, hd: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        c_s[...] = jnp.zeros_like(c_s)
        n_s[...] = jnp.zeros_like(n_s)
        m_s[...] = jnp.full_like(m_s, -1e30)

    f32 = jnp.float32
    q = q_ref[0, :, 0].astype(f32)                   # (B, cs, hd)
    k = k_ref[0, :, 0].astype(f32)
    v = v_ref[0, :, 0].astype(f32)
    lf = lf_ref[0, :, 0].astype(f32)                 # (B, cs)
    li = li_ref[0, :, 0].astype(f32)

    C0 = c_s[...]                                    # (B, hd, hd) f32
    n0 = n_s[...]                                    # (B, hd)
    m0 = m_s[:, 0]                                   # (B,)

    b = jnp.cumsum(lf, axis=-1)                      # (B, cs)
    g = jax.lax.cummax(li - b, axis=1)
    mt = b + jnp.maximum(m0[:, None], g)             # (B, cs)
    a_inter = jnp.exp(b + m0[:, None] - mt)

    logD = li[:, None, :] - b[:, None, :] + b[:, :, None] - mt[:, :, None]
    tri = jnp.tril(jnp.ones((cs, cs), jnp.bool_))
    D = jnp.where(tri[None], jnp.exp(logD), 0.0)     # (B, cs_t, cs_s)

    scale = 1.0 / math.sqrt(hd)
    s_qk = jnp.einsum("btd,bsd->bts", q, k, preferred_element_type=f32) * scale
    w = s_qk * D
    num = jnp.einsum("bts,bsd->btd", w, v, preferred_element_type=f32)
    num = num + a_inter[..., None] * jnp.einsum(
        "btd,bde->bte", q, C0, preferred_element_type=f32) * scale
    den = w.sum(-1) + a_inter * jnp.einsum(
        "btd,bd->bt", q, n0, preferred_element_type=f32) * scale
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-mt))[..., None]
    hs_ref[0, :, 0] = h.astype(hs_ref.dtype)         # (B, cs, hd)

    m_end = mt[:, -1]                                # (B,)
    w_end = jnp.exp(li + b[:, -1:] - b - m_end[:, None])   # (B, cs)
    decay0 = jnp.exp(b[:, -1] + m0 - m_end)
    c_s[...] = decay0[:, None, None] * C0 + jnp.einsum(
        "bs,bsd,bse->bde", w_end, k, v, preferred_element_type=f32)
    n_s[...] = decay0[:, None] * n0 + jnp.einsum(
        "bs,bsd->bd", w_end, k, preferred_element_type=f32)
    m_s[...] = m_end[:, None]

    @pl.when(si == ns - 1)
    def _done():
        cf_ref[0, :, 0] = c_s[...]
        nf_ref[0, :, 0] = n_s[...]
        mf_ref[0, :, 0] = m_s[:, 0]


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)


def _clamp(block: int, dim: int) -> int:
    b = min(block, dim)
    while dim % b:
        b -= 1
    return b


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunkwise(
    q: jax.Array, k: jax.Array, v: jax.Array,
    lf: jax.Array, li: jax.Array,
    *, chunk: int = 128, interpret: bool | None = None,
):
    """Chunkwise mLSTM from zero state.

    q,k,v: (M,B,H,S,hd); lf,li: (M,B,H,S) f32 (log-forget / input-gate
    pre-activations).  Returns (h (M,B,H,S,hd) in q.dtype, final state
    (C (M,B,H,hd,hd) f32, n (M,B,H,hd) f32, m (M,B,H) f32)) — the same
    contract as repro.models.ssm.mlstm_sequence with state=None.
    """
    m, bb, hh, s, hd = q.shape
    cs = _clamp(chunk, s)
    ns = s // cs
    grid = (m, hh, ns)

    seq_spec = pl.BlockSpec((1, bb, 1, cs, hd), lambda mi, hi, si: (mi, 0, hi, si, 0))
    gate_spec = pl.BlockSpec((1, bb, 1, cs), lambda mi, hi, si: (mi, 0, hi, si))
    st_spec = lambda *tail: pl.BlockSpec(
        (1, bb, 1) + tail, lambda mi, hi, si: (mi, 0, hi) + (0,) * len(tail))

    out_shape = (
        jax.ShapeDtypeStruct((m, bb, hh, s, hd), q.dtype),
        jax.ShapeDtypeStruct((m, bb, hh, hd, hd), jnp.float32),
        jax.ShapeDtypeStruct((m, bb, hh, hd), jnp.float32),
        jax.ShapeDtypeStruct((m, bb, hh), jnp.float32),
    )
    hs, cf, nf, mf = pl.pallas_call(
        functools.partial(_kernel, cs=cs, ns=ns, hd=hd),
        grid=grid,
        in_specs=[seq_spec, seq_spec, seq_spec, gate_spec, gate_spec],
        out_specs=[
            seq_spec,
            st_spec(hd, hd),
            st_spec(hd),
            pl.BlockSpec((1, bb, 1), lambda mi, hi, si: (mi, 0, hi)),
        ],
        out_shape=out_shape,
        scratch_shapes=[_vmem((bb, hd, hd)), _vmem((bb, hd)), _vmem((bb, 1))],
        interpret=interpret_mode(interpret),
    )(q, k, v, lf.astype(jnp.float32), li.astype(jnp.float32))
    return hs, (cf, nf, mf)
