"""Pallas TPU kernel: sLSTM cell — the full recurrent scan of one block.

§Perf xlstm pair B named this the next lever: the lax.scan formulation
round-trips the (c, n, h, m) state and ~10 gate intermediates through
HBM every timestep.  On TPU the natural shape is ONE kernel that owns
the whole sequence: the state lives in VMEM scratch across all S steps,
pre-activations stream in S-chunks, and only the h outputs stream back —
HBM traffic drops from O(S · 10 · B · D) residuals to the unavoidable
O(S · B · D) in/out streams.

Math (identical to repro.models.ssm.slstm_block's step, exponential
gating with the m-stabilizer):

    rec_g = h_{t-1} @ r_g          (per-head block-diagonal, g ∈ z,i,f,o)
    z,i,f,o = pre_t[g] + rec_g
    lf = log_sigmoid(f);  m_t = max(lf + m_{t-1}, i)
    c_t = exp(lf + m_{t-1} - m_t) · c + exp(i - m_t) · tanh(z)
    n_t = exp(lf + m_{t-1} - m_t) · n + exp(i - m_t)
    h_t = sigmoid(o) · c_t / max(n_t, 1e-6)

Grid: (M, H, S/cs) — instances × heads × sequence chunks.  Heads are
independent (block-diagonal recurrence), so each program owns one
(instance, head) and carries (c, n, h, m) ∈ (B, hd) f32 scratch across
the S-axis grid steps (the same revisiting pattern as the fused-matmul
K axis).  The per-step recurrent matvec batches over B into a
(B, hd)x(hd, hd) MXU matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _kernel(pre_ref, r_ref, c0_ref, n0_ref, h0_ref, m0_ref,
            hs_ref, cf_ref, nf_ref, hf_ref, mf_ref,
            c_s, n_s, h_s, m_s, *, cs: int, ns: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        c_s[...] = c0_ref[0, :, 0].astype(jnp.float32)
        n_s[...] = n0_ref[0, :, 0].astype(jnp.float32)
        h_s[...] = h0_ref[0, :, 0].astype(jnp.float32)
        m_s[...] = m0_ref[0, :, 0].astype(jnp.float32)

    r = r_ref[0, :, 0].astype(jnp.float32)           # (4, hd, hd)

    def step(t, _):
        pre_t = pre_ref[0, :, t, :, 0].astype(jnp.float32)  # (B, 4, hd)
        h_prev = h_s[...]                             # (B, hd) f32
        rec = jax.lax.dot_general(
            h_prev, r,                                 # (B,hd) x (4,hd,hd)
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # (B, 4, hd)
        zt = pre_t[:, 0] + rec[:, 0]
        it = pre_t[:, 1] + rec[:, 1]
        ft = pre_t[:, 2] + rec[:, 2]
        ot = pre_t[:, 3] + rec[:, 3]
        lf = jax.nn.log_sigmoid(ft)
        mt = jnp.maximum(lf + m_s[...], it)
        fp = jnp.exp(lf + m_s[...] - mt)
        ip = jnp.exp(it - mt)
        c_new = fp * c_s[...] + ip * jnp.tanh(zt)
        n_new = fp * n_s[...] + ip
        h_new = jax.nn.sigmoid(ot) * c_new / jnp.maximum(n_new, 1e-6)
        c_s[...], n_s[...], m_s[...] = c_new, n_new, mt
        h_s[...] = h_new
        hs_ref[0, :, t, 0, :] = h_new.astype(hs_ref.dtype)
        return 0

    jax.lax.fori_loop(0, cs, step, 0)

    @pl.when(si == ns - 1)
    def _done():
        cf_ref[0, :, 0] = c_s[...]
        nf_ref[0, :, 0] = n_s[...]
        hf_ref[0, :, 0] = h_s[...].astype(hf_ref.dtype)
        mf_ref[0, :, 0] = m_s[...]


def _vmem(b: int, hd: int):
    """(B, hd) f32 VMEM state scratch."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM((b, hd), jnp.float32)


def _clamp(block: int, dim: int) -> int:
    b = min(block, dim)
    while dim % b:
        b -= 1
    return b


@functools.partial(jax.jit, static_argnames=("num_heads", "chunk", "interpret"))
def slstm_cell(
    pre: jax.Array,
    r: jax.Array,
    state: tuple,
    *,
    num_heads: int,
    chunk: int = 256,
    interpret: bool | None = None,
):
    """Full sLSTM scan.

    pre: (M, B, S, 4, D) gate pre-activations (x-side, any float dtype);
    r: (M, 4, H, hd, hd) recurrent weights; state: (c, n, h, m) each
    (M, B, D) — c/n/m f32, h in storage dtype.  Returns
    (hs (M, B, S, D) in h.dtype, new state).
    """
    m, b, s, four, d = pre.shape
    assert four == 4
    hh = num_heads
    hd = d // hh
    c0, n0, h0, m0 = state
    cs = _clamp(chunk, s)
    ns = s // cs
    grid = (m, hh, ns)

    # head-major layouts: (M, B, S, 4, H, hd) pre; (M, B, H, hd) state
    pre_h = pre.reshape(m, b, s, 4, hh, hd)
    st = lambda x: x.reshape(m, b, hh, hd)

    out_shape = (
        jax.ShapeDtypeStruct((m, b, s, hh, hd), h0.dtype),   # hs
        jax.ShapeDtypeStruct((m, b, hh, hd), jnp.float32),   # c
        jax.ShapeDtypeStruct((m, b, hh, hd), jnp.float32),   # n
        jax.ShapeDtypeStruct((m, b, hh, hd), h0.dtype),      # h
        jax.ShapeDtypeStruct((m, b, hh, hd), jnp.float32),   # m
    )
    state_spec = pl.BlockSpec((1, b, 1, hd), lambda mi, hi, si: (mi, 0, hi, 0))
    hs, cf, nf, hf, mf = pl.pallas_call(
        functools.partial(_kernel, cs=cs, ns=ns),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, b, cs, 4, 1, hd), lambda mi, hi, si: (mi, 0, si, 0, hi, 0)),
            pl.BlockSpec((1, 4, 1, hd, hd), lambda mi, hi, si: (mi, 0, hi, 0, 0)),
            state_spec, state_spec, state_spec, state_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, b, cs, 1, hd), lambda mi, hi, si: (mi, 0, si, hi, 0)),
            state_spec, state_spec, state_spec, state_spec,
        ],
        out_shape=out_shape,
        scratch_shapes=[_vmem(b, hd) for _ in range(4)],
        interpret=interpret_mode(interpret),
    )(pre_h, r, st(c0), st(n0), st(h0), st(m0))

    unst = lambda x: x.reshape(m, b, d)
    return hs.reshape(m, b, s, d), (unst(cf), unst(nf), unst(hf), unst(mf))
