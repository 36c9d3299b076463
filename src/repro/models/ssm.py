"""xLSTM (sLSTM + mLSTM blocks) — xlstm-1.3b [arXiv:2405.04517].

TPU adaptation (DESIGN.md §2): the GPU reference implements mLSTM with
fused CUDA kernels over the full sequence; here the mLSTM is evaluated in
*chunkwise-parallel* form — an outer ``lax.scan`` over sequence chunks
carrying the (C, n, m) matrix-memory state, with the intra-chunk part
expressed as MXU-friendly masked matmuls (Cs x Cs score/decay matrices).
This keeps memory O(S/Cs · Cs²) instead of O(S²) and is the natural
mapping of linear-attention-style recurrences onto the MXU.

Exact exponential-gating stabilization (the paper's m-state) is carried
across chunks; tests assert the chunkwise path matches the per-step
recurrence to float tolerance, and that decode (single-step) continues
prefill exactly.

Layer layout: ``cfg.slstm_every = k`` makes layer i an sLSTM block when
``i % k == cfg.slstm_offset`` (xLSTM[7:1] ratio for the 1.3b config);
mLSTM runs between sLSTM layers are stacked and scanned.

NetFuse applicability: all projections are instance-batched einsums; the
recurrent state carries a leading instance axis — merged instances evolve
independent states (input-weight local by construction).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.common import (
    Factory, constrain, make_factory, param_axes, param_values,
    stack_layer_params, tree_select_slots,
)

# ---------------------------------------------------------------------------
# config helpers
# ---------------------------------------------------------------------------


def d_inner(cfg: ModelConfig) -> int:
    return int(cfg.mlstm_proj_factor * cfg.d_model)


def slstm_ff(cfg: ModelConfig) -> int:
    # xLSTM sLSTM blocks use a gated FFN with proj factor 4/3, rounded to 128.
    return max(128, int(round(cfg.d_model * 4 / 3 / 128)) * 128)


def is_slstm_layer(cfg: ModelConfig, i: int) -> bool:
    return cfg.slstm_every > 0 and i % cfg.slstm_every == cfg.slstm_offset


def layer_pattern(cfg: ModelConfig) -> list[str]:
    return ["slstm" if is_slstm_layer(cfg, i) else "mlstm" for i in range(cfg.num_layers)]


def mlstm_runs(cfg: ModelConfig) -> list[int]:
    """Lengths of contiguous mLSTM runs between sLSTM layers."""
    runs, cur = [], 0
    for kind in layer_pattern(cfg):
        if kind == "mlstm":
            cur += 1
        else:
            runs.append(cur)
            cur = 0
    runs.append(cur)
    return runs  # len == n_slstm + 1; entries may be 0


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def _mlstm_layer_params(cfg: ModelConfig, f: Factory):
    m, d = cfg.num_instances, cfg.d_model
    di, h = d_inner(cfg), cfg.num_heads
    hd = di // h
    return {
        "norm": f((m, d), ("instances", None), init="ones"),
        "w_up": f((m, d, 2 * di), ("instances", "embed", "mlp"), init="fan_in"),
        "conv_w": f((m, cfg.conv_kernel, di), ("instances", None, "mlp"), init="fan_in"),
        "conv_b": f((m, di), ("instances", "mlp"), init="zeros"),
        # block-diagonal per-head q/k/v (the paper's BlockDiag projections)
        "wq": f((m, h, hd, hd), ("instances", "heads", None, None), init="fan_in"),
        "wk": f((m, h, hd, hd), ("instances", "heads", None, None), init="fan_in"),
        "wv": f((m, h, hd, hd), ("instances", "heads", None, None), init="fan_in"),
        "w_gates": f((m, di, 2 * h), ("instances", "mlp", None), init="fan_in"),
        "b_gates": f((m, 2 * h), ("instances", None), init="zeros"),
        "out_norm": f((m, di), ("instances", "mlp"), init="ones"),
        "w_down": f((m, di, d), ("instances", "mlp", "embed"), init="fan_in"),
    }


def _slstm_layer_params(cfg: ModelConfig, f: Factory):
    m, d, h = cfg.num_instances, cfg.d_model, cfg.num_heads
    hd = d // h
    ff = slstm_ff(cfg)
    return {
        "norm": f((m, d), ("instances", None), init="ones"),
        "w_in": f((m, d, 4 * d), ("instances", "embed", "mlp"), init="fan_in"),
        "b_in": f((m, 4 * d), ("instances", "mlp"), init="zeros"),
        # per-head block-diagonal recurrent weights
        "r": f((m, 4, h, hd, hd), ("instances", None, "heads", None, None), init="fan_in"),
        "out_norm": f((m, d), ("instances", None), init="ones"),
        "ffn_norm": f((m, d), ("instances", None), init="ones"),
        "w_ff_gate": f((m, d, ff), ("instances", "embed", "mlp"), init="fan_in"),
        "w_ff_up": f((m, d, ff), ("instances", "embed", "mlp"), init="fan_in"),
        "w_ff_down": f((m, ff, d), ("instances", "mlp", "embed"), init="fan_in"),
    }


def build_params(cfg: ModelConfig, f: Factory):
    m, d, v = cfg.num_instances, cfg.d_model, cfg.vocab_size
    runs = mlstm_runs(cfg)
    p = {
        "embed": f((m, v, d), ("instances", "vocab", "embed")),
        "mlstm_runs": [
            stack_layer_params([_mlstm_layer_params(cfg, f) for _ in range(n)])
            if n else None
            for n in runs
        ],
        "slstm": [
            _slstm_layer_params(cfg, f) for _ in range(len(runs) - 1)
        ],
        "final_norm": f((m, d), ("instances", None), init="ones"),
        "lm_head": f((m, d, v), ("instances", "embed", "vocab"), init="fan_in"),
    }
    return p


def init(cfg, key):
    return param_values(build_params(cfg, make_factory(cfg, key)))


def abstract_params(cfg):
    return param_values(build_params(cfg, make_factory(cfg, abstract=True)))


def axes(cfg):
    return param_axes(build_params(cfg, make_factory(cfg, abstract=True)))


# ---------------------------------------------------------------------------
# mLSTM cell — chunkwise-parallel sequence form + single-step form
# ---------------------------------------------------------------------------


def _mlstm_chunk(carry, blk, *, hd: int):
    """One chunk. carry: (C (..,hd,hd), n (..,hd), mstab (..,)) with
    leading dims (M,B,H).  blk: q,k,v (M,B,H,Cs,hd); lf, li (M,B,H,Cs) —
    log forget (<=0) and input-gate preactivations."""
    C0, n0, m0 = carry
    q, k, v, lf, li = blk
    cs = q.shape[-2]

    b = jnp.cumsum(lf, axis=-1)                                # (..,Cs) log decay to t
    g = lax.cummax(li - b, axis=li.ndim - 1)                   # running max of (li_s - b_s)
    mt = b + jnp.maximum(m0[..., None], g)                     # stabilizer per step
    a_inter = jnp.exp(b + m0[..., None] - mt)                  # (..,Cs)

    # D[t,s] = exp(li_s + b_t - b_s - m_t) for s<=t
    logD = (
        li[..., None, :] - b[..., None, :] + b[..., :, None] - mt[..., None]
    )                                                          # (..,Cs_t,Cs_s)
    tri = jnp.tril(jnp.ones((cs, cs), bool))
    D = jnp.where(tri, jnp.exp(logD), 0.0)

    # q/k/v stay in their storage dtype (bf16 in production — §Perf xlstm
    # iteration: the chunk-scan buffers dominate HBM traffic); every
    # contraction accumulates in f32, gates/state are always f32.
    f32 = jnp.float32
    s_qk = jnp.einsum(
        "...td,...sd->...ts", q, k, preferred_element_type=f32
    ) / math.sqrt(hd)
    w = s_qk * D                                               # (..,Cs,Cs) f32
    num = jnp.einsum("...ts,...sd->...td", w.astype(v.dtype), v,
                     preferred_element_type=f32)
    num = num + a_inter[..., None] * jnp.einsum(
        "...td,...de->...te", q.astype(f32), C0
    ) / math.sqrt(hd)
    den = w.sum(-1) + a_inter * jnp.einsum(
        "...td,...d->...t", q.astype(f32), n0
    ) / math.sqrt(hd)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-mt))[..., None]

    # end-of-chunk state
    m_end = mt[..., -1]
    w_end = jnp.exp(li + b[..., -1:] - b - m_end[..., None])   # (..,Cs)
    decay0 = jnp.exp(b[..., -1] + m0 - m_end)                  # (..,)
    C_new = decay0[..., None, None] * C0 + jnp.einsum(
        "...s,...sd,...se->...de", w_end.astype(v.dtype), k, v,
        preferred_element_type=f32,
    )
    n_new = decay0[..., None] * n0 + jnp.einsum(
        "...s,...sd->...d", w_end.astype(k.dtype), k, preferred_element_type=f32
    )
    return (C_new, n_new, m_end), h.astype(v.dtype)


def mlstm_sequence(q, k, v, lf, li, *, chunk: int = 64, state=None):
    """Chunkwise mLSTM. q,k,v: (M,B,H,S,hd); lf,li: (M,B,H,S).
    Returns (h (M,B,H,S,hd), final state)."""
    m_, b_, h_, s, hd = q.shape
    cs = min(chunk, s)
    while s % cs:
        cs -= 1
    nc = s // cs
    if state is None:
        state = (
            jnp.zeros((m_, b_, h_, hd, hd), jnp.float32),
            jnp.zeros((m_, b_, h_, hd), jnp.float32),
            jnp.full((m_, b_, h_), -1e30, jnp.float32),
        )

    def to_chunks(x):
        if x.ndim == 5:
            xs = x.reshape(m_, b_, h_, nc, cs, x.shape[-1])
        else:
            xs = x.reshape(m_, b_, h_, nc, cs)
        return jnp.moveaxis(xs, 3, 0)

    xs = (to_chunks(q), to_chunks(k), to_chunks(v), to_chunks(lf), to_chunks(li))

    def step(carry, blk):
        return _mlstm_chunk(carry, blk, hd=hd)

    state, hs = lax.scan(step, state, xs)                      # hs (nc,M,B,H,Cs,hd)
    h = jnp.moveaxis(hs, 0, 3).reshape(m_, b_, h_, s, hd)
    return h, state


def mlstm_step(state, q, k, v, lf, li):
    """Single decode step. q,k,v: (M,B,H,hd); lf,li: (M,B,H)."""
    C0, n0, m0 = state
    hd = q.shape[-1]
    mt = jnp.maximum(m0 + lf, li)
    fp = jnp.exp(lf + m0 - mt)
    ip = jnp.exp(li - mt)
    kf = k.astype(jnp.float32)
    C = fp[..., None, None] * C0 + ip[..., None, None] * (
        kf[..., :, None] * v.astype(jnp.float32)[..., None, :]
    )
    n = fp[..., None] * n0 + ip[..., None] * kf
    qf = q.astype(jnp.float32) / math.sqrt(hd)
    num = jnp.einsum("...d,...de->...e", qf, C)
    den = jnp.einsum("...d,...d->...", qf, n)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-mt))[..., None]
    return (C, n, mt), h


# ---------------------------------------------------------------------------
# mLSTM block
# ---------------------------------------------------------------------------


def _causal_conv(x, w, b, conv_state=None, nvalid=None):
    """Depthwise causal conv via shifted adds. x: (M,B,S,Di); w: (M,K,Di).
    conv_state: (M,B,K-1,Di) trailing inputs from the previous call.
    ``nvalid`` (M,B) int32 — count of VALID leading positions (serving
    tail folding): the carried window is gathered at the last valid
    inputs instead of the chunk's junk suffix."""
    k = w.shape[1]
    if conv_state is None and nvalid is not None:
        conv_state = jnp.zeros(x.shape[:2] + (k - 1, x.shape[3]), x.dtype)
    if conv_state is None:
        pads = [jnp.pad(x, ((0, 0), (0, 0), (j, 0), (0, 0)))[:, :, : x.shape[2]] for j in range(k)]
        new_state = x[:, :, -(k - 1):]
    else:
        ext = jnp.concatenate([conv_state.astype(x.dtype), x], axis=2)
        pads = [ext[:, :, k - 1 - j : k - 1 - j + x.shape[2]] for j in range(k)]
        if nvalid is None:
            new_state = ext[:, :, -(k - 1):]
        else:
            idx = nvalid[..., None] + jnp.arange(k - 1, dtype=jnp.int32)
            new_state = jnp.take_along_axis(
                ext, idx[..., None].astype(jnp.int32), axis=2
            )
    y = sum(w[:, j, :][:, None, None, :].astype(x.dtype) * pads[j] for j in range(k))
    return y + b[:, None, None, :].astype(x.dtype), new_state


def _head_proj(x, w):
    """Block-diagonal per-head projection. x: (M,B,S,H,hd); w: (M,H,hd,hd)."""
    return jnp.einsum("mbshd,mhde->mbshe", x, w.astype(x.dtype))


def mlstm_block(cfg: ModelConfig, lp, x, *, state=None, chunk: int = 64, valid=None):
    """x: (M,B,S,D). state (decode): dict(C,n,m,conv). Returns (y, state).
    ``valid`` (M,B,S) bool: junk suffix of a padded final chunk (serving
    tail folding) is made gate-neutral — log-forget 0, log-input -inf —
    so the (C, n, m) carry skips the padded steps exactly."""
    m, b, s, d = x.shape
    di, h = d_inner(cfg), cfg.num_heads
    hd = di // h
    res = x
    xn = L.rms_norm(x, lp["norm"], cfg.norm_eps)
    up = L.linear(xn, lp["w_up"])                              # (M,B,S,2Di)
    xi, z = up[..., :di], up[..., di:]
    conv_state = state["conv"] if state is not None else None
    nvalid = valid.sum(-1).astype(jnp.int32) if valid is not None else None
    xc, new_conv = _causal_conv(xi, lp["conv_w"], lp["conv_b"], conv_state,
                                nvalid=nvalid)
    xc = jax.nn.silu(xc)

    xch = xc.reshape(m, b, s, h, hd)
    xih = xi.reshape(m, b, s, h, hd)
    q = _head_proj(xch, lp["wq"])
    k = _head_proj(xch, lp["wk"])
    v = _head_proj(xih, lp["wv"])
    gates = L.linear(xc, lp["w_gates"], lp["b_gates"]).astype(jnp.float32)  # (M,B,S,2H)
    li = gates[..., :h]
    lf = jax.nn.log_sigmoid(gates[..., h:])
    if valid is not None:
        li = jnp.where(valid[..., None], li, -1e30)
        lf = jnp.where(valid[..., None], lf, 0.0)

    # to (M,B,H,S,...) layout
    tr = lambda t: jnp.moveaxis(t, 3, 2)                       # (M,B,H,S,hd)
    if state is None or s > 1:
        if cfg.use_pallas_kernels and state is None:
            # matrix memory resident in VMEM across chunks
            # (kernels/mlstm_chunk.py — companion of the sLSTM cell kernel)
            from repro.kernels import ops as K
            hseq, new_cell = K.mlstm_chunkwise(
                tr(q), tr(k), tr(v),
                jnp.moveaxis(lf, 3, 2), jnp.moveaxis(li, 3, 2),
                chunk=chunk,
            )
        else:
            hseq, new_cell = mlstm_sequence(
                tr(q), tr(k), tr(v),
                jnp.moveaxis(lf, 3, 2), jnp.moveaxis(li, 3, 2),
                chunk=chunk,
                state=None if state is None else (state["C"], state["n"], state["m"]),
            )
        hs = jnp.moveaxis(hseq, 2, 3)                          # (M,B,S,H,hd)
    else:
        cell = (state["C"], state["n"], state["m"])
        new_cell, hstep = mlstm_step(
            cell, q[:, :, 0], k[:, :, 0], v[:, :, 0], lf[:, :, 0], li[:, :, 0]
        )
        hs = hstep[:, :, None]                                 # (M,B,1,H,hd)

    hs = hs.reshape(m, b, s, di).astype(x.dtype)
    # per-head group norm (xLSTM's multi-head layer norm), then gate
    hs = hs.reshape(m, b, s, h, hd)
    mu = hs.mean(-1, keepdims=True)
    var = hs.var(-1, keepdims=True)
    hs = ((hs - mu) * lax.rsqrt(var + cfg.norm_eps)).reshape(m, b, s, di)
    hs = hs * lp["out_norm"][:, None, None, :].astype(hs.dtype)
    out = L.linear(hs * jax.nn.silu(z), lp["w_down"])
    new_state = {"C": new_cell[0], "n": new_cell[1], "m": new_cell[2], "conv": new_conv}
    return res + out, new_state


def mlstm_state_shape(cfg: ModelConfig, m: int, b: int):
    di, h = d_inner(cfg), cfg.num_heads
    hd = di // h
    k = cfg.conv_kernel
    return {
        "C": ((m, b, h, hd, hd), jnp.float32),
        "n": ((m, b, h, hd), jnp.float32),
        "m": ((m, b, h), jnp.float32),
        "conv": ((m, b, k - 1, di), jnp.dtype(cfg.dtype)),
    }


# ---------------------------------------------------------------------------
# sLSTM block
# ---------------------------------------------------------------------------


def slstm_block(cfg: ModelConfig, lp, x, *, state=None, valid=None):
    """x: (M,B,S,D). Sequential scan over time (sLSTM is strictly
    recurrent through h). state: dict(c,n,h,m) each (M,B,D).
    ``valid`` (M,B,S) bool: junk steps (serving tail folding) get forced
    gate pre-activations (forget +inf, input -inf) so c/n/m are carried
    unchanged — works for the scan AND the Pallas cell, which both derive
    gates from ``pre``; the h carry is re-gathered at the last valid step
    afterwards (h is the one state the gates can't protect)."""
    m, b, s, d = x.shape
    h_heads = cfg.num_heads
    hd = d // h_heads
    res = x
    xn = L.rms_norm(x, lp["norm"], cfg.norm_eps)
    # pre-activations stay in storage dtype (bf16 in production) and are
    # upcast per step — the (M,B,S,4D) f32 buffer otherwise dominates the
    # scan's HBM traffic (§Perf xlstm iteration).  Gate math is f32.
    pre = L.linear(xn, lp["w_in"], lp["b_in"]).reshape(m, b, s, 4, d)
    if valid is not None:
        # (z, i, f, o) slots: input gate -> -BIG (exp underflows to 0),
        # forget gate -> +BIG (log_sigmoid saturates to exactly 0)
        neutral = jnp.asarray([0.0, -1e30, 1e30, 0.0], pre.dtype)
        pre = jnp.where(valid[..., None, None],
                        pre, neutral.reshape(1, 1, 1, 4, 1))

    if state is None:
        st = tuple(jnp.zeros((m, b, d), jnp.float32) for _ in range(3)) + (
            jnp.full((m, b, d), -1e30, jnp.float32),
        )
    else:
        st = (state["c"], state["n"], state["h"], state["m"])
    # h carry in storage dtype (bf16 in production): h is only a matmul
    # input; c/n/m (the numerically sensitive gate state) stay f32.  The
    # f32 h chain is what the scan saves per step for backward — in bf16
    # that residual buffer halves (§Perf xlstm iteration 3).
    st = (st[0], st[1], st[2].astype(x.dtype), st[3])

    r = lp["r"].astype(jnp.float32)                            # (M,4,H,hd,hd)

    def step(carry, pre_t):
        c, n, hprev, mstab = carry                             # (M,B,D)
        hh = hprev.reshape(m, b, h_heads, hd)
        rec = jnp.einsum("mbhd,mghde->mbghe", hh, r).reshape(m, b, 4, d)
        zt, it, ft, ot = [pre_t[:, :, j].astype(jnp.float32) + rec[:, :, j]
                          for j in range(4)]
        lf = jax.nn.log_sigmoid(ft)
        mt = jnp.maximum(lf + mstab, it)
        ip = jnp.exp(it - mt)
        fp = jnp.exp(lf + mstab - mt)
        c_new = fp * c + ip * jnp.tanh(zt)
        n_new = fp * n + ip
        h_new = (jax.nn.sigmoid(ot) * c_new / jnp.maximum(n_new, 1e-6)).astype(x.dtype)
        return (c_new, n_new, h_new, mt), h_new

    if cfg.use_pallas_kernels:
        # whole-sequence Pallas cell: (c,n,h,m) resident in VMEM scratch
        # across all S steps (kernels/slstm_cell.py — §Perf xlstm lever).
        from repro.kernels import ops as K
        hs, (c, n, hlast, mstab) = K.slstm_cell(
            pre, lp["r"], st, num_heads=h_heads
        )
    else:
        # checkpoint each step: backward then saves only the (c,n,h,m)
        # carry chain and recomputes the ~10 per-step gate intermediates —
        # those f32 (M,B,D)xS residual stacks dominate the sLSTM's HBM
        # traffic otherwise (§Perf xlstm iteration 4; iteration 3 showed
        # shrinking ONE of them doesn't move the term).
        (c, n, hlast, mstab), hs = lax.scan(
            jax.checkpoint(step), st, jnp.moveaxis(pre, 2, 0)
        )
        hs = jnp.moveaxis(hs, 0, 2)                            # (M,B,S,D)

    if valid is not None:
        # gate-neutral junk leaves c/n/m untouched but every step still
        # emits an h — re-select the h carry at the last VALID step (or
        # keep the incoming h when the whole chunk is junk)
        nv = valid.sum(-1).astype(jnp.int32)                   # (M,B)
        idx = jnp.clip(nv - 1, 0, s - 1)[..., None, None]
        h_sel = jnp.take_along_axis(
            hs, jnp.broadcast_to(idx, (m, b, 1, d)), axis=2
        )[:, :, 0]
        hlast = jnp.where((nv > 0)[..., None], h_sel, st[2])

    # per-head group norm + residual, then gated FFN
    hh = hs.reshape(m, b, s, h_heads, hd)
    mu = hh.mean(-1, keepdims=True)
    var = hh.var(-1, keepdims=True)
    hs = ((hh - mu) * lax.rsqrt(var + cfg.norm_eps)).reshape(m, b, s, d)
    hs = hs * lp["out_norm"][:, None, None, :].astype(hs.dtype)
    x = res + hs
    nrm = L.rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    x = x + L.swiglu_mlp(nrm, lp["w_ff_gate"], lp["w_ff_up"], lp["w_ff_down"])
    new_state = {"c": c, "n": n, "h": hlast, "m": mstab}
    return x, new_state


def slstm_state_shape(cfg: ModelConfig, m: int, b: int):
    d = cfg.d_model
    return {
        "c": ((m, b, d), jnp.float32),
        "n": ((m, b, d), jnp.float32),
        "h": ((m, b, d), jnp.dtype(cfg.dtype)),   # matmul input only
        "m": ((m, b, d), jnp.float32),
    }


# ---------------------------------------------------------------------------
# whole model
# ---------------------------------------------------------------------------


def _trunk(cfg, params, x, *, states=None, chunk=None, remat=False, valid=None):
    """Run all blocks. states: None or dict(mlstm_runs=[...], slstm=[...]).
    ``valid`` (M,B,S): serving tail-folding junk mask, threaded to every
    recurrent cell.  Returns (x, new_states)."""
    runs = mlstm_runs(cfg)
    if chunk is None:
        chunk = cfg.mlstm_chunk
    new_states = {"mlstm_runs": [], "slstm": []}

    for ri, n in enumerate(runs):
        if n:
            run_params = params["mlstm_runs"][ri]
            run_state = states["mlstm_runs"][ri] if states is not None else None

            def body(xc, xs, _n=n):
                lp, st = xs
                out, new_st = mlstm_block(cfg, lp, xc, state=st, chunk=chunk,
                                          valid=valid)
                return out, new_st

            if remat:
                body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
            if run_state is None:
                m_, b_ = x.shape[0], x.shape[1]
                shapes = mlstm_state_shape(cfg, m_, b_)
                run_state = {
                    kk: jnp.zeros((n,) + sh, dt) if kk != "m" else
                        jnp.full((n,) + sh, -1e30, dt)
                    for kk, (sh, dt) in shapes.items()
                }
            x, new_st = lax.scan(body, x, (run_params, run_state))
            new_states["mlstm_runs"].append(new_st)
        else:
            new_states["mlstm_runs"].append(None)
        if ri < len(runs) - 1:
            s_state = states["slstm"][ri] if states is not None else None
            x, new_s = slstm_block(cfg, params["slstm"][ri], x, state=s_state,
                                   valid=valid)
            new_states["slstm"].append(new_s)
    return x, new_states


def forward(cfg, params, tokens, *, remat: bool = False):
    x = L.embed(tokens, params["embed"], jnp.dtype(cfg.dtype))
    x, _ = _trunk(cfg, params, x, remat=remat)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return L.unembed(x, params["lm_head"])


def prefill(cfg, params, tokens, *, state=None):
    """Returns (last logits, recurrent states) — the SSM 'cache'.

    ``state`` continues a previous prefill exactly (chunked prompt
    processing: the serving admission path feeds fixed-size chunks so one
    compile covers every prompt length — see serving/prefill.py)."""
    x = L.embed(tokens, params["embed"], jnp.dtype(cfg.dtype))
    m, b, s = tokens.shape
    states = make_state(cfg, m, b) if state is None else state
    x, states = _trunk(cfg, params, x, states=states)
    x = L.rms_norm(x[:, :, -1:], params["final_norm"], cfg.norm_eps)
    return L.unembed(x, params["lm_head"])[:, :, 0], states


def decode_step(cfg, params, states, tokens, pos=None, *, live=None):
    """One token. tokens (M,B,1). pos unused (state is positionless).
    A lane whose ``live`` is False keeps its state (a select over the
    per-slot recurrent state, which every step rewrites whole anyway)."""
    x = L.embed(tokens, params["embed"], jnp.dtype(cfg.dtype))
    x, new = _trunk(cfg, params, x, states=states)
    if live is not None:
        new = tree_select_slots(live, new, states, state_axes(cfg))
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return L.unembed(x, params["lm_head"])[:, :, 0], new


def make_state(cfg, m, b):
    runs = mlstm_runs(cfg)
    st = {"mlstm_runs": [], "slstm": []}
    for ri, n in enumerate(runs):
        if n:
            shapes = mlstm_state_shape(cfg, m, b)
            st["mlstm_runs"].append({
                kk: (jnp.zeros((n,) + sh, dt) if kk != "m"
                     else jnp.full((n,) + sh, -1e30, dt))
                for kk, (sh, dt) in shapes.items()
            })
        else:
            st["mlstm_runs"].append(None)
        if ri < len(runs) - 1:
            st["slstm"].append({
                kk: (jnp.zeros(sh, dt) if kk != "m" else jnp.full(sh, -1e30, dt))
                for kk, (sh, dt) in slstm_state_shape(cfg, m, b).items()
            })
    return st


def init_chunk_carry(cfg, m, b, cache_len):
    return {"cache": make_state(cfg, m, b)}


def chunk_carry_axes(cfg):
    return {"cache": state_axes(cfg)}


def prefill_chunk(cfg, params, batch, carry, offset):
    """One chunk of a state-carrying prefill.  The recurrent state is
    positionless, so ``offset`` is unused — chaining is pure state
    threading (this was already exact pre-refactor; the chunk-carry
    protocol just gives it the uniform serving signature).
    batch["valid"] (M,B,C) marks the junk suffix of a padded final chunk
    (tail folding): junk steps are gate-neutral in every cell, so the
    carried state equals the exact-length pass."""
    x = L.embed(batch["tokens"], params["embed"], jnp.dtype(cfg.dtype))
    _, states = _trunk(cfg, params, x, states=carry["cache"],
                       valid=batch.get("valid"))
    return {"cache": states}


def take_state(cfg, state, m, b):
    """Slice slot (m, b) out of an (M, B) recurrent-state grid, keeping
    singleton dims — the recurrent-family counterpart of KV-cache slot
    surgery (serving admission/eviction)."""
    from repro.models.common import tree_take_slot
    return tree_take_slot(state, state_axes(cfg), m, b)


def put_state(cfg, grid, one, m, b):
    """Write a single-slot state tree into grid slot (m, b)."""
    from repro.models.common import tree_put_slot
    return tree_put_slot(grid, state_axes(cfg), one, m, b)


def state_axes(cfg):
    """Logical axes for the recurrent state (for sharding rules)."""
    runs = mlstm_runs(cfg)
    ax = {"mlstm_runs": [], "slstm": []}
    for ri, n in enumerate(runs):
        ax["mlstm_runs"].append(
            {
                "C": ("layers", "instances", "batch", "heads", None, None),
                "n": ("layers", "instances", "batch", "heads", None),
                "m": ("layers", "instances", "batch", "heads"),
                "conv": ("layers", "instances", "batch", None, "mlp"),
            } if n else None
        )
        if ri < len(runs) - 1:
            ax["slstm"].append({k: ("instances", "batch", None) for k in ("c", "n", "h", "m")})
    return ax
