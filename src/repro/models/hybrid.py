"""Hymba-style hybrid-head decoder — hymba-1.5b [arXiv:2411.13676].

Every block runs an attention head-group and a Mamba (selective-SSM)
head-group *in parallel* on the same input; outputs are per-branch
normalized and averaged.  Additional Hymba features implemented:

* **meta tokens** — ``R`` learnable tokens prepended to the sequence,
  visible to every query as attention sinks even under the sliding
  window (flash_attention's ``sink``),
* **SWA/global mix** — layers {0, L/2, L-1} use full attention, the
  rest sliding-window (per-layer window is a *traced* scalar so the
  whole stack still runs under one ``lax.scan`` for train/prefill;
  decode groups layers by cache size: ring buffers for SWA layers,
  full-context caches for the three global layers).

The Mamba branch uses a chunked associative scan over time (TPU
adaptation: the CUDA selective-scan kernel becomes chunk-parallel
prefix products — see DESIGN.md §2).  Sub-quadratic end to end, so this
architecture runs the long_500k shape natively.
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
    stack_layer_params,
)
from repro.models.layers import KVCache

NUM_META_TOKENS = 128
GLOBAL_WINDOW = 1 << 30  # "no window" sentinel for global-attention layers
DEFAULT_SWA = 1024


def d_inner(cfg: ModelConfig) -> int:
    return 2 * cfg.d_model  # mamba expansion factor 2


def dt_rank(cfg: ModelConfig) -> int:
    return max(1, math.ceil(cfg.d_model / 16))


SSM_HEAD_DIM = 64


def ssm_heads(cfg: ModelConfig) -> int:
    di = d_inner(cfg)
    hd = SSM_HEAD_DIM
    while di % hd:
        hd //= 2
    return di // hd


def global_layers(cfg: ModelConfig) -> set[int]:
    n = cfg.num_layers
    return {0, n // 2, n - 1} if n >= 3 else set(range(n))


def swa_window(cfg: ModelConfig) -> int:
    return cfg.sliding_window if cfg.sliding_window else DEFAULT_SWA


def min_serving_context(cfg: ModelConfig, max_new: int = 0) -> int:
    """Smallest serving max_context for this config: the SWA ring layout
    needs meta tokens + a full window (plus decode headroom)."""
    return NUM_META_TOKENS + swa_window(cfg) + max_new


def layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """(L,) int32 per-layer window (GLOBAL_WINDOW for global layers)."""
    g = global_layers(cfg)
    w = swa_window(cfg)
    return jnp.array(
        [GLOBAL_WINDOW if i in g else w for i in range(cfg.num_layers)], jnp.int32
    )


def decode_groups(cfg: ModelConfig) -> list[tuple[int, int, bool]]:
    """Contiguous (start, end, is_global) layer groups for decode."""
    g = global_layers(cfg)
    groups, start = [], 0
    for i in range(1, cfg.num_layers + 1):
        if i == cfg.num_layers or (i in g) != (start in g):
            groups.append((start, i, start in g))
            start = i
    return groups


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def _layer_params(cfg: ModelConfig, f: Factory):
    m, d, h, kvh, hd = (
        cfg.num_instances, cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
    )
    di, n, r = d_inner(cfg), cfg.ssm_state, dt_rank(cfg)
    return {
        "norm": f((m, d), ("instances", None), init="ones"),
        # attention branch
        "wq": f((m, d, h * hd), ("instances", "embed", "heads_flat"), init="fan_in"),
        "wk": f((m, d, kvh * hd), ("instances", "embed", "kv_flat"), init="fan_in"),
        "wv": f((m, d, kvh * hd), ("instances", "embed", "kv_flat"), init="fan_in"),
        "wo": f((m, h * hd, d), ("instances", "heads_flat", "embed"), init="fan_in"),
        "attn_out_norm": f((m, d), ("instances", None), init="ones"),
        # mamba branch
        "w_ssm_in": f((m, d, 2 * di), ("instances", "embed", "mlp"), init="fan_in"),
        "conv_w": f((m, cfg.conv_kernel, di), ("instances", None, "mlp"), init="fan_in"),
        "conv_b": f((m, di), ("instances", "mlp"), init="zeros"),
        "w_bc": f((m, di, 2 * n), ("instances", "mlp", None), init="fan_in"),
        # SSD (Mamba-2) head-shared decay: dt/A per SSM head, not per
        # channel — the TPU adaptation that turns the selective scan into
        # MXU matmuls (DESIGN.md §Perf / [Dao & Gu 2024]).
        "w_dt": f((m, di, ssm_heads(cfg)), ("instances", "mlp", None), init="fan_in"),
        "b_dt": f((m, ssm_heads(cfg)), ("instances", None), init="zeros"),
        "a_log": f((m, ssm_heads(cfg)), ("instances", None), init="zeros"),
        "d_skip": f((m, di), ("instances", "mlp"), init="ones"),
        "w_ssm_out": f((m, di, d), ("instances", "mlp", "embed"), init="fan_in"),
        "ssm_out_norm": f((m, d), ("instances", None), init="ones"),
        # ffn
        "mlp_norm": f((m, d), ("instances", None), init="ones"),
        "w_gate": f((m, d, cfg.d_ff), ("instances", "embed", "mlp"), init="fan_in"),
        "w_up": f((m, d, cfg.d_ff), ("instances", "embed", "mlp"), init="fan_in"),
        "w_down": f((m, cfg.d_ff, d), ("instances", "mlp", "embed"), init="fan_in"),
    }


def build_params(cfg: ModelConfig, f: Factory):
    m, d, v = cfg.num_instances, cfg.d_model, cfg.vocab_size
    return {
        "embed": f((m, v, d), ("instances", "vocab", "embed")),
        "meta_tokens": f((m, NUM_META_TOKENS, d), ("instances", None, "embed")),
        "layers": stack_layer_params([_layer_params(cfg, f) for _ in range(cfg.num_layers)]),
        "final_norm": f((m, d), ("instances", None), init="ones"),
        "lm_head": f((m, d, v), ("instances", "embed", "vocab"), init="fan_in"),
    }


def init(cfg, key):
    return param_values(build_params(cfg, make_factory(cfg, key)))


def abstract_params(cfg):
    return param_values(build_params(cfg, make_factory(cfg, abstract=True)))


def axes(cfg):
    return param_axes(build_params(cfg, make_factory(cfg, abstract=True)))


# ---------------------------------------------------------------------------
# mamba branch
# ---------------------------------------------------------------------------


def _ssd_chunk_scan(u, da, b_in, c_out, h0, *, chunk: int = 64):
    """SSD chunkwise scan (exact, stable — all exponents <= 0).

    u: (M,B,S,H,hd) dt-scaled inputs; da: (M,B,S,H) per-head log decay
    (<= 0); b_in, c_out: (M,B,S,N); h0: (M,B,H,hd,N).
    Returns (y (M,B,S,H,hd), h_final).

    Within a chunk the pairwise decay exp(cum_t - cum_s), s <= t, is a
    (Cs, Cs) matrix PER HEAD (not per channel), so the intra-chunk part
    is two MXU einsums; chunks are linked by a cheap lax.scan carrying
    the (H, hd, N) state.
    """
    m, b, s, h, hd = u.shape
    n = b_in.shape[-1]
    cs = min(chunk, s)
    while s % cs:
        cs -= 1
    nc = s // cs

    uc = u.reshape(m, b, nc, cs, h, hd).astype(jnp.float32)
    dac = da.reshape(m, b, nc, cs, h)
    bc = b_in.reshape(m, b, nc, cs, n).astype(jnp.float32)
    cc = c_out.reshape(m, b, nc, cs, n).astype(jnp.float32)

    cum = jnp.cumsum(dac, axis=3)                              # (M,B,nc,Cs,H)
    # pairwise decay L[t,s] = exp(cum_t - cum_s + da_s?) — recurrence
    # h_t = e^{da_t} h_{t-1} + u_t gives weight exp(cum_t - cum_s) for u_s.
    diff = cum[:, :, :, :, None, :] - cum[:, :, :, None, :, :]  # (M,B,nc,t,s,H)
    tri = jnp.tril(jnp.ones((cs, cs), bool))[None, None, None, :, :, None]
    L = jnp.where(tri, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)   # (M,B,nc,t,s,H)
    G = jnp.einsum("mbctn,mbcsn->mbcts", cc, bc)               # (M,B,nc,t,s)
    y_intra = jnp.einsum("mbctsh,mbcshd->mbcthd", L * G[..., None], uc)

    # chunk summaries -> inter-chunk state scan
    decay_end = jnp.exp(cum[:, :, :, -1, :])                   # (M,B,nc,H)
    w_end = jnp.exp(cum[:, :, :, -1:, :] - cum)                # (M,B,nc,Cs,H)
    chunk_in = jnp.einsum("mbcsh,mbcshd,mbcsn->mbchdn", w_end, uc, bc)

    def link(hst, xs):
        dec, cin = xs                                          # (M,B,H), (M,B,H,hd,N)
        h_new = dec[..., None, None] * hst + cin
        return h_new, hst                                      # emit state BEFORE chunk

    h_fin, h_starts = lax.scan(
        link, h0.astype(jnp.float32),
        (jnp.moveaxis(decay_end, 2, 0), jnp.moveaxis(chunk_in, 2, 0)),
    )
    h_starts = jnp.moveaxis(h_starts, 0, 2)                    # (M,B,nc,H,hd,N)
    y_inter = jnp.exp(cum)[..., None] * jnp.einsum(
        "mbchdn,mbctn->mbcthd", h_starts, cc
    )
    y = (y_intra + y_inter).reshape(m, b, s, h, hd)
    return y, h_fin


def mamba_branch(cfg: ModelConfig, lp, xn, *, state=None, valid=None):
    """Selective SSM, SSD (head-shared-decay) form. xn: (M,B,S,D).
    state (decode): {"h": (M,B,Di,N) f32, "conv": (M,B,K-1,Di)}.
    ``valid`` (M,B,S) bool marks the junk suffix of a padded final chunk
    (serving tail folding): junk steps are made gate-neutral (zero decay,
    zero input → h unchanged) and the conv window is gathered at the last
    VALID inputs, so the carried state matches the exact-length pass.
    Returns (out (M,B,S,D), new_state)."""
    m, b, s, d = xn.shape
    di, n = d_inner(cfg), cfg.ssm_state
    nh = ssm_heads(cfg)
    hd = di // nh

    up = L.linear(xn, lp["w_ssm_in"])                          # (M,B,S,2Di)
    xi, z = up[..., :di], up[..., di:]
    conv_state = state["conv"] if state is not None else None
    nvalid = valid.sum(-1).astype(jnp.int32) if valid is not None else None
    xc, new_conv = _conv(xi, lp["conv_w"], lp["conv_b"], conv_state,
                         nvalid=nvalid)
    xc = jax.nn.silu(xc)

    bcp = L.linear(xc, lp["w_bc"]).astype(jnp.float32)         # (M,B,S,2N)
    b_in, c_out = bcp[..., :n], bcp[..., n:]
    dt = jax.nn.softplus(
        L.linear(xc, lp["w_dt"]).astype(jnp.float32)
        + lp["b_dt"][:, None, None, :].astype(jnp.float32)
    )                                                          # (M,B,S,H)
    a = -jnp.exp(lp["a_log"].astype(jnp.float32))              # (M,H)
    da = dt * a[:, None, None, :]                              # (M,B,S,H) <= 0

    xh = xc.reshape(m, b, s, nh, hd).astype(jnp.float32)
    u = dt[..., None] * xh                                     # (M,B,S,H,hd)
    if valid is not None:
        # gate-neutral junk: exp(0)·h + 0 = h — the recurrence skips the
        # padded steps exactly (their y outputs are garbage, discarded)
        da = jnp.where(valid[..., None], da, 0.0)
        u = jnp.where(valid[..., None, None], u, 0.0)

    if state is None or s > 1:
        h0 = (
            state["h"].reshape(m, b, nh, hd, n) if state is not None
            else jnp.zeros((m, b, nh, hd, n), jnp.float32)
        )
        y, h_fin = _ssd_chunk_scan(u, da, b_in, c_out, h0)
        y = y.reshape(m, b, s, di)
    else:
        h0 = state["h"].reshape(m, b, nh, hd, n)
        h_new = (
            jnp.exp(da[:, :, 0])[..., None, None] * h0
            + u[:, :, 0][..., None] * b_in[:, :, 0][:, :, None, None, :]
        )
        y = jnp.einsum("mbhdn,mbn->mbhd", h_new, c_out[:, :, 0])
        y = y.reshape(m, b, 1, di)
        h_fin = h_new

    y = y.astype(xn.dtype) + xc * lp["d_skip"][:, None, None, :].astype(xn.dtype)
    out = L.linear(y * jax.nn.silu(z), lp["w_ssm_out"])
    new_state = {"h": h_fin.reshape(m, b, di, n), "conv": new_conv}
    return out, new_state


def _conv(x, w, bias, conv_state=None, nvalid=None):
    """Depthwise causal conv — the mamba branch shares ssm's cell
    (incl. the tail-folding nvalid window gather), but keeps its own
    no-state short-sequence pad: a stateless call over fewer than K-1
    positions still emits a full (K-1)-deep conv state."""
    from repro.models.ssm import _causal_conv

    k = w.shape[1]
    if conv_state is None and nvalid is None and x.shape[2] < k - 1:
        conv_state = jnp.zeros(x.shape[:2] + (k - 1, x.shape[3]), x.dtype)
    return _causal_conv(x, w, bias, conv_state, nvalid=nvalid)


def mamba_state_shape(cfg, m, b):
    di, n, k = d_inner(cfg), cfg.ssm_state, cfg.conv_kernel
    return {
        "h": ((m, b, di, n), jnp.float32),
        "conv": ((m, b, k - 1, di), jnp.dtype(cfg.dtype)),
    }


# ---------------------------------------------------------------------------
# hybrid block
# ---------------------------------------------------------------------------


def _norm_branch(y, scale, eps):
    return L.rms_norm(y, scale, eps)


def hymba_block(
    cfg, lp, x, positions, window, *,
    kv_cache=None, decode_pos=None, cache_slot=None, cache_kv_pos=None,
    ssm_state=None, kernel_attn: bool = False, live=None,
):
    """One hybrid block. window: static int or traced scalar.
    ``kernel_attn`` routes decode attention through the Pallas
    flash-decode kernel (valid only for the plain-ring global-group
    layout — see gqa_attention's use_kernel contract)."""
    xn = L.rms_norm(x, lp["norm"], cfg.norm_eps)
    attn_out, new_kv = L.gqa_attention(
        xn, lp,
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
        positions=positions, window=window, sink=NUM_META_TOKENS,
        cache=kv_cache, decode_pos=decode_pos,
        cache_slot=cache_slot, cache_kv_pos=cache_kv_pos,
        use_kernel=kernel_attn, live=live,
    )
    ssm_out, new_ssm = mamba_branch(cfg, lp, xn, state=ssm_state)
    if live is not None:
        # a stopped lane keeps its mamba state (DESIGN.md §6.6)
        new_ssm = jax.tree.map(
            lambda n, o: jnp.where(
                live.reshape(live.shape + (1,) * (n.ndim - 2)), n, o),
            new_ssm, ssm_state)
    fused = 0.5 * (
        _norm_branch(attn_out, lp["attn_out_norm"], cfg.norm_eps)
        + _norm_branch(ssm_out, lp["ssm_out_norm"], cfg.norm_eps)
    )
    x = x + fused
    n = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    x = x + L.swiglu_mlp(n, lp["w_gate"], lp["w_up"], lp["w_down"])
    return x, new_kv, new_ssm


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def _prepend_meta(cfg, params, x):
    m, b, s, d = x.shape
    meta = jnp.broadcast_to(
        params["meta_tokens"][:, None].astype(x.dtype), (m, b, NUM_META_TOKENS, d)
    )
    return jnp.concatenate([meta, x], axis=2)


def _positions(m, b, s):
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (m, b, s))


def forward(cfg, params, tokens, *, remat: bool = False):
    """Training forward; logits over the real (non-meta) positions."""
    m, b, s = tokens.shape
    x = L.embed(tokens, params["embed"], jnp.dtype(cfg.dtype))
    x = _prepend_meta(cfg, params, x)
    positions = _positions(m, b, s + NUM_META_TOKENS)
    windows = layer_windows(cfg)

    def body(xc, xs):
        lp, w = xs
        out, _, _ = hymba_block(cfg, lp, xc, positions, w)
        return out, None

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = lax.scan(body, x, (params["layers"], windows))
    x = x[:, :, NUM_META_TOKENS:]
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return L.unembed(x, params["lm_head"])


def make_cache(cfg, m, b, context_len):
    """Decode caches: per decode-group KV (ring W+meta for SWA, full ctx
    for global) + per-layer mamba states."""
    w = swa_window(cfg)
    kv = []
    for (i0, i1, is_global) in decode_groups(cfg):
        s_cache = context_len if is_global else min(NUM_META_TOKENS + w, context_len)
        kv.append(L.make_kv_cache(
            i1 - i0, m, b, s_cache, cfg.num_kv_heads, cfg.head_dim, jnp.dtype(cfg.dtype)
        ))
    shapes = mamba_state_shape(cfg, m, b)
    ssm_state = {
        k: jnp.zeros((cfg.num_layers,) + sh, dt) for k, (sh, dt) in shapes.items()
    }
    return {"kv": kv, "ssm": ssm_state}


def _swa_slot_positions(pos, s_cache):
    """Slot->absolute-position map for the meta+ring cache layout: slots
    [0, R) hold meta tokens 0..R-1 forever; slots [R, R+W) ring over
    positions >= R.  pos: (M,B) current absolute position (>= R)."""
    r = NUM_META_TOKENS
    w = s_cache - r
    ring = L.cache_slot_positions(pos - r, w)                  # (M,B,w) of pos-r
    ring = jnp.where(ring >= 0, ring + r, -1)
    meta = jnp.broadcast_to(
        jnp.arange(r, dtype=jnp.int32), pos.shape + (r,)
    )
    return jnp.concatenate([meta, ring], axis=-1)


def decode_step(cfg, params, cache, tokens, pos, *, live=None):
    """tokens (M,B,1); pos (M,B) absolute position INCLUDING the meta
    offset (first real token decodes at pos = NUM_META_TOKENS + prompt).
    A lane whose ``live`` is False keeps its K/V rows and mamba states."""
    m, b, _ = tokens.shape
    x = L.embed(tokens, params["embed"], jnp.dtype(cfg.dtype))
    positions = pos[..., None]
    w = swa_window(cfg)
    new_kv, new_ssm = [], {k: [] for k in cache["ssm"]}

    for gi, (i0, i1, is_global) in enumerate(decode_groups(cfg)):
        lp_g = jax.tree.map(lambda t: t[i0:i1], params["layers"])
        ssm_g = jax.tree.map(lambda t: t[i0:i1], cache["ssm"])
        kv_g = cache["kv"][gi]
        s_cache = kv_g.k.shape[3]
        if is_global:
            slot = pos % s_cache
            kv_pos = L.cache_slot_positions(pos, s_cache)
            win = GLOBAL_WINDOW
        else:
            r = NUM_META_TOKENS
            slot = r + (pos - r) % (s_cache - r)
            kv_pos = _swa_slot_positions(pos, s_cache)
            win = w

        # global groups are a plain ring with no effective window, which
        # is exactly the flash-decode kernel's contract; SWA groups keep
        # the meta-pinned XLA path
        kattn = bool(cfg.use_pallas_kernels) and is_global

        def body(xc, xs, win=win, slot=slot, kv_pos=kv_pos, kattn=kattn):
            lp, ck, cv, sh, sconv = xs
            out, nkv, nssm = hymba_block(
                cfg, lp, xc, positions, win,
                kv_cache=(ck, cv), decode_pos=pos,
                cache_slot=slot, cache_kv_pos=kv_pos,
                ssm_state={"h": sh, "conv": sconv}, kernel_attn=kattn,
                live=live,
            )
            return out, (nkv[0], nkv[1], nssm["h"], nssm["conv"])

        x, (nk, nv, nh, nconv) = lax.scan(
            body, x, (lp_g, kv_g.k, kv_g.v, ssm_g["h"], ssm_g["conv"])
        )
        new_kv.append(KVCache(k=nk, v=nv))
        new_ssm["h"].append(nh)
        new_ssm["conv"].append(nconv)

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(x, params["lm_head"])[:, :, 0]
    new_cache = {
        "kv": new_kv,
        "ssm": {k: jnp.concatenate(v, axis=0) for k, v in new_ssm.items()},
    }
    return logits, new_cache


def prefill(cfg, params, tokens):
    """Prompt pass; returns (last logits, decode cache). The prompt plus
    meta tokens must fit the SWA ring for SWA layers (or be <= context)."""
    m, b, s = tokens.shape
    x = L.embed(tokens, params["embed"], jnp.dtype(cfg.dtype))
    x = _prepend_meta(cfg, params, x)
    st = s + NUM_META_TOKENS
    positions = _positions(m, b, st)
    w = swa_window(cfg)
    cache = make_cache(cfg, m, b, context_len=max(st, NUM_META_TOKENS + w))
    windows = layer_windows(cfg)

    # run layer-by-layer (python loop) so per-layer k/v can be captured
    # into the heterogeneous group caches; prefill is offline so HLO size
    # is acceptable here.
    groups = decode_groups(cfg)
    new_kv = []
    ssm_h, ssm_conv = [], []
    for gi, (i0, i1, is_global) in enumerate(groups):
        kv_g = cache["kv"][gi]
        ks, vs = [], []
        for li in range(i0, i1):
            lp = jax.tree.map(lambda t: t[li], params["layers"])
            xn = L.rms_norm(x, lp["norm"], cfg.norm_eps)
            q = L.linear(xn, lp["wq"]).reshape(m, b, st, cfg.num_heads, cfg.head_dim)
            kk = L.linear(xn, lp["wk"]).reshape(m, b, st, cfg.num_kv_heads, cfg.head_dim)
            vv = L.linear(xn, lp["wv"]).reshape(m, b, st, cfg.num_kv_heads, cfg.head_dim)
            q = L.rope(q, positions, cfg.rope_theta)
            kk = L.rope(kk, positions, cfg.rope_theta)
            win = GLOBAL_WINDOW if is_global else w
            o = L.flash_attention(
                q, kk, vv, positions, positions, window=win, sink=NUM_META_TOKENS
            )
            attn_out = L.linear(o.reshape(m, b, st, -1), lp["wo"])
            ssm_out, sstate = mamba_branch(cfg, lp, xn)
            fused = 0.5 * (
                _norm_branch(attn_out, lp["attn_out_norm"], cfg.norm_eps)
                + _norm_branch(ssm_out, lp["ssm_out_norm"], cfg.norm_eps)
            )
            x = x + fused
            n = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            x = x + L.swiglu_mlp(n, lp["w_gate"], lp["w_up"], lp["w_down"])
            ssm_h.append(sstate["h"])
            ssm_conv.append(sstate["conv"])
            # place k/v into this group's cache layout
            s_cache = kv_g.k.shape[3]
            if is_global:
                pad = s_cache - st
                kc = jnp.pad(kk, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
                vc = jnp.pad(vv, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            else:
                r = NUM_META_TOKENS
                ring = s_cache - r
                # meta tokens + last `ring` real positions, ring-aligned
                n_real = st - r
                if n_real <= ring:
                    pad = ring - n_real
                    real_k = jnp.pad(kk[:, :, r:], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
                    real_v = jnp.pad(vv[:, :, r:], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
                else:
                    # keep last `ring` positions, rotated to ring slots
                    keep_k = kk[:, :, st - ring:]
                    keep_v = vv[:, :, st - ring:]
                    shift = (st - r) % ring
                    real_k = jnp.roll(keep_k, shift, axis=2)
                    real_v = jnp.roll(keep_v, shift, axis=2)
                kc = jnp.concatenate([kk[:, :, :r], real_k], axis=2)
                vc = jnp.concatenate([vv[:, :, :r], real_v], axis=2)
            ks.append(kc.astype(jnp.dtype(cfg.dtype)))
            vs.append(vc.astype(jnp.dtype(cfg.dtype)))
        new_kv.append(KVCache(k=jnp.stack(ks), v=jnp.stack(vs)))

    x = L.rms_norm(x[:, :, -1:], params["final_norm"], cfg.norm_eps)
    logits = L.unembed(x, params["lm_head"])[:, :, 0]
    return logits, {
        "kv": new_kv,
        "ssm": {"h": jnp.stack(ssm_h), "conv": jnp.stack(ssm_conv)},
    }


def init_chunk_carry(cfg, m, b, cache_len):
    return {"cache": make_cache(cfg, m, b, cache_len)}


def chunk_carry_axes(cfg):
    return {"cache": cache_axes(cfg)}


def prefill_chunk(cfg, params, batch, carry, offset):
    """One chunk of a state-carrying Hymba prefill — the mid-prompt
    chaining of the meta-token + SWA-ring caches that exact-length
    prefill couldn't do (old serving limitation).

    Positions [0, R) are the meta tokens (their embeddings come from
    ``params["meta_tokens"]``, the chunk's token ids there are ignored);
    prompt tokens follow at R+i.  Per decode group, chunk queries attend
    over [group cache before this chunk, chunk k/v] with one positional
    mask (causality + per-group window + ring validity + meta sink), so
    a ring slot overwritten by this chunk is still visible to exactly
    the chunk queries that precede the overwriting position.  Mamba
    states thread through ``mamba_branch(state=...)`` as in decode."""
    from repro.models.common import active_rules, constrain_axes

    tokens = batch["tokens"]
    cache = carry["cache"]
    valid = batch.get("valid")            # (M,B,C) tail-folding junk mask
    m, b, c = tokens.shape
    r = NUM_META_TOKENS
    positions = offset[..., None] + jnp.arange(c, dtype=jnp.int32)   # (M,B,C)
    tok_x = L.embed(tokens, params["embed"], jnp.dtype(cfg.dtype))
    midx = jnp.clip(positions, 0, r - 1)
    meta_x = jax.vmap(lambda t, i: jnp.take(t, i, axis=0))(
        params["meta_tokens"], midx.reshape(m, b * c)
    ).reshape(m, b, c, -1).astype(tok_x.dtype)
    x = jnp.where((positions < r)[..., None], meta_x, tok_x)
    w = swa_window(cfg)
    kv_ax = ("instances", "batch", "cache_seq", "kv_heads", "kv_hd")
    new_kv, new_ssm = [], {k: [] for k in cache["ssm"]}

    for gi, (i0, i1, is_global) in enumerate(decode_groups(cfg)):
        lp_g = jax.tree.map(lambda t: t[i0:i1], params["layers"])
        ssm_g = jax.tree.map(lambda t: t[i0:i1], cache["ssm"])
        kv_g = cache["kv"][gi]
        s_cache = kv_g.k.shape[3]
        pin = 0 if is_global else r
        win = GLOBAL_WINDOW if is_global else w
        before = L.cache_positions_after(offset - 1, s_cache, pin)
        kv_pos = jnp.concatenate([before, positions], axis=-1)

        def body(xc, xs, win=win, pin=pin, kv_pos=kv_pos, s_cache=s_cache):
            lp, ck, cv, sh, sconv = xs
            xn = L.rms_norm(xc, lp["norm"], cfg.norm_eps)
            q = L.linear(xn, lp["wq"]).reshape(m, b, c, cfg.num_heads, cfg.head_dim)
            kk = L.linear(xn, lp["wk"]).reshape(m, b, c, cfg.num_kv_heads, cfg.head_dim)
            vv = L.linear(xn, lp["wv"]).reshape(m, b, c, cfg.num_kv_heads, cfg.head_dim)
            q = L.rope(q, positions, cfg.rope_theta)
            kk = L.rope(kk, positions, cfg.rope_theta)
            k_all = jnp.concatenate([ck, kk.astype(ck.dtype)], axis=2)
            v_all = jnp.concatenate([cv, vv.astype(cv.dtype)], axis=2)
            if cfg.use_pallas_kernels:
                # the group's window/pin are static (groups are a python
                # loop), so the Pallas chunk-prefill kernel derives the
                # causal+window+ring+sink mask from the lane offsets alone
                from repro.kernels import ops as K
                o = K.chunk_prefill_attention(
                    q, k_all, v_all, offset, s_cache=s_cache, pin=pin,
                    window=win, sink=r, rules=active_rules(),
                )
            else:
                o = L.flash_attention(
                    q, k_all, v_all, positions, kv_pos, window=win, sink=r,
                )
            attn_out = L.linear(o.reshape(m, b, c, -1), lp["wo"])
            ssm_out, nssm = mamba_branch(
                cfg, lp, xn, state={"h": sh, "conv": sconv}, valid=valid
            )
            fused = 0.5 * (
                _norm_branch(attn_out, lp["attn_out_norm"], cfg.norm_eps)
                + _norm_branch(ssm_out, lp["ssm_out_norm"], cfg.norm_eps)
            )
            xc = xc + fused
            n = L.rms_norm(xc, lp["mlp_norm"], cfg.norm_eps)
            xc = xc + L.swiglu_mlp(n, lp["w_gate"], lp["w_up"], lp["w_down"])
            nk = constrain_axes(L.cache_append_chunk(ck, kk, positions, pin, valid), kv_ax)
            nv = constrain_axes(L.cache_append_chunk(cv, vv, positions, pin, valid), kv_ax)
            return xc, (nk, nv, nssm["h"], nssm["conv"])

        x, (nk, nv, nh, nconv) = lax.scan(
            body, x, (lp_g, kv_g.k, kv_g.v, ssm_g["h"], ssm_g["conv"])
        )
        new_kv.append(KVCache(k=nk, v=nv))
        new_ssm["h"].append(nh)
        new_ssm["conv"].append(nconv)

    return {"cache": {
        "kv": new_kv,
        "ssm": {k: jnp.concatenate(v, axis=0) for k, v in new_ssm.items()},
    }}


def cache_abstract(cfg, m, b, context_len):
    """ShapeDtypeStruct cache (for the dry-run input specs)."""
    real = make_cache.__wrapped__ if hasattr(make_cache, "__wrapped__") else None
    c = jax.eval_shape(lambda: make_cache(cfg, m, b, context_len))
    return c


def cache_axes(cfg):
    ax = ("layers", "instances", "batch", "cache_seq", "kv_heads", "kv_hd")
    return {
        "kv": [KVCache(k=ax, v=ax) for _ in decode_groups(cfg)],
        "ssm": {
            "h": ("layers", "instances", "batch", "mlp", None),
            "conv": ("layers", "instances", "batch", None, "mlp"),
        },
    }


def take_state(cfg, cache, m, b):
    """Slice slot (m, b) out of the (M, B) hybrid cache (KV group caches
    + per-layer mamba states), keeping singleton dims.  The SWA ring and
    global caches keep their layouts, so a slot extracted here drops back
    in with put_state without re-rotation."""
    from repro.models.common import tree_take_slot
    return tree_take_slot(cache, cache_axes(cfg), m, b)


def put_state(cfg, grid, one, m, b):
    """Write a single-slot hybrid cache into grid slot (m, b).  KV leaves
    with a different cache_seq length are prefix-clipped (a per-request
    prefill cache may be shorter than the serving grid's context)."""
    from repro.models.common import tree_put_slot
    return tree_put_slot(grid, cache_axes(cfg), one, m, b)
