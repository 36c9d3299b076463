"""Token-choice top-k MoE decoder (olmoe-1b-7b, qwen3-moe-30b-a3b).

Fusion-aware like :mod:`repro.models.dense`.  NetFuse applicability
(DESIGN.md §4): the merged model is a *block-diagonal* MoE — instance m's
router only ever routes to instance m's E experts, which is exactly the
grouped-op structure of the paper generalized to E-way grouped weights;
merging M instances yields M*E experts in M routing groups.

Dispatch is sort-based with per-(instance, batch-row) token groups and a
static capacity (C = ceil(S*K/E * capacity_factor)):

  1. router top-k -> expert ids per token,
  2. per row: sort assignments by expert, position-in-expert via
     searchsorted segment starts (no T×E one-hot tensors — those would
     dominate HLO FLOPs/bytes and poison the roofline),
  3. scatter into a (E, C, D) buffer, batched expert einsum (this is the
     all-to-all producer under expert-parallel sharding),
  4. gather back, weight by router probs, scatter-add per token.

Tokens beyond capacity are dropped (standard capacity-factor semantics);
tests check zero drops at cf >= 1 with uniform routing and exact
per-instance isolation.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.common import (
    Factory, active_rules, constrain, make_factory, param_axes, param_values,
    stack_layer_params,
)
from repro.models.layers import KVCache


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def _layer_params(cfg: ModelConfig, f: Factory):
    m, d, h, kvh, hd = (
        cfg.num_instances, cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
    )
    e, ff = cfg.num_experts, cfg.d_ff
    p = {
        "attn_norm": f((m, d), ("instances", None), init="ones"),
        "wq": f((m, d, h * hd), ("instances", "embed", "heads_flat"), init="fan_in"),
        "wk": f((m, d, kvh * hd), ("instances", "embed", "kv_flat"), init="fan_in"),
        "wv": f((m, d, kvh * hd), ("instances", "embed", "kv_flat"), init="fan_in"),
        "wo": f((m, h * hd, d), ("instances", "heads_flat", "embed"), init="fan_in"),
        "mlp_norm": f((m, d), ("instances", None), init="ones"),
        "router": f((m, d, e), ("instances", "embed", None), init="fan_in"),
        "we_gate": f((m, e, d, ff), ("instances", "experts", "embed", "expert_mlp"), init="fan_in"),
        "we_up": f((m, e, d, ff), ("instances", "experts", "embed", "expert_mlp"), init="fan_in"),
        "we_down": f((m, e, ff, d), ("instances", "experts", "expert_mlp", "embed"), init="fan_in"),
    }
    if cfg.qkv_bias:
        p["bq"] = f((m, h * hd), ("instances", "heads_flat"), init="zeros")
        p["bk"] = f((m, kvh * hd), ("instances", "kv_flat"), init="zeros")
        p["bv"] = f((m, kvh * hd), ("instances", "kv_flat"), init="zeros")
    return p


def build_params(cfg: ModelConfig, f: Factory):
    m, d, v = cfg.num_instances, cfg.d_model, cfg.vocab_size
    return {
        "embed": f((m, v, d), ("instances", "vocab", "embed")),
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
# MoE dispatch
# ---------------------------------------------------------------------------


def capacity(cfg: ModelConfig, s: int) -> int:
    return max(1, math.ceil(s * cfg.num_experts_per_tok / cfg.num_experts * cfg.capacity_factor))


def _sorted_keep(e_sorted, cap, num_experts, counts=None, limit=None):
    """The keep/drop rule over one row's sorted assignment stream —
    shared by the plain and the expert-window (ep) dispatch so the two
    paths cannot drift apart (ep-vs-plain routing parity is a tested
    invariant): local position-in-expert below the chunk-local ``cap``,
    sentinel (masked-token) assignments excluded, and — when ``counts``
    carries earlier chunks' per-expert totals — GLOBAL position below
    ``limit``, the request's exact-length capacity.  Returns (pos, keep)."""
    sk = e_sorted.shape[0]
    starts = jnp.searchsorted(e_sorted, jnp.arange(num_experts, dtype=e_sorted.dtype))
    eid = jnp.minimum(e_sorted, num_experts - 1)
    pos = jnp.arange(sk, dtype=jnp.int32) - starts[eid].astype(jnp.int32)
    keep = (e_sorted < num_experts) & (pos < cap)
    if counts is not None:
        keep = keep & (counts[eid].astype(jnp.int32) + pos < limit)
    return pos, keep


def _row_dispatch(x_row, e_sorted, order, cap, num_experts, counts=None, limit=None):
    """Per-(m,b) row: build the (E*C, D) dispatch buffer.

    x_row: (S, D); e_sorted: (S*K,) expert id per sorted assignment
    (``num_experts`` is the sentinel id for masked-out assignments —
    they sort last and are never kept); order: (S*K,) argsort
    permutation.  ``counts``/``limit`` switch :func:`_sorted_keep` to
    the chainable chunked form, so chunked prefill routes identically
    to one exact-length pass.  Returns (buffer (E*C, D), dest, keep,
    tok_sorted)."""
    sk = e_sorted.shape[0]
    k = sk // x_row.shape[0]
    pos, keep = _sorted_keep(e_sorted, cap, num_experts, counts, limit)
    dest = jnp.where(keep, e_sorted.astype(jnp.int32) * cap + pos, num_experts * cap)
    tok_sorted = (order // k).astype(jnp.int32)
    buf = jnp.zeros((num_experts * cap, x_row.shape[1]), x_row.dtype)
    buf = buf.at[dest].set(x_row[tok_sorted], mode="drop")
    return buf, dest, keep, tok_sorted


def _row_combine(y_buf, dest, keep, tok_sorted, w_sorted, s):
    """Per row: gather expert outputs back and scatter-add into tokens."""
    y = jnp.take(y_buf, jnp.minimum(dest, y_buf.shape[0] - 1), axis=0)
    y = y * (keep & (dest < y_buf.shape[0]))[:, None].astype(y.dtype)
    y = y * w_sorted[:, None].astype(y.dtype)
    out = jnp.zeros((s, y.shape[1]), y.dtype)
    return out.at[tok_sorted].add(y, mode="drop")


def _shmap_rows(fn, rules, in_args, in_logical, out_logical):
    """Run ``fn`` (a per-(instance,batch-row) vmapped dispatch/combine) under
    ``jax.shard_map`` over the batch mesh axes, so its data-dependent
    gathers/scatters are device-local and invisible to GSPMD.

    §Perf (qwen3-moe iteration 3): left to GSPMD, the sorted dispatch's
    gather/scatter lower to "replicate-then-repartition" — per-layer
    collective-permutes/all-reduces of the full dispatch buffers plus u32
    index broadcasts at payload width.  shard_map makes them free: every
    token row lives on exactly one device.

    ``in_logical``/``out_logical``: logical axis tuples per arg/output,
    resolved against the active Rules (so divisibility guards and the
    pod axis are handled exactly like the surrounding constraints).
    """
    specs_in = tuple(
        rules.spec(lg, a.shape) for lg, a in zip(in_logical, in_args)
    )

    def wrapped(*args):
        outs = fn(*args)
        return outs

    # out shapes are only known after tracing; rules.spec needs shapes for
    # divisibility checks.  Trace abstractly first.
    out_abs = jax.eval_shape(fn, *in_args)
    flat_abs, treedef = jax.tree.flatten(out_abs)
    specs_out = treedef.unflatten(
        [rules.spec(lg, a.shape) for lg, a in zip(out_logical, flat_abs)]
    )
    return jax.shard_map(
        wrapped, mesh=rules.mesh, in_specs=specs_in, out_specs=specs_out,
        check_vma=False,
    )(*in_args)


def _row_dispatch_window(x_row, e_sorted, order, cap, num_experts, lo, e_local,
                         counts=None, limit=None):
    """Like _row_dispatch but scatters only assignments whose destination
    falls in the expert window [lo·cap, (lo+e_local)·cap) — the local
    expert shard.  Returns (buffer (e_local·cap, D), dest, keep_l,
    tok_sorted); dest stays GLOBAL so the caller's combine can share it.
    The keep rule (incl. the masked/chainable chunked form) is the SAME
    :func:`_sorted_keep` the plain dispatch uses."""
    sk = e_sorted.shape[0]
    k = sk // x_row.shape[0]
    pos, keep = _sorted_keep(e_sorted, cap, num_experts, counts, limit)
    dest = jnp.where(keep, e_sorted.astype(jnp.int32) * cap + pos, num_experts * cap)
    tok_sorted = (order // k).astype(jnp.int32)
    local = keep & (dest >= lo * cap) & (dest < (lo + e_local) * cap)
    dest_l = jnp.where(local, dest - lo * cap, e_local * cap)
    buf = jnp.zeros((e_local * cap, x_row.shape[1]), x_row.dtype)
    buf = buf.at[dest_l].set(x_row[tok_sorted], mode="drop")
    return buf, dest_l, local, tok_sorted


def _moe_mlp_ep_shmap(rules, lp, x, e_sorted, order, w_sorted, cap, e, s,
                      counts=None, limit=None):
    """Canonical expert parallelism in ONE shard_map (§Perf qwen3-moe
    iteration 4).

    Key observation: the dispatch inputs (x, sorted assignments) are
    batch-sharded and *replicated over "model"* — every model-rank can
    rebuild its rows' dispatch state locally for free.  So each rank:
      1. scatters only the assignments that target its expert window
         (E/TP experts) — local, no wire,
      2. runs the expert einsums on its local expert slice of the
         experts->"model"-sharded weights — no wire,
      3. combines its experts' outputs back into token space (s, d) —
         local scatter-add,
      4. one psum over "model" sums the per-window partials.
    Wire per layer = token bytes (the psum) — ~K·cf× less than moving
    dispatch buffers, independent of E.
    """
    m, b, _, d = x.shape
    f = lp["we_gate"].shape[-1]
    mesh = rules.mesh
    nm = dict(mesh.shape).get("model", 1)
    x_spec = rules.spec(("instances", "batch", None, None), x.shape)
    row_spec = rules.spec(("instances", "batch", None), e_sorted.shape)
    # weights enter as explicit args so their experts->"model" sharding is
    # honored (a closure capture would lift them as replicated implicit
    # inputs = the weight all-gather this path exists to avoid).  The
    # embed/mlp dims are requested unsharded — that regather is the
    # standard FSDP per-layer weight gather, not an EP cost.
    wg_spec = rules.spec(("instances", "experts", None, None), (m, e, d, f))
    wd_spec = rules.spec(("instances", "experts", None, None), (m, e, f, d))
    # chunked extras ride as explicit batch-sharded inputs (replicated
    # over "model", like the dispatch rows) so every rank applies the
    # SAME global counts+limit keep rule to its expert window; the
    # non-chunked call passes neutral dummies (0 counts, INT32_MAX
    # limit), under which the chunked keep rule collapses to the plain
    # one — a single code path either way
    if counts is None:
        counts = jnp.zeros((m, x.shape[1], e), jnp.int32)
        limit = jnp.full((m, x.shape[1]), jnp.iinfo(jnp.int32).max, jnp.int32)

    def body(x_l, es_l, od_l, ws_l, ct_l, lm_l, wg, wu, wd):
        e_local = wg.shape[1]
        lo = lax.axis_index("model") * e_local if e_local != e else 0

        def row(xr, es, od, ct, lm):
            return _row_dispatch_window(xr, es, od, cap, e, lo, e_local,
                                        counts=ct, limit=lm)

        buf, dest_l, local, tok = jax.vmap(jax.vmap(row))(
            x_l, es_l, od_l, ct_l, lm_l)
        m_l, b_l = buf.shape[0], buf.shape[1]
        buf = buf.reshape(m_l, b_l, e_local, cap, d)

        h = jax.nn.silu(jnp.einsum("mbecd,medf->mbecf", buf, wg.astype(buf.dtype)))
        h = h * jnp.einsum("mbecd,medf->mbecf", buf, wu.astype(buf.dtype))
        y_buf = jnp.einsum("mbecf,mefd->mbecd", h, wd.astype(buf.dtype))
        y_buf = y_buf.reshape(m_l, b_l, e_local * cap, d)

        comb = jax.vmap(jax.vmap(
            lambda yb, de, ke, ts, ww: _row_combine(yb, de, ke, ts, ww, s)
        ))
        part = comb(y_buf, dest_l, local, tok, ws_l.astype(y_buf.dtype))
        if e_local != e:
            part = lax.psum(part, "model")          # sum expert-window partials
        return part                                  # (m_l, b_l, s, d)

    out_spec = rules.spec(("instances", "batch", None, None), (m, b, s, d))
    ct_spec = rules.spec(("instances", "batch", None), counts.shape)
    lm_spec = rules.spec(("instances", "batch"), limit.shape)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, row_spec, row_spec, row_spec, ct_spec, lm_spec,
                  wg_spec, wg_spec, wd_spec),
        out_specs=out_spec,
        check_vma=False,
    )(x, e_sorted, order, w_sorted, counts, limit,
      lp["we_gate"], lp["we_up"], lp["we_down"])


def moe_mlp(cfg: ModelConfig, lp, x, *, valid=None, counts=None, limit=None):
    """x: (M,B,S,D) -> (M,B,S,D), aux load-balance loss (scalar, f32).

    Chainable/masked routing (serving chunked prefill — DESIGN.md §6.2):

    * ``valid`` (M,B,S) bool masks padded/junk tokens out of routing
      entirely (their assignments take a sentinel expert id, sort last,
      never occupy capacity and combine to zero),
    * ``counts`` (M,B,E) int32 carries per-expert assignment counts from
      earlier chunks of the same request and ``limit`` (M,B) int32 is
      the exact-length capacity computed from the request's REAL token
      count — together they make the keep/drop decisions of a chunked
      prefill identical to one exact-length pass (position-in-expert is
      global, capacity comes from unpadded lengths).

    Returns (out, aux) — plus the updated counts as a third element when
    ``counts`` is given."""
    m, b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    chunked = counts is not None
    # chunk-local buffers never drop (S*K rows bound any expert's share);
    # all dropping is decided by the global counts+limit rule above
    cap = s * k if chunked else capacity(cfg, s)

    # §Perf (EXPERIMENTS.md qwen3-moe iteration 1): the sort-based dispatch
    # below is data-dependent gather/scatter along the token axis.  GSPMD
    # cannot partition such ops when the sliced dim (seq, under Megatron-SP)
    # or the gathered payload dim is sharded — it falls back to "replicate
    # then re-partition", i.e. per-layer all-reduces of the full (B,S·K,D)
    # buffer (~17 TB/step for qwen3-moe train_4k).  Constrain the whole
    # dispatch region to batch-only sharding: batched gathers/scatters over
    # sharded batch dims partition natively.
    x = constrain(x, "instances", "batch", None, "act_embed")

    logits = jnp.einsum(
        "mbsd,mde->mbse", x.astype(jnp.float32), lp["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)                    # (M,B,S,E)
    top_w, top_e = lax.top_k(probs, k)                         # (M,B,S,K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    e_flat = top_e.reshape(m, b, s * k)
    w_flat = top_w.reshape(m, b, s * k)
    if valid is not None:
        v_flat = jnp.broadcast_to(valid[..., None], (m, b, s, k)).reshape(m, b, s * k)
        e_flat = jnp.where(v_flat, e_flat, e)      # sentinel: sorts last, never kept
    new_counts = None
    if chunked:
        # every (non-masked) assignment advances its expert's global
        # position, kept or dropped — matching the exact-length rule
        new_counts = counts + jax.nn.one_hot(e_flat, e, dtype=jnp.int32).sum(axis=2)
    order = jnp.argsort(e_flat, axis=-1).astype(jnp.int32)
    e_sorted = constrain(
        jnp.take_along_axis(e_flat, order, axis=-1), "instances", "batch", None
    )
    w_sorted = jnp.take_along_axis(w_flat, order, axis=-1)

    rules = active_rules()
    # experts_compute placement (§Perf qwen3-moe iterations 1–4):
    #   "model" — expert-parallel einsums; buf resharded batch->experts by
    #             GSPMD (costly replicate-repartition in practice),
    #   None    — DP-compute: buf stays batch-sharded, expert weights
    #             all-gathered per layer (wins when dispatched activations,
    #             ~K·cf× token bytes, outweigh the weights),
    #   "ep"    — canonical EP in one shard_map: per-rank expert-window
    #             dispatch + local einsums + token-space psum (wire per
    #             layer = token bytes; see _moe_mlp_ep_shmap).
    placement = rules.mapping.get("experts_compute") if rules is not None else None
    if placement == "ep":
        # masked/chainable routing works here too: the sentinel expert id
        # sorts masked tokens last (never kept in any window) and the
        # global counts+limit keep rule is applied per rank before the
        # window filter, so the ep path routes exactly like the plain one
        out = _moe_mlp_ep_shmap(
            rules, lp, x, e_sorted, order, w_sorted, cap, e, s,
            counts=counts, limit=limit,
        )
        out = constrain(out, "instances", "batch", "seq", "act_embed")
        frac = jnp.mean(
            (jax.nn.one_hot(top_e, e, dtype=jnp.float32)).sum(-2), axis=(1, 2)
        )
        pmean = probs.mean(axis=(1, 2))
        aux = (e * (frac / k * pmean).sum(-1)).mean()
        if chunked:
            return out, aux, new_counts
        return out, aux

    row2 = ("instances", "batch", None)
    row3 = ("instances", "batch", None, None)
    if chunked:
        disp = jax.vmap(jax.vmap(
            lambda xr, es, od, ct, lm: _row_dispatch(xr, es, od, cap, e, ct, lm)
        ))
        d_args = (x, e_sorted, order, counts, limit)
        d_logical = (row3, row2, row2, row2, ("instances", "batch"))
    else:
        disp = jax.vmap(jax.vmap(lambda xr, es, od: _row_dispatch(xr, es, od, cap, e)))
        d_args = (x, e_sorted, order)
        d_logical = (row3, row2, row2)
    if rules is None:
        buf, dest, keep, tok_sorted = disp(*d_args)
    else:
        buf, dest, keep, tok_sorted = _shmap_rows(
            disp, rules, d_args,
            in_logical=d_logical,
            out_logical=(row3, row2, row2, row2),
        )
    buf = buf.reshape(m, b, e, cap, d)
    buf = constrain(buf, "instances", "batch", "experts_compute", None, "act_embed")
    h = jax.nn.silu(jnp.einsum("mbecd,medf->mbecf", buf, lp["we_gate"].astype(buf.dtype)))
    h = h * jnp.einsum("mbecd,medf->mbecf", buf, lp["we_up"].astype(buf.dtype))
    h = constrain(h, "instances", "batch", "experts_compute", None, "expert_mlp")
    y_buf = jnp.einsum("mbecf,mefd->mbecd", h, lp["we_down"].astype(buf.dtype))
    y_buf = y_buf.reshape(m, b, e * cap, d)
    y_buf = constrain(y_buf, "instances", "batch", None, "act_embed")

    comb = jax.vmap(jax.vmap(
        lambda yb, de, ke, ts, ws: _row_combine(yb, de, ke, ts, ws, s)
    ))
    ws_cast = w_sorted.astype(y_buf.dtype)
    if rules is None:
        out = comb(y_buf, dest, keep, tok_sorted, ws_cast)
    else:
        out = _shmap_rows(
            comb, rules, (y_buf, dest, keep, tok_sorted, ws_cast),
            in_logical=(row3, row2, row2, row2, row2),
            out_logical=(row3,),
        )
    out = constrain(out, "instances", "batch", "seq", "act_embed")

    # load-balance aux (Switch-style): E * sum_e f_e * P_e
    frac = jnp.mean(
        (jax.nn.one_hot(top_e, e, dtype=jnp.float32)).sum(-2), axis=(1, 2)
    )                                                          # (M,E) assignment frac * k
    pmean = probs.mean(axis=(1, 2))                            # (M,E)
    aux = (e * (frac / k * pmean).sum(-1)).mean()
    if chunked:
        return out, aux, new_counts
    return out, aux


# ---------------------------------------------------------------------------
# blocks / entry points
# ---------------------------------------------------------------------------


def _attn(cfg, lp, x, positions, *, cache=None, decode_pos=None, live=None):
    n = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    h, new_cache = L.gqa_attention(
        n, lp,
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
        positions=positions, window=cfg.sliding_window,
        cache=cache, decode_pos=decode_pos, live=live,
    )
    return x + h, new_cache


def _block(cfg, lp, x, positions, *, cache=None, decode_pos=None, live=None):
    x, new_cache = _attn(cfg, lp, x, positions, cache=cache,
                         decode_pos=decode_pos, live=live)
    n = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    y, aux = moe_mlp(cfg, lp, n)
    return x + y, new_cache, aux


def _positions(tokens):
    m, b, s = tokens.shape
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (m, b, s))


def forward(cfg, params, tokens, *, remat: bool = False, return_aux: bool = False):
    x = L.embed(tokens, params["embed"], jnp.dtype(cfg.dtype))
    positions = _positions(tokens)

    def body(carry, lp):
        xc, aux_sum = carry
        out, _, aux = _block(cfg, lp, xc, positions)
        return (out, aux_sum + aux), None

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    (x, aux), _ = lax.scan(body, (x, jnp.float32(0.0)), params["layers"])
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(x, params["lm_head"])
    if return_aux:
        return logits, aux / cfg.num_layers
    return logits


def prefill(cfg, params, tokens, *, cache_len: int | None = None):
    m, b, s = tokens.shape
    x = L.embed(tokens, params["embed"], jnp.dtype(cfg.dtype))
    positions = _positions(tokens)
    window = cfg.sliding_window
    if cache_len is None:
        cache_len = window if window else s

    def body(xc, lp):
        n = L.rms_norm(xc, lp["attn_norm"], cfg.norm_eps)
        q = L.linear(n, lp["wq"], lp.get("bq")).reshape(m, b, s, cfg.num_heads, cfg.head_dim)
        kk = L.linear(n, lp["wk"], lp.get("bk")).reshape(m, b, s, cfg.num_kv_heads, cfg.head_dim)
        vv = L.linear(n, lp["wv"], lp.get("bv")).reshape(m, b, s, cfg.num_kv_heads, cfg.head_dim)
        q = L.rope(q, positions, cfg.rope_theta)
        kk = L.rope(kk, positions, cfg.rope_theta)
        o = L.flash_attention(q, kk, vv, positions, positions, window=window)
        xc = xc + L.linear(o.reshape(m, b, s, -1), lp["wo"], lp.get("bo"))
        n = L.rms_norm(xc, lp["mlp_norm"], cfg.norm_eps)
        y, _ = moe_mlp(cfg, lp, n)
        xc = xc + y
        if cache_len >= s:
            pad = cache_len - s
            kc = jnp.pad(kk, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            vc = jnp.pad(vv, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        else:
            kc, vc = kk[:, :, s - cache_len:], vv[:, :, s - cache_len:]
        return xc, (kc.astype(jnp.dtype(cfg.dtype)), vc.astype(jnp.dtype(cfg.dtype)))

    x, (ck, cv) = lax.scan(body, x, params["layers"])
    x = L.rms_norm(x[:, :, -1:], params["final_norm"], cfg.norm_eps)
    return L.unembed(x, params["lm_head"])[:, :, 0], KVCache(k=ck, v=cv)


def decode_step(cfg, params, cache: KVCache, tokens, pos, *, live=None):
    """One decode step; a lane whose ``live`` is False keeps its cache."""
    x = L.embed(tokens, params["embed"], jnp.dtype(cfg.dtype))
    positions = pos[..., None]

    def body(xc, xs):
        lp, ck, cv = xs
        out, new_cache, _ = _block(cfg, lp, xc, positions, cache=(ck, cv),
                                   decode_pos=pos, live=live)
        return out, new_cache

    x, (nk, nv) = lax.scan(body, x, (params["layers"], cache.k, cache.v))
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return L.unembed(x, params["lm_head"])[:, :, 0], KVCache(k=nk, v=nv)


def make_cache(cfg, m, b, context_len):
    s_cache = cfg.sliding_window if cfg.sliding_window else context_len
    return L.make_kv_cache(
        cfg.num_layers, m, b, s_cache, cfg.num_kv_heads, cfg.head_dim, jnp.dtype(cfg.dtype)
    )


def cache_axes(cfg):
    ax = ("layers", "instances", "batch", "cache_seq", "kv_heads", "kv_hd")
    return KVCache(k=ax, v=ax)


def init_chunk_carry(cfg: ModelConfig, m: int, b: int, cache_len: int):
    return {
        "cache": make_cache(cfg, m, b, cache_len),
        # per-layer, per-expert assignment counts from earlier chunks:
        # routers are independent per layer, so the chainable capacity
        # rule needs one usage row per layer
        "counts": jnp.zeros((cfg.num_layers, m, b, cfg.num_experts), jnp.int32),
    }


def chunk_carry_axes(cfg: ModelConfig):
    return {
        "cache": cache_axes(cfg),
        "counts": ("layers", "instances", "batch", None),
    }


def prefill_chunk(cfg: ModelConfig, params, batch, carry, offset):
    """Chunked prefill with exact-length-equivalent expert routing.

    batch["moe_limit"]: (M,B) int32 — the capacity an exact-length
    prefill of this request's REAL token count would use; combined with
    the carried per-layer expert counts, chunked routing keeps/drops
    exactly the tokens the exact pass would (closes the bucketed-prefill
    capacity caveat)."""
    from repro.models.common import constrain_axes

    tokens, limit = batch["tokens"], batch["moe_limit"]
    cache, counts = carry["cache"], carry["counts"]
    valid = batch.get("valid")            # (M,B,C) tail-folding junk mask
    m, b, c = tokens.shape
    x = L.embed(tokens, params["embed"], jnp.dtype(cfg.dtype))
    positions = offset[..., None] + jnp.arange(c, dtype=jnp.int32)
    window = cfg.sliding_window
    s_cache = cache.k.shape[3]
    before = L.cache_positions_after(offset - 1, s_cache, 0)
    kv_pos = jnp.concatenate([before, positions], axis=-1)
    kv_ax = ("instances", "batch", "cache_seq", "kv_heads", "kv_hd")

    def body(xc, xs):
        lp, ck, cv, cnt = xs
        n = L.rms_norm(xc, lp["attn_norm"], cfg.norm_eps)
        q = L.linear(n, lp["wq"], lp.get("bq")).reshape(m, b, c, cfg.num_heads, cfg.head_dim)
        kk = L.linear(n, lp["wk"], lp.get("bk")).reshape(m, b, c, cfg.num_kv_heads, cfg.head_dim)
        vv = L.linear(n, lp["wv"], lp.get("bv")).reshape(m, b, c, cfg.num_kv_heads, cfg.head_dim)
        q = L.rope(q, positions, cfg.rope_theta)
        kk = L.rope(kk, positions, cfg.rope_theta)
        o = L.flash_attention(
            q,
            jnp.concatenate([ck, kk.astype(ck.dtype)], axis=2),
            jnp.concatenate([cv, vv.astype(cv.dtype)], axis=2),
            positions, kv_pos, window=window,
        )
        xc = xc + L.linear(o.reshape(m, b, c, -1), lp["wo"], lp.get("bo"))
        n = L.rms_norm(xc, lp["mlp_norm"], cfg.norm_eps)
        y, _, new_cnt = moe_mlp(cfg, lp, n, valid=valid, counts=cnt, limit=limit)
        xc = xc + y
        nk = constrain_axes(L.cache_append_chunk(ck, kk, positions, 0, valid), kv_ax)
        nv = constrain_axes(L.cache_append_chunk(cv, vv, positions, 0, valid), kv_ax)
        return xc, (nk, nv, new_cnt)

    _, (nk, nv, ncnt) = lax.scan(body, x, (params["layers"], cache.k, cache.v, counts))
    return {"cache": KVCache(k=nk, v=nv), "counts": ncnt}
