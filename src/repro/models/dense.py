"""Dense llama-family decoder (tinyllama, deepseek-67b, granite-3-2b,
qwen1.5-0.5b; also the LM trunk reused by the VLM family).

Fusion-aware: params carry a leading instances axis M; tokens are
(M, B, S) with per-instance batches.  Layer stack runs under lax.scan
over params stacked on a leading L axis.

Entry points:
  forward(cfg, params, tokens)                      -> logits (M,B,S,V)
  prefill(cfg, params, tokens)                      -> (last logits, KVCache)
  decode_step(cfg, params, cache, tokens, pos)      -> (logits, KVCache)

``cfg.sliding_window > 0`` switches every layer to sliding-window
attention (the sub-quadratic variant used for the long_500k shape); the
decode cache is then a ring buffer of window size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.common import (
    Factory,
    constrain,
    make_factory,
    param_axes,
    param_values,
    stack_layer_params,
)
from repro.models.layers import KVCache


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def _layer_params(cfg: ModelConfig, f: Factory):
    m, d, h, kvh, hd, ff = (
        cfg.num_instances, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
        cfg.head_dim, cfg.d_ff,
    )
    p = {
        "attn_norm": f((m, d), ("instances", None), init="ones"),
        "wq": f((m, d, h * hd), ("instances", "embed", "heads_flat"), init="fan_in"),
        "wk": f((m, d, kvh * hd), ("instances", "embed", "kv_flat"), init="fan_in"),
        "wv": f((m, d, kvh * hd), ("instances", "embed", "kv_flat"), init="fan_in"),
        "wo": f((m, h * hd, d), ("instances", "heads_flat", "embed"), init="fan_in"),
        "mlp_norm": f((m, d), ("instances", None), init="ones"),
        "w_gate": f((m, d, ff), ("instances", "embed", "mlp"), init="fan_in"),
        "w_up": f((m, d, ff), ("instances", "embed", "mlp"), init="fan_in"),
        "w_down": f((m, ff, d), ("instances", "mlp", "embed"), init="fan_in"),
    }
    if cfg.qkv_bias:
        p["bq"] = f((m, h * hd), ("instances", "heads_flat"), init="zeros")
        p["bk"] = f((m, kvh * hd), ("instances", "kv_flat"), init="zeros")
        p["bv"] = f((m, kvh * hd), ("instances", "kv_flat"), init="zeros")
    return p


def build_params(cfg: ModelConfig, f: Factory):
    m, d, v = cfg.num_instances, cfg.d_model, cfg.vocab_size
    layers = stack_layer_params([_layer_params(cfg, f) for _ in range(cfg.num_layers)])
    p = {
        "embed": f((m, v, d), ("instances", "vocab", "embed")),
        "layers": layers,
        "final_norm": f((m, d), ("instances", None), init="ones"),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = f((m, d, v), ("instances", "embed", "vocab"), init="fan_in")
    return p


def init(cfg: ModelConfig, key):
    return param_values(build_params(cfg, make_factory(cfg, key)))


def abstract_params(cfg: ModelConfig):
    return param_values(build_params(cfg, make_factory(cfg, abstract=True)))


def axes(cfg: ModelConfig):
    return param_axes(build_params(cfg, make_factory(cfg, abstract=True)))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _attn_mlp(cfg: ModelConfig, lp, x, positions, *, window, cache=None, decode_pos=None):
    """One transformer block; returns (x, new_cache_layer)."""
    n = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    h, new_cache = L.gqa_attention(
        n, lp,
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
        positions=positions, window=window, cache=cache, decode_pos=decode_pos,
    )
    x = x + h
    n = L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    x = x + L.swiglu_mlp(n, lp["w_gate"], lp["w_up"], lp["w_down"])
    return x, new_cache


def _positions(tokens):
    m, b, s = tokens.shape
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (m, b, s))


def _embed_in(cfg, params, tokens):
    x = L.embed(tokens, params["embed"], jnp.dtype(cfg.dtype))
    return constrain(x, "instances", "batch", "seq", "act_embed")


def _logits(cfg, params, x):
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["lm_head"] if not cfg.tie_embeddings else jnp.swapaxes(params["embed"], -1, -2)
    return L.unembed(x, head)


def forward(
    cfg: ModelConfig,
    params,
    tokens,
    *,
    inputs_embeds=None,
    positions=None,
    remat: bool = False,
) -> jax.Array:
    """Full-sequence forward (training / evaluation). Returns (M,B,S,V)."""
    x = _embed_in(cfg, params, tokens) if inputs_embeds is None else inputs_embeds
    positions = _positions(tokens) if positions is None else positions
    window = cfg.sliding_window

    def body(xc, lp):
        out, _ = _attn_mlp(cfg, lp, xc, positions, window=window)
        return out, None

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = lax.scan(body, x, params["layers"])
    return _logits(cfg, params, x)


def prefill(cfg: ModelConfig, params, tokens, *, cache_len: int | None = None):
    """Process a full prompt; returns (logits for last position, KVCache).

    The returned cache has length ``cache_len`` (defaults to the window
    size for sliding-window models, else the prompt length) and is laid
    out ring-buffer-consistently so decode can continue at pos = S."""
    m, b, s = tokens.shape
    x = _embed_in(cfg, params, tokens)
    positions = _positions(tokens)
    window = cfg.sliding_window
    if cache_len is None:
        cache_len = window if window else s

    def body(xc, lp):
        n = L.rms_norm(xc, lp["attn_norm"], cfg.norm_eps)
        # recompute k/v for cache extraction: run attention and also emit k,v
        q = L.linear(n, lp["wq"], lp.get("bq")).reshape(m, b, s, cfg.num_heads, cfg.head_dim)
        k = L.linear(n, lp["wk"], lp.get("bk")).reshape(m, b, s, cfg.num_kv_heads, cfg.head_dim)
        v = L.linear(n, lp["wv"], lp.get("bv")).reshape(m, b, s, cfg.num_kv_heads, cfg.head_dim)
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
        o = L.flash_attention(q, k, v, positions, positions, window=window)
        h = L.linear(o.reshape(m, b, s, -1), lp["wo"], lp.get("bo"))
        xc = xc + h
        nn = L.rms_norm(xc, lp["mlp_norm"], cfg.norm_eps)
        xc = xc + L.swiglu_mlp(nn, lp["w_gate"], lp["w_up"], lp["w_down"])
        if cache_len >= s:
            pad = cache_len - s
            kc = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            vc = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        else:
            assert s % cache_len == 0, "prompt must be a multiple of the window"
            kc, vc = k[:, :, s - cache_len :], v[:, :, s - cache_len :]
        return xc, (kc.astype(jnp.dtype(cfg.dtype)), vc.astype(jnp.dtype(cfg.dtype)))

    x, (ck, cv) = lax.scan(body, x, params["layers"])
    logits = _logits(cfg, params, x[:, :, -1:])[:, :, 0]
    return logits, KVCache(k=ck, v=cv)


def init_chunk_carry(cfg: ModelConfig, m: int, b: int, cache_len: int):
    return {"cache": make_cache(cfg, m, b, cache_len)}


def chunk_carry_axes(cfg: ModelConfig):
    return {"cache": cache_axes(cfg)}


def prefill_chunk(cfg: ModelConfig, params, batch, carry, offset):
    """One chunk of a state-carrying prefill (serving admission).

    batch["tokens"]: (M,B,C) tokens at absolute positions
    offset..offset+C-1 (offset (M,B) int32, may differ per instance
    row).  The carry's KV cache holds every earlier position; the chunk
    attends over [cache-so-far, chunk] and appends its k/v at the ring
    slots, so any prompt length runs through the same compiled shape.
    batch["valid"] (M,B,C) bool, when present, marks the junk suffix of
    a padded final chunk (tail folding): invalid rows never reach the
    cache, and causality keeps them invisible to the real queries."""
    x = _embed_in(cfg, params, batch["tokens"])
    return _prefill_chunk_embeds(cfg, params, x, carry, offset,
                                 valid=batch.get("valid"))


def _prefill_chunk_embeds(cfg: ModelConfig, params, x, carry, offset, valid=None):
    """Chunk body on precomputed input embeddings (shared with vlm)."""
    from repro.models.common import active_rules, constrain_axes

    cache = carry["cache"]
    m, b, c, _ = x.shape
    positions = offset[..., None] + jnp.arange(c, dtype=jnp.int32)   # (M,B,C)
    window = cfg.sliding_window
    s_cache = cache.k.shape[3]
    # the cache as it stood BEFORE this chunk: ring slots labeled with
    # their absolute positions (-1 = not yet written); chunk keys ride
    # along with their own positions, so one positional mask covers
    # causality + sliding window + ring validity mid-prompt
    before = L.cache_positions_after(offset - 1, s_cache, 0)
    kv_pos = jnp.concatenate([before, positions], axis=-1)
    kv_ax = ("instances", "batch", "cache_seq", "kv_heads", "kv_hd")

    def body(xc, xs):
        lp, ck, cv = xs
        n = L.rms_norm(xc, lp["attn_norm"], cfg.norm_eps)
        q = L.linear(n, lp["wq"], lp.get("bq")).reshape(m, b, c, cfg.num_heads, cfg.head_dim)
        k = L.linear(n, lp["wk"], lp.get("bk")).reshape(m, b, c, cfg.num_kv_heads, cfg.head_dim)
        v = L.linear(n, lp["wv"], lp.get("bv")).reshape(m, b, c, cfg.num_kv_heads, cfg.head_dim)
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
        k_all = jnp.concatenate([ck, k.astype(ck.dtype)], axis=2)
        v_all = jnp.concatenate([cv, v.astype(cv.dtype)], axis=2)
        if cfg.use_pallas_kernels:
            # Pallas chunk-prefill flash attention: streams the cache S
            # axis through VMEM with online softmax, positions derived
            # in-kernel from the scalar-prefetched lane offsets
            from repro.kernels import ops as K
            o = K.chunk_prefill_attention(
                q, k_all, v_all, offset, s_cache=s_cache, window=window,
                rules=active_rules(),
            )
        else:
            o = L.flash_attention(q, k_all, v_all, positions, kv_pos, window=window)
        xc = xc + L.linear(o.reshape(m, b, c, -1), lp["wo"], lp.get("bo"))
        nn = L.rms_norm(xc, lp["mlp_norm"], cfg.norm_eps)
        xc = xc + L.swiglu_mlp(nn, lp["w_gate"], lp["w_up"], lp["w_down"])
        # pin the appended cache to its logical layout inside the scan
        # body — without the constraint GSPMD re-derives the kv sharding
        # per iteration and can fall back to full rematerialization
        nk = constrain_axes(L.cache_append_chunk(ck, k, positions, 0, valid), kv_ax)
        nv = constrain_axes(L.cache_append_chunk(cv, v, positions, 0, valid), kv_ax)
        return xc, (nk, nv)

    _, (nk, nv) = lax.scan(body, x, (params["layers"], cache.k, cache.v))
    return {"cache": KVCache(k=nk, v=nv)}


def _decode_layers_fused(cfg: ModelConfig, params, cache: KVCache, x, pos):
    """Megakernel decode body: one Pallas launch per layer
    (kernels/decode_layer.py) — norms, QKV+RoPE, in-kernel ring append,
    flash decode attention, out-proj, SwiGLU all fused over the (M, B)
    grid.  x: (M,B,D) residual; returns (x_out, updated cache)."""
    from repro.kernels import ops as K
    from repro.models.common import active_rules

    rules = active_rules()

    def body(xc, xs):
        lp, ck, cv = xs
        out, nk, nv = K.decode_layer(
            lp, xc, ck, cv, pos, num_heads=cfg.num_heads,
            head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
            window=cfg.sliding_window, eps=cfg.norm_eps, rules=rules,
        )
        return out, (nk, nv)

    x, (nk, nv) = lax.scan(body, x, (params["layers"], cache.k, cache.v))
    return x, KVCache(k=nk, v=nv)


def decode_step(cfg: ModelConfig, params, cache: KVCache, tokens, pos):
    """One decode step. tokens (M,B,1); pos (M,B) = index of this token.
    Returns (logits (M,B,V), updated cache)."""
    x = _embed_in(cfg, params, tokens)
    if cfg.use_pallas_kernels:
        x, new_cache = _decode_layers_fused(cfg, params, cache, x[:, :, 0], pos)
        logits = _logits(cfg, params, x[:, :, None])[:, :, 0]
        return logits, new_cache
    positions = pos[..., None]
    window = cfg.sliding_window
    # The layer loop CARRIES the stacked cache and writes each layer back
    # in place, rather than scanning it in and out (a second full cache).
    # It is carried with (KVH, hd) merged into one lane-dense dim: on a
    # TPU a head_dim-64 minor dim pads to 128 lanes, so the loop would
    # otherwise hold padded copies of the cache at twice its size.
    layer_shape = cache.k.shape[1:]
    flat = lambda a: a.reshape(a.shape[:-2] + (-1,))

    def body(carry, lp):
        xc, ks, vs, i = carry
        out, (nk, nv) = _attn_mlp(
            cfg, lp, xc, positions, window=window,
            cache=(ks[i].reshape(layer_shape), vs[i].reshape(layer_shape)),
            decode_pos=pos,
        )
        ks = lax.dynamic_update_index_in_dim(ks, flat(nk), i, 0)
        vs = lax.dynamic_update_index_in_dim(vs, flat(nv), i, 0)
        return (out, ks, vs, i + 1), None

    (x, nk, nv, _), _ = lax.scan(
        body, (x, flat(cache.k), flat(cache.v), 0), params["layers"])
    logits = _logits(cfg, params, x)[:, :, 0]
    return logits, KVCache(k=nk.reshape(cache.k.shape),
                           v=nv.reshape(cache.v.shape))


def decode_step_sample(cfg: ModelConfig, params, cache: KVCache, tokens, pos):
    """Greedy decode step: returns (next_token (M,B) int32, new cache).

    With ``cfg.use_pallas_kernels`` the final-norm + logits projection +
    argmax collapse into one fused kernel
    (kernels/decode_layer.py::logits_sample), so a steady-state decode
    scan step is ~num_layers + 1 launches; otherwise this is argmax over
    the plain decode_step logits (the two are token-identical)."""
    if cfg.use_pallas_kernels:
        from repro.kernels import ops as K
        from repro.models.common import active_rules

        x = _embed_in(cfg, params, tokens)[:, :, 0]
        x, new_cache = _decode_layers_fused(cfg, params, cache, x, pos)
        head = (
            jnp.swapaxes(params["embed"], -1, -2) if cfg.tie_embeddings
            else params["lm_head"]
        )
        tok = K.logits_sample(x, params["final_norm"], head,
                              eps=cfg.norm_eps, rules=active_rules())
        return tok, new_cache
    logits, new_cache = decode_step(cfg, params, cache, tokens, pos)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), new_cache


def make_cache(cfg: ModelConfig, m: int, b: int, context_len: int) -> KVCache:
    s_cache = cfg.sliding_window if cfg.sliding_window else context_len
    return L.make_kv_cache(
        cfg.num_layers, m, b, s_cache, cfg.num_kv_heads, cfg.head_dim,
        jnp.dtype(cfg.dtype),
    )


def cache_axes(cfg: ModelConfig):
    ax = ("layers", "instances", "batch", "cache_seq", "kv_heads", "kv_hd")
    return KVCache(k=ax, v=ax)
