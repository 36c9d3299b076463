"""Whisper-style encoder-decoder — whisper-small [arXiv:2212.04356].

The mel-spectrogram + conv feature extractor is a STUB per the
assignment spec: ``input_specs`` provides post-conv frame embeddings
(M, B, F, D).  Implemented here: sinusoidal encoder positions, the
bidirectional encoder stack, and the causal decoder with self- +
cross-attention (pre-LN, GELU MLPs, learned decoder positions — extended
beyond 448 to cover the assigned train_4k shape; noted in DESIGN.md).

Decode caches: ring-buffer self-attention KV (as dense) plus per-layer
cross-attention K/V computed once from the encoder output at prefill.
long_500k is SKIPPED for this arch (encoder-decoder with fixed encoder
horizon — see DESIGN.md §4).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.common import (
    Factory, make_factory, param_axes, param_values, stack_layer_params,
)
from repro.models.layers import KVCache


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def _attn_params(cfg, f, prefix, kv_dim=None):
    m, d, h, hd = cfg.num_instances, cfg.d_model, cfg.num_heads, cfg.head_dim
    kvh = cfg.num_kv_heads
    return {
        f"{prefix}wq": f((m, d, h * hd), ("instances", "embed", "heads_flat"), init="fan_in"),
        f"{prefix}wk": f((m, d, kvh * hd), ("instances", "embed", "kv_flat"), init="fan_in"),
        f"{prefix}wv": f((m, d, kvh * hd), ("instances", "embed", "kv_flat"), init="fan_in"),
        f"{prefix}wo": f((m, h * hd, d), ("instances", "heads_flat", "embed"), init="fan_in"),
        f"{prefix}bq": f((m, h * hd), ("instances", "heads_flat"), init="zeros"),
        f"{prefix}bv": f((m, kvh * hd), ("instances", "kv_flat"), init="zeros"),
        f"{prefix}bo": f((m, d), ("instances", "embed"), init="zeros"),
    }


def _enc_layer(cfg, f):
    m, d, ff = cfg.num_instances, cfg.d_model, cfg.d_ff
    p = {
        "ln1_s": f((m, d), ("instances", None), init="ones"),
        "ln1_b": f((m, d), ("instances", None), init="zeros"),
        "ln2_s": f((m, d), ("instances", None), init="ones"),
        "ln2_b": f((m, d), ("instances", None), init="zeros"),
        "w1": f((m, d, ff), ("instances", "embed", "mlp"), init="fan_in"),
        "b1": f((m, ff), ("instances", "mlp"), init="zeros"),
        "w2": f((m, ff, d), ("instances", "mlp", "embed"), init="fan_in"),
        "b2": f((m, d), ("instances", "embed"), init="zeros"),
    }
    p.update(_attn_params(cfg, f, ""))
    return p


def _dec_layer(cfg, f):
    m, d, ff = cfg.num_instances, cfg.d_model, cfg.d_ff
    p = {
        "ln1_s": f((m, d), ("instances", None), init="ones"),
        "ln1_b": f((m, d), ("instances", None), init="zeros"),
        "ln_x_s": f((m, d), ("instances", None), init="ones"),
        "ln_x_b": f((m, d), ("instances", None), init="zeros"),
        "ln2_s": f((m, d), ("instances", None), init="ones"),
        "ln2_b": f((m, d), ("instances", None), init="zeros"),
        "w1": f((m, d, ff), ("instances", "embed", "mlp"), init="fan_in"),
        "b1": f((m, ff), ("instances", "mlp"), init="zeros"),
        "w2": f((m, ff, d), ("instances", "mlp", "embed"), init="fan_in"),
        "b2": f((m, d), ("instances", "embed"), init="zeros"),
    }
    p.update(_attn_params(cfg, f, ""))       # self-attention
    p.update(_attn_params(cfg, f, "x_"))     # cross-attention
    return p


def build_params(cfg: ModelConfig, f: Factory):
    m, d, v = cfg.num_instances, cfg.d_model, cfg.vocab_size
    enc_l = cfg.encoder_layers or cfg.num_layers
    max_pos = cfg.max_target_positions or 4608
    return {
        "embed": f((m, v, d), ("instances", "vocab", "embed")),
        "pos_embed": f((m, max_pos, d), ("instances", None, "embed")),
        "enc_layers": stack_layer_params([_enc_layer(cfg, f) for _ in range(enc_l)]),
        "enc_ln_s": f((m, d), ("instances", None), init="ones"),
        "enc_ln_b": f((m, d), ("instances", None), init="zeros"),
        "dec_layers": stack_layer_params([_dec_layer(cfg, f) for _ in range(cfg.num_layers)]),
        "final_ln_s": f((m, d), ("instances", None), init="ones"),
        "final_ln_b": f((m, d), ("instances", None), init="zeros"),
    }


def init(cfg, key):
    return param_values(build_params(cfg, make_factory(cfg, key)))


def abstract_params(cfg):
    return param_values(build_params(cfg, make_factory(cfg, abstract=True)))


def axes(cfg):
    return param_axes(build_params(cfg, make_factory(cfg, abstract=True)))


# ---------------------------------------------------------------------------
# encoder
# ---------------------------------------------------------------------------


def _sinusoid(n: int, d: int) -> np.ndarray:
    pos = np.arange(n)[:, None]
    dim = np.arange(d // 2)[None, :]
    inv = np.exp(-np.log(10000.0) * dim / max(d // 2 - 1, 1))
    ang = pos * inv
    return np.concatenate([np.sin(ang), np.cos(ang)], axis=-1).astype(np.float32)


def _mha(cfg, lp, x, kv_x, *, prefix="", causal, positions=None, q_pos=None,
         cache=None, decode_pos=None, live=None):
    """Whisper MHA (no RoPE, learned/sinusoidal positions added outside)."""
    m, b, s, d = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = L.linear(x, lp[f"{prefix}wq"], lp.get(f"{prefix}bq")).reshape(m, b, s, h, hd)
    if kv_x is not None:
        skv = kv_x.shape[2]
        k = L.linear(kv_x, lp[f"{prefix}wk"]).reshape(m, b, skv, kvh, hd)
        v = L.linear(kv_x, lp[f"{prefix}wv"], lp.get(f"{prefix}bv")).reshape(m, b, skv, kvh, hd)
    else:
        k = v = None
    if cache is not None:
        ck, cv = L.cache_update_one(cache[0], cache[1], k, v, decode_pos,
                                    live=live)
        kv_pos = L.cache_slot_positions(decode_pos, ck.shape[2])
        o = L.flash_attention(q, ck, cv, decode_pos[..., None], kv_pos, causal=True)
        new_cache = (ck, cv)
    else:
        skv = k.shape[2]
        kv_pos = jnp.broadcast_to(jnp.arange(skv, dtype=jnp.int32), (m, b, skv))
        qp = q_pos if q_pos is not None else (
            positions if positions is not None
            else jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (m, b, s))
        )
        o = L.flash_attention(q, k, v, qp, kv_pos, causal=causal)
        new_cache = None
    out = L.linear(o.reshape(m, b, s, h * hd), lp[f"{prefix}wo"], lp.get(f"{prefix}bo"))
    return out, new_cache, (k, v)


def encode(cfg, params, frame_embeds):
    """frame_embeds: (M,B,F,D) stub conv features -> encoder states."""
    m, b, fr, d = frame_embeds.shape
    x = frame_embeds.astype(jnp.dtype(cfg.dtype))
    x = x + jnp.asarray(_sinusoid(fr, d), x.dtype)

    def body(xc, lp):
        n = L.layer_norm(xc, lp["ln1_s"], lp["ln1_b"], cfg.norm_eps)
        a, _, _ = _mha(cfg, lp, n, n, causal=False)
        xc = xc + a
        n = L.layer_norm(xc, lp["ln2_s"], lp["ln2_b"], cfg.norm_eps)
        xc = xc + L.gelu_mlp(n, lp["w1"], lp["b1"], lp["w2"], lp["b2"])
        return xc, None

    x, _ = lax.scan(body, x, params["enc_layers"])
    return L.layer_norm(x, params["enc_ln_s"], params["enc_ln_b"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# decoder
# ---------------------------------------------------------------------------


def _dec_embed(cfg, params, tokens, start: int = 0):
    x = L.embed(tokens, params["embed"], jnp.dtype(cfg.dtype))
    s = tokens.shape[2]
    pe = lax.dynamic_slice_in_dim(params["pos_embed"], start, s, axis=1)
    return x + pe[:, None].astype(x.dtype)


def decode_full(cfg, params, tokens, enc_out, *, remat: bool = False):
    """Teacher-forced decoder pass (training). Returns (M,B,S,V) logits."""
    x = _dec_embed(cfg, params, tokens)
    m, b, s, d = x.shape

    def body(xc, lp):
        n = L.layer_norm(xc, lp["ln1_s"], lp["ln1_b"], cfg.norm_eps)
        a, _, _ = _mha(cfg, lp, n, n, causal=True)
        xc = xc + a
        n = L.layer_norm(xc, lp["ln_x_s"], lp["ln_x_b"], cfg.norm_eps)
        a, _, _ = _mha(cfg, lp, n, enc_out, prefix="x_", causal=False)
        xc = xc + a
        n = L.layer_norm(xc, lp["ln2_s"], lp["ln2_b"], cfg.norm_eps)
        xc = xc + L.gelu_mlp(n, lp["w1"], lp["b1"], lp["w2"], lp["b2"])
        return xc, None

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = lax.scan(body, x, params["dec_layers"])
    x = L.layer_norm(x, params["final_ln_s"], params["final_ln_b"], cfg.norm_eps)
    return L.unembed(x, jnp.swapaxes(params["embed"], -1, -2))


def forward(cfg, params, tokens, frame_embeds, *, remat: bool = False):
    return decode_full(cfg, params, tokens, encode(cfg, params, frame_embeds), remat=remat)


def prefill(cfg, params, tokens, frame_embeds, *, cache_len: int | None = None):
    """Encode audio + run the decoder prompt; returns (last logits, cache).
    cache = {"self": KVCache, "cross_k": (L,M,B,F,KVH,hd), "cross_v": ...}"""
    enc_out = encode(cfg, params, frame_embeds)
    x = _dec_embed(cfg, params, tokens)
    m, b, s, d = x.shape
    cache_len = cache_len or s
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (m, b, s))

    def body(xc, lp):
        n = L.layer_norm(xc, lp["ln1_s"], lp["ln1_b"], cfg.norm_eps)
        a, _, (k, v) = _mha(cfg, lp, n, n, causal=True, positions=positions)
        xc = xc + a
        n = L.layer_norm(xc, lp["ln_x_s"], lp["ln_x_b"], cfg.norm_eps)
        a, _, (xk, xv) = _mha(cfg, lp, n, enc_out, prefix="x_", causal=False)
        xc = xc + a
        n = L.layer_norm(xc, lp["ln2_s"], lp["ln2_b"], cfg.norm_eps)
        xc = xc + L.gelu_mlp(n, lp["w1"], lp["b1"], lp["w2"], lp["b2"])
        pad = cache_len - s
        kc = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.dtype(cfg.dtype)
        return xc, (kc.astype(dt), vc.astype(dt), xk.astype(dt), xv.astype(dt))

    x, (ck, cv, xk, xv) = lax.scan(body, x, params["dec_layers"])
    x = L.layer_norm(x[:, :, -1:], params["final_ln_s"], params["final_ln_b"], cfg.norm_eps)
    logits = L.unembed(x, jnp.swapaxes(params["embed"], -1, -2))[:, :, 0]
    return logits, {"self": KVCache(k=ck, v=cv), "cross_k": xk, "cross_v": xv}


def decode_step(cfg, params, cache, tokens, pos, *, live=None):
    """One decoder token; cross-attention reads precomputed encoder KV.
    A lane whose ``live`` is False keeps its self-attention cache."""
    m, b, _ = tokens.shape
    x = L.embed(tokens, params["embed"], jnp.dtype(cfg.dtype))
    # learned position embedding at each request's position (pos may vary
    # per (m, b); gather per instance-batch element)
    flat_pos = pos.reshape(m * b).astype(jnp.int32)
    tables = jnp.repeat(params["pos_embed"], b, axis=0)        # (M*B, P, D)
    pe = jax.vmap(lambda t, i: lax.dynamic_slice_in_dim(t, i, 1, axis=0))(
        tables, flat_pos
    ).reshape(m, b, 1, -1)
    x = x + pe.astype(x.dtype)

    def body(xc, xs):
        lp, ck, cv, xk, xv = xs
        n = L.layer_norm(xc, lp["ln1_s"], lp["ln1_b"], cfg.norm_eps)
        a, new_cache, _ = _mha(cfg, lp, n, n, causal=True, cache=(ck, cv),
                               decode_pos=pos, live=live)
        xc = xc + a
        n = L.layer_norm(xc, lp["ln_x_s"], lp["ln_x_b"], cfg.norm_eps)
        # cross attention against cached encoder K/V
        h_, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = L.linear(n, lp["x_wq"], lp.get("x_bq")).reshape(m, b, 1, h_, hd)
        fr = xk.shape[2]
        kv_pos = jnp.broadcast_to(jnp.arange(fr, dtype=jnp.int32), (m, b, fr))
        o = L.flash_attention(q, xk, xv, pos[..., None] * 0 + fr, kv_pos, causal=False)
        a = L.linear(o.reshape(m, b, 1, h_ * hd), lp["x_wo"], lp.get("x_bo"))
        xc = xc + a
        n = L.layer_norm(xc, lp["ln2_s"], lp["ln2_b"], cfg.norm_eps)
        xc = xc + L.gelu_mlp(n, lp["w1"], lp["b1"], lp["w2"], lp["b2"])
        return xc, new_cache

    x, (nk, nv) = lax.scan(
        body, x, (params["dec_layers"], cache["self"].k, cache["self"].v,
                  cache["cross_k"], cache["cross_v"])
    )
    x = L.layer_norm(x, params["final_ln_s"], params["final_ln_b"], cfg.norm_eps)
    logits = L.unembed(x, jnp.swapaxes(params["embed"], -1, -2))[:, :, 0]
    return logits, {"self": KVCache(k=nk, v=nv), "cross_k": cache["cross_k"],
                    "cross_v": cache["cross_v"]}


def init_chunk_carry(cfg, m, b, cache_len):
    return {"cache": make_cache(cfg, m, b, cache_len)}


def chunk_carry_axes(cfg):
    return {"cache": cache_axes(cfg)}


def prefill_chunk(cfg, params, batch, carry, offset):
    """One decoder chunk of a state-carrying prefill.

    The encoder runs on batch["frames"] every chunk and the (identical)
    cross-attention K/V are rewritten into the carry — recomputation
    keeps the runtime at exactly two compiled shapes (chunk + tail)
    instead of adding a third init-time shape; frames are short relative
    to decode work, and serving feeds stub (zero) frames anyway."""
    from repro.models.common import constrain_axes

    tokens, frames = batch["tokens"], batch["frames"]
    cache = carry["cache"]
    valid = batch.get("valid")            # (M,B,C) tail-folding junk mask
    m, b, c = tokens.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    enc_out = encode(cfg, params, frames)
    fr = enc_out.shape[2]
    positions = offset[..., None] + jnp.arange(c, dtype=jnp.int32)   # (M,B,C)
    x = L.embed(tokens, params["embed"], dt)
    # learned positions gathered at each lane's absolute offsets
    pidx = jnp.clip(positions, 0, params["pos_embed"].shape[1] - 1)
    pe = jax.vmap(lambda t, i: jnp.take(t, i, axis=0))(
        params["pos_embed"], pidx.reshape(m, b * c)
    ).reshape(m, b, c, -1)
    x = x + pe.astype(x.dtype)
    s_cache = cache["self"].k.shape[3]
    before = L.cache_positions_after(offset - 1, s_cache, 0)
    kv_pos_self = jnp.concatenate([before, positions], axis=-1)
    kv_pos_x = jnp.broadcast_to(jnp.arange(fr, dtype=jnp.int32), (m, b, fr))
    kv_ax = ("instances", "batch", "cache_seq", "kv_heads", "kv_hd")

    def body(xc, xs):
        lp, ck, cv = xs
        n = L.layer_norm(xc, lp["ln1_s"], lp["ln1_b"], cfg.norm_eps)
        q = L.linear(n, lp["wq"], lp.get("bq")).reshape(m, b, c, h, hd)
        k = L.linear(n, lp["wk"]).reshape(m, b, c, kvh, hd)
        v = L.linear(n, lp["wv"], lp.get("bv")).reshape(m, b, c, kvh, hd)
        o = L.flash_attention(
            q,
            jnp.concatenate([ck, k.astype(ck.dtype)], axis=2),
            jnp.concatenate([cv, v.astype(cv.dtype)], axis=2),
            positions, kv_pos_self, causal=True,
        )
        xc = xc + L.linear(o.reshape(m, b, c, h * hd), lp["wo"], lp.get("bo"))
        n = L.layer_norm(xc, lp["ln_x_s"], lp["ln_x_b"], cfg.norm_eps)
        xq = L.linear(n, lp["x_wq"], lp.get("x_bq")).reshape(m, b, c, h, hd)
        xk = L.linear(enc_out, lp["x_wk"]).reshape(m, b, fr, kvh, hd)
        xv = L.linear(enc_out, lp["x_wv"], lp.get("x_bv")).reshape(m, b, fr, kvh, hd)
        o = L.flash_attention(xq, xk, xv, positions, kv_pos_x, causal=False)
        xc = xc + L.linear(o.reshape(m, b, c, h * hd), lp["x_wo"], lp.get("x_bo"))
        n = L.layer_norm(xc, lp["ln2_s"], lp["ln2_b"], cfg.norm_eps)
        xc = xc + L.gelu_mlp(n, lp["w1"], lp["b1"], lp["w2"], lp["b2"])
        nk = constrain_axes(L.cache_append_chunk(ck, k, positions, 0, valid), kv_ax)
        nv = constrain_axes(L.cache_append_chunk(cv, v, positions, 0, valid), kv_ax)
        return xc, (nk, nv, xk.astype(dt), xv.astype(dt))

    _, (nk, nv, xks, xvs) = lax.scan(body, x, (params["dec_layers"], cache["self"].k, cache["self"].v))
    return {"cache": {"self": KVCache(k=nk, v=nv), "cross_k": xks, "cross_v": xvs}}


def make_cache(cfg, m, b, context_len, num_frames=None):
    fr = num_frames or cfg.num_audio_frames
    dt = jnp.dtype(cfg.dtype)
    kvh, hd, l = cfg.num_kv_heads, cfg.head_dim, cfg.num_layers
    return {
        "self": L.make_kv_cache(l, m, b, context_len, kvh, hd, dt),
        "cross_k": jnp.zeros((l, m, b, fr, kvh, hd), dt),
        "cross_v": jnp.zeros((l, m, b, fr, kvh, hd), dt),
    }


def cache_axes(cfg):
    ax = ("layers", "instances", "batch", "cache_seq", "kv_heads", "kv_hd")
    return {
        "self": KVCache(k=ax, v=ax),
        "cross_k": ax,
        "cross_v": ax,
    }
