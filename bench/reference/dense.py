"""Plain reference of a llama-family decoder (Qwen1.5, Granite 3.0 dense).

One instance at a time, the whole sequence at once: no cache, no batching,
no kernels.  RMSNorm, rotary embedding (rotate-half, inv_freq =
theta^(-2i/head_dim)), grouped-query attention with query head h reading kv
head h // (heads / kv_heads), scaled by head_dim^-1/2, SwiGLU MLP, as the
published model cards describe them.  Departures of the served model from
its source are stated in the configuration file (``reduced``), and the
reference follows the configuration as it is run.

``mode="reference"``: float32 weights and activations, every matmul at
``Precision.HIGHEST``.  ``mode="control"``: the nearest lower precision than
the served bfloat16, the step a later change might be tempted by: weights
rounded to float8 e4m3 with one scale per output channel, activations and
matmuls in bfloat16 (float32 accumulation, norms and softmax).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bench.model import Dense

F8_MAX = 448.0      # largest finite float8 e4m3fn


def instance(grid, m):
    """Instance ``m`` of the grid: layer leaves (L, M, ...), the rest (M, ...)."""
    layers = jax.tree.map(lambda a: jnp.take(a, m, axis=1), grid["layers"])
    rest = {k: jnp.take(v, m, axis=0) for k, v in grid.items() if k != "layers"}
    return {**rest, "layers": layers}


def _fp8(w, axis):
    """``w`` rounded to float8 e4m3, one scale per slice along ``axis``."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return ((w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
            * scale).astype(jnp.bfloat16)


def _rms(x, w, eps, dt):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(dt)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None] * inv                # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def logits(d: Dense, w, tokens, mode: str):
    """(S, V) float32 logits of one instance over one sequence."""
    low = mode == "control"
    if mode not in ("reference", "control"):
        raise ValueError(mode)
    dt = jnp.bfloat16 if low else jnp.float32
    prec = None if low else lax.Precision.HIGHEST

    def weight(a):
        # projection matrices are (in, out): one scale per output channel
        return _fp8(a, axis=-2) if low else a.astype(jnp.float32)

    def mm(x, a):
        return jnp.matmul(x, weight(a), precision=prec,
                          preferred_element_type=jnp.float32).astype(dt)

    s = tokens.shape[0]
    h, kvh, hd = d.heads, d.kv_heads, d.head_dim
    pos = jnp.arange(s)
    causal = pos[:, None] >= pos[None, :]
    table = w["embed"]
    table = _fp8(table, axis=-1) if low else table.astype(jnp.float32)
    x = table[tokens].astype(dt)

    def layer(x, lw):
        n = _rms(x, lw["attn_norm"], d.norm_eps, dt)
        q, k, v = mm(n, lw["wq"]), mm(n, lw["wk"]), mm(n, lw["wv"])
        if d.qkv_bias:
            q = q + lw["bq"].astype(dt)
            k = k + lw["bk"].astype(dt)
            v = v + lw["bv"].astype(dt)
        q = _rope(q.reshape(s, h, hd), pos, d.rope_theta)
        k = _rope(k.reshape(s, kvh, hd), pos, d.rope_theta)
        v = v.reshape(s, kvh, hd)
        k = jnp.repeat(k, h // kvh, axis=1)
        v = jnp.repeat(v, h // kvh, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=prec,
                        preferred_element_type=jnp.float32) / jnp.sqrt(
                            jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p.astype(dt), v, precision=prec,
                       preferred_element_type=jnp.float32).astype(dt)
        x = x + mm(o.reshape(s, h * hd), lw["wo"])
        n = _rms(x, lw["mlp_norm"], d.norm_eps, dt)
        x = x + mm(jax.nn.silu(mm(n, lw["w_gate"])) * mm(n, lw["w_up"]),
                   lw["w_down"])
        return x, None

    x, _ = lax.scan(layer, x, w["layers"])
    x = _rms(x, w["final_norm"], d.norm_eps, dt)
    head = w["embed"].T if d.tied else w["lm_head"]
    return jnp.matmul(x, weight(head), precision=prec,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1))
def gaps(d: Dense, control: bool, grid, m, tokens, targets, mask):
    """Over the masked positions: the widest gap by which the target token's
    reference logit lies below the reference's best, and the same for the
    token the control puts first (when ``control``; else NaN)."""
    w = instance(grid, m)
    ref = logits(d, w, tokens, "reference")
    best = ref.max(axis=-1)

    def widest(tok):
        g = best - jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]
        return jnp.max(jnp.where(mask, g, -jnp.inf))

    served = widest(targets)
    if not control:
        return served, jnp.float32(jnp.nan)
    low = logits(d, w, tokens, "control")
    return served, widest(jnp.argmax(low, axis=-1).astype(targets.dtype))
