"""Uniform model API: family dispatch + input specs for every
(architecture × input shape) combination.

Entry points used by the launcher, tests and benchmarks:

  init / abstract_params / axes
  train_logits(cfg, params, batch)   -> (logits, aux) aligned with labels
  prefill(cfg, params, batch)        -> (last logits, cache)
  decode_step(cfg, params, cache, tokens, pos) -> (logits, cache)
  make_cache / abstract_cache / cache_axes
  input_specs(cfg, shape)            -> batch of ShapeDtypeStructs

Batch layout per family (see DESIGN.md §5):
  dense/moe/ssm/hybrid: {tokens (M,B,S), labels (M,B,S)}
  vlm:   {tokens (M,B,S-P), image_embeds (M,B,P,Dv), labels (M,B,S-P)}
  audio: {tokens (M,B,S), frames (M,B,F,D), labels (M,B,S)}
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import audio, common, dense, hybrid, moe, ssm, vlm
from repro.models import layers as L

_FAMILY = {
    "dense": dense, "moe": moe, "ssm": ssm, "hybrid": hybrid,
    "vlm": vlm, "audio": audio,
}


def family_module(cfg: ModelConfig):
    return _FAMILY[cfg.family]


def init(cfg, key):
    return family_module(cfg).init(cfg, key)


def init_instance(cfg, key, i):
    """Instance ``i`` of a merged grid, alone: ``init`` of the
    one-instance config under ``fold_in(key, i)`` — the same model
    whatever the grid's M is.  One compiled program serves every i."""
    return _init_one(cfg.with_(num_instances=1), key, i)


@functools.partial(jax.jit, static_argnums=0)
def _init_one(cfg, key, i):
    return init(cfg, jax.random.fold_in(key, i))


def init_instances(cfg, key, *, mesh=None):
    """The merged grid of ``cfg.num_instances`` instances, instance i
    being :func:`init_instance` (drawn at f32, held at
    ``cfg.param_dtype``).  Each instance is drawn, merged into the grid
    and released in turn (``common.merge_instances``), so no second full
    copy of the weights ever exists.  With a ``mesh`` the grid is built
    in place in the serving layout (``serve_rules``), never whole on one
    device."""
    shardings = None
    if mesh is not None:
        from repro.launch.shardings import serve_rules, tree_shardings
        shardings = tree_shardings(serve_rules(mesh), axes(cfg),
                                   abstract_params(cfg))
    master = cfg.with_(param_dtype="float32")
    return common.merge_instances(
        (init_instance(master, key, i) for i in range(cfg.num_instances)),
        axes(cfg), like=abstract_params(cfg), shardings=shardings)


def abstract_params(cfg):
    return family_module(cfg).abstract_params(cfg)


def axes(cfg):
    return family_module(cfg).axes(cfg)


# ---------------------------------------------------------------------------
# forward entry points
# ---------------------------------------------------------------------------


def train_logits(cfg: ModelConfig, params, batch, *, remat: bool | None = None):
    """Logits aligned with batch['labels'] (next-token labels)."""
    remat = cfg.remat if remat is None else remat
    fam = cfg.family
    if fam in ("dense",):
        return dense.forward(cfg, params, batch["tokens"], remat=remat)
    if fam == "moe":
        logits, aux = moe.forward(cfg, params, batch["tokens"], remat=remat, return_aux=True)
        return logits, aux
    if fam == "ssm":
        return ssm.forward(cfg, params, batch["tokens"], remat=remat)
    if fam == "hybrid":
        return hybrid.forward(cfg, params, batch["tokens"], remat=remat)
    if fam == "vlm":
        return vlm.text_logits(cfg, params, batch["tokens"], batch["image_embeds"], remat=remat)
    if fam == "audio":
        return audio.forward(cfg, params, batch["tokens"], batch["frames"], remat=remat)
    raise ValueError(fam)


def prefill(cfg: ModelConfig, params, batch, *, cache_len: int | None = None):
    fam = cfg.family
    if fam == "dense":
        return dense.prefill(cfg, params, batch["tokens"], cache_len=cache_len)
    if fam == "moe":
        return moe.prefill(cfg, params, batch["tokens"], cache_len=cache_len)
    if fam == "ssm":
        return ssm.prefill(cfg, params, batch["tokens"])
    if fam == "hybrid":
        return hybrid.prefill(cfg, params, batch["tokens"])
    if fam == "vlm":
        return vlm.prefill(cfg, params, batch["tokens"], batch["image_embeds"], cache_len=cache_len)
    if fam == "audio":
        return audio.prefill(cfg, params, batch["tokens"], batch["frames"], cache_len=cache_len)
    raise ValueError(fam)


def decode_step(cfg: ModelConfig, params, cache, tokens, pos):
    return family_module(cfg).decode_step(cfg, params, cache, tokens, pos)


def decode_step_sample(cfg: ModelConfig, params, cache, tokens, pos):
    """Greedy decode step: (next_token (M,B) int32, new cache).

    Families with a fused decode+sample path (dense/vlm megakernel:
    final-norm + logits + argmax in one Pallas call) provide their own;
    everything else is argmax over decode_step logits — token-identical
    to the engine's temperature<=0 sampler either way."""
    mod = family_module(cfg)
    if hasattr(mod, "decode_step_sample"):
        return mod.decode_step_sample(cfg, params, cache, tokens, pos)
    logits, new_cache = mod.decode_step(cfg, params, cache, tokens, pos)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), new_cache


# ---------------------------------------------------------------------------
# chunked prefill (chainable cache-carry protocol — DESIGN.md §6.2)
# ---------------------------------------------------------------------------


def prefill_prefix_len(cfg: ModelConfig) -> int:
    """Learned-prefix positions that precede the prompt tokens in the
    prefill position stream (hybrid meta tokens, vlm image patches)."""
    if cfg.family == "hybrid":
        return hybrid.NUM_META_TOKENS
    if cfg.family == "vlm":
        return cfg.num_image_patches
    return 0


def init_chunk_carry(cfg: ModelConfig, m: int, b: int, cache_len: int):
    """Fresh chunk-prefill carry: {"cache": <the family's decode
    cache/state tree>} plus family extras (moe adds per-layer expert
    counts).  The cache leaf shapes match ``make_cache`` at the same
    ``cache_len``, so the serving slot scatter consumes carries
    unchanged."""
    return family_module(cfg).init_chunk_carry(cfg, m, b, cache_len)


def chunk_carry_axes(cfg: ModelConfig):
    """Logical-axes tree matching :func:`init_chunk_carry`'s structure."""
    return family_module(cfg).chunk_carry_axes(cfg)


def prefill_chunk(cfg: ModelConfig, params, batch, carry, offset):
    """Process one prompt chunk, threading the carry.

    batch["tokens"] is (M,B,C) at absolute positions offset..offset+C-1
    (offset: (M,B) int32; positions below ``prefill_prefix_len`` take
    the family's prefix embeddings and ignore the token ids).  vlm/audio
    additionally read batch["image_embeds"]/batch["frames"]; moe reads
    batch["moe_limit"]; batch["valid"] (M,B,C) bool marks the junk
    suffix of a padded final chunk (tail folding — the junk never
    reaches caches, routing or recurrent state).  Returns the advanced
    carry — every family, any prompt length, ONE compiled shape."""
    return family_module(cfg).prefill_chunk(cfg, params, batch, carry, offset)


def make_cache(cfg: ModelConfig, m: int, b: int, context_len: int):
    fam = cfg.family
    if fam in ("dense", "vlm"):
        return dense.make_cache(cfg, m, b, context_len)
    if fam == "moe":
        return moe.make_cache(cfg, m, b, context_len)
    if fam == "ssm":
        return ssm.make_state(cfg, m, b)
    if fam == "hybrid":
        return hybrid.make_cache(cfg, m, b, context_len)
    if fam == "audio":
        return audio.make_cache(cfg, m, b, context_len)
    raise ValueError(fam)


def abstract_cache(cfg, m, b, context_len):
    return jax.eval_shape(lambda: make_cache(cfg, m, b, context_len))


def take_state(cfg: ModelConfig, cache, m, b):
    """Slot surgery: slice slot (m, b) out of an (M, B)-grid cache/state
    tree (singleton dims kept).  Works for every family — KV-cache stacks
    and recurrent-state layouts alike; ssm/hybrid provide their own
    helpers, the rest go through the generic axes-driven path."""
    fam = family_module(cfg)
    if hasattr(fam, "take_state"):
        return fam.take_state(cfg, cache, m, b)
    from repro.models.common import tree_take_slot
    return tree_take_slot(cache, cache_axes(cfg), m, b)


def put_state(cfg: ModelConfig, grid, one, m, b):
    """Slot surgery: write a single-slot cache/state tree into grid slot
    (m, b).  Inverse of :func:`take_state`."""
    fam = family_module(cfg)
    if hasattr(fam, "put_state"):
        return fam.put_state(cfg, grid, one, m, b)
    from repro.models.common import tree_put_slot
    return tree_put_slot(grid, cache_axes(cfg), one, m, b)


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStructs — nothing allocated)
# ---------------------------------------------------------------------------


def _tok(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """Abstract inputs for jit(...).lower(**input_specs).

    Returns {"batch": ...} for train/prefill; decode shapes return
    {"cache": ..., "tokens": ..., "pos": ...}."""
    m = cfg.num_instances
    assert shape.global_batch % m == 0, (shape.global_batch, m)
    b = shape.global_batch // m
    s = shape.seq_len
    dt = jnp.dtype(cfg.dtype)

    if shape.kind in ("train", "prefill"):
        if cfg.family == "vlm":
            p = cfg.num_image_patches
            batch = {
                "tokens": _tok(m, b, s - p),
                "image_embeds": jax.ShapeDtypeStruct((m, b, p, cfg.vision_embed_dim), dt),
            }
            if shape.kind == "train":
                batch["labels"] = _tok(m, b, s - p)
        elif cfg.family == "audio":
            batch = {
                "tokens": _tok(m, b, s),
                "frames": jax.ShapeDtypeStruct((m, b, cfg.num_audio_frames, cfg.d_model), dt),
            }
            if shape.kind == "train":
                batch["labels"] = _tok(m, b, s)
        else:
            batch = {"tokens": _tok(m, b, s)}
            if shape.kind == "train":
                batch["labels"] = _tok(m, b, s)
        return {"batch": batch}

    # decode: one new token against a seq_len-deep cache
    return {
        "cache": abstract_cache(cfg, m, b, s),
        "tokens": _tok(m, b, 1),
        "pos": _tok(m, b),
    }


# ---------------------------------------------------------------------------
# loss (used by train_step and smoke tests)
# ---------------------------------------------------------------------------


def loss_fn(cfg: ModelConfig, params, batch):
    """Mean next-token cross entropy (+ MoE aux)."""
    from repro.models.common import constrain

    out = train_logits(cfg, params, batch)
    aux = jnp.float32(0.0)
    if cfg.family == "moe":
        out, aux = out
    logits = out.astype(jnp.float32)
    # loss region: batch over data, vocab over model (the (tokens, V)
    # logits tensor is the largest activation in training — see DESIGN.md)
    logits = constrain(logits, "instances", "batch", None, "vocab")
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    loss = nll.mean()
    return loss + cfg.router_aux_loss * aux, {"nll": loss, "aux": aux}


def cache_axes(cfg: ModelConfig):
    """Logical-axis tree matching abstract_cache's structure."""
    fam = cfg.family
    if fam in ("dense", "vlm"):
        return dense.cache_axes(cfg)
    if fam == "moe":
        return moe.cache_axes(cfg)
    if fam == "ssm":
        return ssm.state_axes(cfg)
    if fam == "hybrid":
        return hybrid.cache_axes(cfg)
    if fam == "audio":
        return audio.cache_axes(cfg)
    raise ValueError(fam)
