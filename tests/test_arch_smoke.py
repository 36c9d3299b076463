"""Per-architecture smoke tests (assignment requirement): a REDUCED
variant of each assigned arch family runs one forward + one train step
on CPU; output shapes checked, no NaNs."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.configs.base import ShapeConfig
from repro.configs import registry

ARCHS = sorted(registry.ASSIGNED)

SMOKE_SHAPE = ShapeConfig("smoke", seq_len=32, global_batch=2, kind="train")
SMOKE_DECODE = ShapeConfig("smoke_decode", seq_len=64, global_batch=2, kind="decode")


def _real_batch(cfg, shape, key):
    specs = api.input_specs(cfg, shape)
    def mk(s):
        if s.dtype == jnp.int32:
            return jax.random.randint(key, s.shape, 0, max(cfg.vocab_size, 2)).astype(jnp.int32)
        return jax.random.normal(key, s.shape, s.dtype) * 0.3
    return jax.tree.map(mk, specs)


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = registry.get_smoke_config(arch)
    assert cfg.num_layers <= 3 and cfg.d_model <= 512
    if cfg.num_experts:
        assert cfg.num_experts <= 4
    params = api.init(cfg, jax.random.PRNGKey(0))
    specs = _real_batch(cfg, SMOKE_SHAPE, jax.random.PRNGKey(1))
    batch = specs["batch"]

    # forward
    out = api.train_logits(cfg, params, batch, remat=False)
    if cfg.family == "moe":
        out, aux = out
        assert np.isfinite(float(aux))
    assert out.shape[:2] == batch["labels"].shape[:2]
    assert out.shape[2] == batch["labels"].shape[2]
    assert out.shape[3] == cfg.vocab_size
    assert not bool(jnp.isnan(out).any())

    # one SGD train step via value_and_grad
    loss, metrics = api.loss_fn(cfg, params, batch)
    assert np.isfinite(float(loss))
    grads = jax.grad(lambda p: api.loss_fn(cfg, p, batch)[0])(params)
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree.leaves(grads)))
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0
    new_params = jax.tree.map(lambda p, g: p - 1e-3 * g.astype(p.dtype), params, grads)
    loss2, _ = api.loss_fn(cfg, new_params, batch)
    assert np.isfinite(float(loss2))


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_decode_step(arch):
    cfg = registry.get_smoke_config(arch)
    params = api.init(cfg, jax.random.PRNGKey(0))
    m = cfg.num_instances
    b = SMOKE_DECODE.global_batch // m
    cache = api.make_cache(cfg, m, b, SMOKE_DECODE.seq_len)
    tokens = jnp.zeros((m, b, 1), jnp.int32)
    pos = jnp.full((m, b), SMOKE_DECODE.seq_len // 2, jnp.int32)
    logits, new_cache = api.decode_step(cfg, params, cache, tokens, pos)
    assert logits.shape == (m, b, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any())
    # cache structure preserved
    assert jax.tree.structure(jax.tree.map(jnp.shape, cache)) == \
        jax.tree.structure(jax.tree.map(jnp.shape, new_cache))


def _layer_scan_decode_step(cfg, params, cache, tokens, pos):
    """The dense decode step in its plain form: the layer scan takes each
    layer's cache in and puts the appended one out."""
    from repro.models import dense

    x = dense._embed_in(cfg, params, tokens)

    def body(xc, xs):
        lp, ck, cv = xs
        return dense._attn_mlp(cfg, lp, xc, pos[..., None],
                               window=cfg.sliding_window, cache=(ck, cv),
                               decode_pos=pos)

    x, (nk, nv) = jax.lax.scan(body, x, (params["layers"], cache.k, cache.v))
    return dense._logits(cfg, params, x)[:, :, 0], dense.KVCache(k=nk, v=nv)


@pytest.mark.parametrize("window", [0, 8])
@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "qwen1.5-0.5b"])
def test_dense_decode_step_matches_layer_scan(arch, window):
    """dense.decode_step carries the stacked cache through the layer loop
    and updates it in place; logits and cache are bitwise those of the
    plain scan, on a filled cache and positions on both sides of a ring
    wrap."""
    cfg = registry.get_smoke_config(arch).with_(num_instances=2,
                                                sliding_window=window)
    params = api.init(cfg, jax.random.PRNGKey(0))
    cache = api.make_cache(cfg, 2, 3, 16)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    cache = type(cache)(
        k=jax.random.normal(ks[0], cache.k.shape, cache.k.dtype),
        v=jax.random.normal(ks[1], cache.v.shape, cache.v.dtype))
    tokens = jax.random.randint(ks[2], (2, 3, 1), 0, cfg.vocab_size)
    pos = jnp.array([[0, 7, 15], [16, 21, 40]], jnp.int32)
    got = jax.jit(functools.partial(api.decode_step, cfg))(
        params, cache, tokens, pos)
    want = jax.jit(functools.partial(_layer_scan_decode_step, cfg))(
        params, cache, tokens, pos)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "olmoe-1b-7b", "internvl2-26b"])
def test_smoke_sliding_window_variant(arch):
    """long_500k variant (full-attention families w/ window) still runs."""
    cfg = registry.get_smoke_config(arch).with_(sliding_window=8)
    params = api.init(cfg, jax.random.PRNGKey(0))
    batch = _real_batch(cfg, SMOKE_SHAPE, jax.random.PRNGKey(1))["batch"]
    out = api.train_logits(cfg, params, batch, remat=False)
    if cfg.family == "moe":
        out = out[0]
    assert not bool(jnp.isnan(out).any())


def test_full_configs_match_assignment():
    """The full (non-smoke) configs carry the exact published shapes."""
    c = registry.get_config("olmoe-1b-7b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.d_ff,
            c.vocab_size, c.num_experts, c.num_experts_per_tok) == \
        (16, 2048, 16, 16, 1024, 50304, 64, 8)
    c = registry.get_config("hymba-1.5b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.d_ff,
            c.vocab_size, c.ssm_state) == (32, 1600, 25, 5, 5504, 32001, 16)
    c = registry.get_config("xlstm-1.3b")
    assert (c.num_layers, c.d_model, c.num_heads, c.vocab_size) == (48, 2048, 4, 50304)
    c = registry.get_config("internvl2-26b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.d_ff,
            c.vocab_size) == (48, 6144, 48, 8, 16384, 92553)
    c = registry.get_config("tinyllama-1.1b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.d_ff,
            c.vocab_size) == (22, 2048, 32, 4, 5632, 32000)
    c = registry.get_config("deepseek-67b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.d_ff,
            c.vocab_size) == (95, 8192, 64, 8, 22016, 102400)
    c = registry.get_config("whisper-small")
    assert (c.num_layers, c.d_model, c.num_heads, c.d_ff, c.vocab_size) == \
        (12, 768, 12, 3072, 51865)
    c = registry.get_config("granite-3-2b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.d_ff,
            c.vocab_size) == (40, 2048, 32, 8, 8192, 49155)
    c = registry.get_config("qwen1.5-0.5b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.d_ff,
            c.vocab_size, c.qkv_bias) == (24, 1024, 16, 16, 2816, 151936, True)
    c = registry.get_config("qwen3-moe-30b-a3b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.d_ff,
            c.vocab_size, c.num_experts, c.num_experts_per_tok) == \
        (48, 2048, 32, 4, 768, 151936, 128, 8)


def test_shape_support_matrix():
    for arch in registry.ASSIGNED:
        assert registry.supported(arch, "train_4k")
        assert registry.supported(arch, "prefill_32k")
        assert registry.supported(arch, "decode_32k")
    assert not registry.supported("whisper-small", "long_500k")
    assert registry.supported("xlstm-1.3b", "long_500k")
    assert registry.supported("hymba-1.5b", "long_500k")
    # full-attention archs run long_500k via the sliding-window variant
    cfg = registry.config_for_shape("deepseek-67b", "long_500k")
    assert cfg.sliding_window == registry.LONG_CONTEXT_WINDOW
