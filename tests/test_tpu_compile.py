"""The serving kernels compile for a TPU v5e at qwen1.5-0.5b widths.

Each kernel test lowers one Pallas kernel of the main serving path with
Mosaic (``interpret=False``) for a described, unattached v5e chip and
compiles it, as the chip's compiler would: no chip is needed, and what
the compiler refuses (block tiling, VMEM) fails here.  One more test
compiles the XLA decode steps and reads the program the chip would run.
Shapes are the serving grid of chip_smoke.py: M=4 instances x 8 slots,
max_context 1024, 4 prefill lanes of 32-token chunks.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

M, B, D, H, KVH, HD, FF, V = 4, 8, 1024, 16, 16, 64, 2816, 151936
S, LANES, CHUNK = 1024, 4, 32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's program cannot be read back from the persistent
    # cache, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_decode_attention_compiles(one_chip):
    from repro.kernels.decode_attn import decode_attention

    kv = _spec(one_chip, (M, B, S, KVH, HD))
    _compile(lambda q, k, v, n: decode_attention(q, k, v, n, interpret=False),
             _spec(one_chip, (M, B, H, HD)), kv, kv,
             _spec(one_chip, (M, B), jnp.int32))


def test_chunk_prefill_attention_compiles(one_chip):
    from repro.kernels.chunk_prefill_attn import chunk_prefill_attention

    kv = _spec(one_chip, (LANES, 1, S + CHUNK, KVH, HD))
    _compile(lambda q, k, v, o: chunk_prefill_attention(
                 q, k, v, o, s_cache=S, interpret=False),
             _spec(one_chip, (LANES, 1, CHUNK, H, HD)), kv, kv,
             _spec(one_chip, (LANES, 1), jnp.int32))


def test_logits_argmax_compiles(one_chip):
    from repro.kernels.decode_layer import _logits_argmax_parts

    _compile(lambda x, s, h: _logits_argmax_parts(x, s, h, interpret=False),
             _spec(one_chip, (M, B, D)), _spec(one_chip, (M, D)),
             _spec(one_chip, (M, D, V)))


@pytest.mark.xfail(strict=True, reason=(
    "Mosaic refuses the megakernel's bf16 matmuls: \"'tpu.matmul' op "
    "Expected matmul acc to be 32-bit\"; with f32 accumulators it then "
    "refuses the attention einsum: \"'tpu.matmul' op Not implemented: "
    "Up to 1 batch dim supported\".  One lane's bf16 layer weights "
    "(about 25 MB) would not fit VMEM either (ROADMAP Speed 6)"))
def test_decode_layer_compiles(one_chip):
    from repro.kernels.decode_layer import decode_layer

    mat = lambda *shape: _spec(one_chip, shape)
    lp = {
        "attn_norm": mat(M, D), "wq": mat(M, D, H * HD),
        "wk": mat(M, D, KVH * HD), "wv": mat(M, D, KVH * HD),
        "bq": mat(M, H * HD), "bk": mat(M, KVH * HD), "bv": mat(M, KVH * HD),
        "wo": mat(M, H * HD, D), "mlp_norm": mat(M, D),
        "w_gate": mat(M, D, FF), "w_up": mat(M, D, FF),
        "w_down": mat(M, FF, D),
    }
    cache = mat(M, B, S, KVH, HD)
    _compile(lambda lp, x, ck, cv, p: decode_layer(
                 lp, x, ck, cv, p, num_heads=H, head_dim=HD,
                 rope_theta=1e6, interpret=False),
             lp, mat(M, B, D), cache, cache,
             _spec(one_chip, (M, B), jnp.int32))


@pytest.mark.parametrize("heads", [H, 8])
def test_decode_steps_write_the_cache_in_place(one_chip, heads):
    """Four decode steps of the (M, B) grid with a lane mask, as the
    serving block scans them, at qwen1.5-0.5b widths (head dim 64, which
    the v5e lays out slots-minor) and with 8 heads of 128 (row-major):
    the compiled program holds no select, copy or transpose the size of
    the K/V cache or of one layer of it.  A stopped lane freezes at its
    row write, in the cache's own layout."""
    import math
    import re

    from repro import api
    from repro.configs import registry

    cfg = registry.serving_config("qwen1.5-0.5b").with_(
        num_instances=M, dtype="bfloat16", num_heads=heads,
        num_kv_heads=heads, head_dim=D // heads)
    spec = lambda a: _spec(one_chip, a.shape, a.dtype)
    params = jax.tree.map(spec, api.abstract_params(cfg))
    cache = jax.tree.map(spec, api.abstract_cache(cfg, M, B, S))
    grid = lambda dtype: _spec(one_chip, (M, B), dtype)

    def block(params, cache, tok, pos, alive):
        def body(carry, _):
            tok, pos, cache, alive = carry
            logits, cache = api.decode_step(cfg, params, cache, tok[..., None],
                                            pos, live=alive)
            nxt = jnp.where(alive, jnp.argmax(logits, -1).astype(tok.dtype),
                            tok)
            return (nxt, jnp.where(alive, pos + 1, pos), cache,
                    alive & (nxt != 0)), None

        return jax.lax.scan(body, (tok, pos, cache, alive), None, length=4)[0]

    text = jax.jit(block, donate_argnums=1).lower(
        params, cache, grid(jnp.int32), grid(jnp.int32), grid(jnp.bool_)
    ).compile().as_text()
    leaf = jax.tree.leaves(cache)[0]
    sizes = {leaf.size, leaf.size // cfg.num_layers}
    found = []
    for name, shapes, op in re.findall(
            r"%(\S+) = (.*?) ([\w-]+)\(", text):
        if op in ("select", "copy", "transpose") or "select" in name:
            if sizes & {math.prod(int(d) for d in s.split(",") if d)
                        for s in re.findall(r"\[([\d,]*)\]", shapes)}:
                found.append(f"{name} {op} {shapes}")
    assert not found, found
