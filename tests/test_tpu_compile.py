"""The serving kernels compile for a TPU v5e at qwen1.5-0.5b widths.

Each test lowers one Pallas kernel of the main serving path with Mosaic
(``interpret=False``) for a described, unattached v5e chip and compiles
it, as the chip's compiler would: no chip is needed, and what the
compiler refuses (block tiling, VMEM) fails here.  Shapes are the
serving grid of chip_smoke.py: M=4 instances x 8 slots, max_context
1024, 4 prefill lanes of 32-token chunks.
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
    "(about 25 MB) would not fit VMEM either (ROADMAP Speed 4)"))
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
