"""The system under test: the program's merged serving engine, built from a
configuration file and given the benchmark's weights.  This is the only
module of the benchmark that imports the program (``src/repro``)."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from repro import api                                       # noqa: E402
from repro.configs.base import ModelConfig                  # noqa: E402
from repro.serving import MultiModelServer, Request         # noqa: E402
from repro.serving.frontend import AsyncEngine, EngineClosed  # noqa: E402

from bench.model import Dense                               # noqa: E402

__all__ = ["AsyncEngine", "EngineClosed", "Request", "build_server", "check_layout",
           "model_config", "warm"]


def model_config(d: Dense, name: str) -> ModelConfig:
    return ModelConfig(
        name=name, family="dense", num_layers=d.layers, d_model=d.d_model,
        num_heads=d.heads, num_kv_heads=d.kv_heads, d_ff=d.d_ff,
        vocab_size=d.vocab, qkv_bias=d.qkv_bias, tie_embeddings=d.tied,
        rope_theta=d.rope_theta, norm_eps=d.norm_eps,
        num_instances=d.instances, dtype="bfloat16", param_dtype="bfloat16")


def check_layout(cfg: ModelConfig, grid) -> None:
    """The benchmark's grid has the program's tree, shapes and dtypes."""
    want = jax.tree.map(lambda a: (a.shape, a.dtype), api.abstract_params(cfg))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), grid)
    if want != got:
        raise ValueError(f"weights layout differs from the program's:\n"
                         f"program {want}\nbenchmark {got}")


def build_server(cfg: ModelConfig, grid, serving: dict) -> MultiModelServer:
    return MultiModelServer(
        cfg, grid,
        slots_per_instance=serving["slots_per_instance"],
        max_context=serving["max_context"],
        decode_steps=serving["decode_steps"],
        prefill_chunk=serving["prefill_chunk"],
        prefill_lanes=serving["prefill_lanes"],
        chunk_budget=serving["chunk_budget"],
        scheduler=serving["scheduler"],
        temperature=0.0, eos_id=None)


def horizons(server) -> list[int]:
    """Every decode-block horizon the adaptive policy can pick."""
    ks, k = [], 1
    while k <= server.decode_steps:
        ks.append(k)
        k *= 2
    return ks


def warm(server) -> float:
    """Compile (or fetch from the cache) every program the window runs:
    the decode block at each horizon, the prefill chunk and the slot
    scatter.  Returns the seconds it took."""
    t0 = time.perf_counter()
    m, b = server.m, server.b
    zeros = np.zeros((m, b), np.int32)
    dead = np.zeros((m, b), bool)
    for k in horizons(server):
        # all lanes dead: the block leaves every slot as it was
        with server._ctx():
            out = server._step(server.params, server.cache, jnp.asarray(zeros),
                               jnp.asarray(zeros), server._key,
                               jnp.asarray(dead), jnp.asarray(zeros), k)
        server.cache = out[3]
        jax.block_until_ready(out)
    chunk = server.prefill.chunk
    for i in range(m):
        server.submit(Request(instance=i, prompt=[1] * (2 * chunk + 2),
                              max_new_tokens=2))
    server.run_until_drained()
    return time.perf_counter() - t0
