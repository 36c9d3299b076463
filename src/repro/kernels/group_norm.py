"""Pallas TPU kernel: merged per-instance RMS norm (the layer-norm ->
group-norm rule of the paper, instance-axis form).

Each grid step owns (1 instance, bt rows, full D): the normalization
reduction runs entirely in VMEM/VREGs (one row's D fits easily — D <=
8192 -> 32 KB f32), stats in f32, cast on write.  Grid: (M, T/bt).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[0].astype(jnp.float32)                 # (bt, D)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * s_ref[0].astype(jnp.float32)[None, :]
    o_ref[0] = y.astype(o_ref.dtype)


def _clamp(block: int, dim: int) -> int:
    b = min(block, dim)
    while dim % b:
        b -= 1
    return b


@functools.partial(jax.jit, static_argnames=("eps", "block_t", "interpret"))
def group_rms_norm(
    x: jax.Array,
    scale: jax.Array,
    *,
    eps: float = 1e-5,
    block_t: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """x: (M,T,D), scale: (M,D) -> (M,T,D)."""
    m, t, d = x.shape
    bt = _clamp(block_t, t)
    grid = (m, t // bt)
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, d), lambda mi, ti: (mi, ti, 0)),
            pl.BlockSpec((1, d), lambda mi, ti: (mi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, d), lambda mi, ti: (mi, ti, 0)),
        out_shape=jax.ShapeDtypeStruct((m, t, d), x.dtype),
        interpret=interpret_mode(interpret),
    )(x, scale)
