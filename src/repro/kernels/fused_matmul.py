"""Pallas TPU kernel: NetFuse merged (instance-batched) matmul.

The paper's hot spot: M fine-tuned instances each multiply their own
(B, D) activations with their own (D, F) weights.  At small per-instance
batch (the paper's serving regime, bs=1-8) a plain matmul wastes the
128x128 MXU; batching the instance dim into the grid keeps the systolic
array fed while preserving input-weight locality (instance m's tile only
ever meets instance m's weight tile).

Grid: (M, T/bt, F/bf, D/bd) — the K (=D) dimension is the innermost
grid axis and accumulates into a VMEM f32 scratch, written back once on
the last K step (standard Pallas matmul revisiting pattern).  Block
shapes default to MXU-aligned 128s and clamp to the problem size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[0], w_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _bias_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[0], w_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _done():
        o_ref[0] = (acc_ref[...] + b_ref[0].astype(jnp.float32)).astype(o_ref.dtype)


def _clamp(block: int, dim: int) -> int:
    b = min(block, dim)
    while dim % b:
        b -= 1
    return b


@functools.partial(
    jax.jit, static_argnames=("block_t", "block_f", "block_d", "interpret")
)
def fused_matmul(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array | None = None,
    *,
    block_t: int = 128,
    block_f: int = 128,
    block_d: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """x: (M,T,D) @ w: (M,D,F) [+ b: (M,F)] -> (M,T,F).

    ``interpret=None`` auto-detects: compiled Mosaic on TPU, Pallas
    interpreter elsewhere (kernel bodies execute on CPU for tests)."""
    m, t, d = x.shape
    f = w.shape[2]
    bt, bf, bd = _clamp(block_t, t), _clamp(block_f, f), _clamp(block_d, d)
    nk = d // bd
    grid = (m, t // bt, f // bf, nk)

    x_spec = pl.BlockSpec((1, bt, bd), lambda mi, ti, fi, ki: (mi, ti, ki))
    w_spec = pl.BlockSpec((1, bd, bf), lambda mi, ti, fi, ki: (mi, ki, fi))
    o_spec = pl.BlockSpec((1, bt, bf), lambda mi, ti, fi, ki: (mi, ti, fi))

    if b is None:
        return pl.pallas_call(
            functools.partial(_kernel, nk=nk),
            grid=grid,
            in_specs=[x_spec, w_spec],
            out_specs=o_spec,
            out_shape=jax.ShapeDtypeStruct((m, t, f), x.dtype),
            scratch_shapes=[pltpu_scratch(bt, bf)],
            interpret=interpret_mode(interpret),
        )(x, w)
    b_spec = pl.BlockSpec((1, bf), lambda mi, ti, fi, ki: (mi, fi))
    return pl.pallas_call(
        functools.partial(_bias_kernel, nk=nk),
        grid=grid,
        in_specs=[x_spec, w_spec, b_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((m, t, f), x.dtype),
        scratch_shapes=[pltpu_scratch(bt, bf)],
        interpret=interpret_mode(interpret),
    )(x, w, b)


def pltpu_scratch(bt: int, bf: int):
    """f32 VMEM accumulator scratch (TPU memory space)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM((bt, bf), jnp.float32)


def fused_matmul_sharded(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array | None = None,
    *,
    rules,
    **kw,
) -> jax.Array:
    """``fused_matmul`` under ``shard_map`` on the rules' mesh.

    The (M, T, F) problem is embarrassingly parallel under the serving
    layout: instances (M) ride the data axes and output features (F —
    logical ``mlp``) ride "model", so each rank runs the Pallas kernel
    on its local (M_l, T, D) x (M_l, D, F_l) block — no collectives, and
    the interpret-mode fallback inside :func:`fused_matmul` is intact
    (the per-rank body is an ordinary pallas_call).  Dims that don't
    divide their mesh axes replicate via the rules' divisibility guard,
    so any shape is accepted.
    """

    m, t, d = x.shape
    f = w.shape[2]
    x_spec = rules.spec(("instances", None, None), (m, t, d))
    w_spec = rules.spec(("instances", None, "mlp"), (m, d, f))
    o_spec = rules.spec(("instances", None, "mlp"), (m, t, f))

    if b is None:
        return jax.shard_map(
            lambda xl, wl: fused_matmul(xl, wl, **kw),
            mesh=rules.mesh, in_specs=(x_spec, w_spec), out_specs=o_spec,
            check_vma=False,
        )(x, w)
    b_spec = rules.spec(("instances", "mlp"), b.shape)
    return jax.shard_map(
        lambda xl, wl, bl: fused_matmul(xl, wl, bl, **kw),
        mesh=rules.mesh, in_specs=(x_spec, w_spec, b_spec), out_specs=o_spec,
        check_vma=False,
    )(x, w, b)
