"""The merged grid's weights, made on the device from the seed.

The benchmark makes the weights itself, so that the reference can make the
same ones again without taking anything from the program.  The tree is laid
out as the program's dense decoder holds a merged grid (instances axis M,
layer stacks on a leading L axis); ``program.check_layout`` compares it with
the program's own abstract tree before anything runs.

Values: embedding rows N(0, 0.02^2); projection matrices N(0, 1/fan_in), so
activations keep unit scale; norm scales 1 + N(0, 0.1^2) and QKV biases
N(0, 0.5^2), so that both differ between instances and a path that drops
them shows in the logits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import Dense


def key(seed: int):
    """A PRNG key from all the bits of a (possibly > 32-bit) seed."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def layout(d: Dense) -> dict:
    """(shape, kind) of every leaf, in the program's tree."""
    m, l, dm, hd = d.instances, d.layers, d.d_model, d.head_dim
    q, kv = d.heads * hd, d.kv_heads * hd
    layers = {
        "attn_norm": ((l, m, dm), "norm"),
        "wq": ((l, m, dm, q), "matrix"),
        "wk": ((l, m, dm, kv), "matrix"),
        "wv": ((l, m, dm, kv), "matrix"),
        "wo": ((l, m, q, dm), "matrix"),
        "mlp_norm": ((l, m, dm), "norm"),
        "w_gate": ((l, m, dm, d.d_ff), "matrix"),
        "w_up": ((l, m, dm, d.d_ff), "matrix"),
        "w_down": ((l, m, d.d_ff, dm), "matrix"),
    }
    if d.qkv_bias:
        layers.update(bq=((l, m, q), "bias"), bk=((l, m, kv), "bias"),
                      bv=((l, m, kv), "bias"))
    tree = {"embed": ((m, d.vocab, dm), "embed"), "layers": layers,
            "final_norm": ((m, dm), "norm")}
    if not d.tied:
        tree["lm_head"] = ((m, dm, d.vocab), "matrix")
    return tree


def _leaf(k, shape, kind, dtype):
    z = jax.random.normal(k, shape, dtype)
    if kind == "embed":
        return z * jnp.asarray(0.02, dtype)
    if kind == "matrix":
        return z * jnp.asarray(1.0 / np.sqrt(shape[-2]), dtype)
    if kind == "norm":
        return 1 + z * jnp.asarray(0.1, dtype)
    if kind == "bias":
        return z * jnp.asarray(0.5, dtype)
    raise ValueError(kind)


def make_grid(d: Dense, seed: int, dtype=jnp.bfloat16):
    """The whole grid in one jitted call, in the type it is served in."""
    spec = layout(d)
    paths, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[1], str))

    def build(k):
        return treedef.unflatten([
            _leaf(jax.random.fold_in(k, i), shape, kind, dtype)
            for i, (shape, kind) in enumerate(paths)])

    return jax.jit(build)(key(seed))
