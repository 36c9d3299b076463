"""Shared utilities for the fusion-aware model zoo.

Conventions (see DESIGN.md §2.1):

* every parameter tensor carries a leading ``instances`` axis ``M``
  (NetFuse-merged fine-tuned instances; M=1 is the plain model),
* activations are ``(M, B, ...)`` — per-instance batches,
* layer stacks are stacked along a leading ``L`` axis and executed with
  ``lax.scan``,
* every param is built together with its *logical sharding axes* so the
  launcher can derive PartitionSpecs (MaxText-style logical axis rules).

``build_params(cfg, factory)`` functions return a pytree whose leaves are
:class:`PA` (value + logical axes).  ``factory`` decides whether values
are real random arrays (init) or ShapeDtypeStructs (abstract init for the
multi-pod dry-run — no host allocation for 67B-param models).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class PA:
    """A parameter leaf: value + logical sharding axes (one name per dim,
    None = replicated dim)."""
    value: Any
    axes: tuple[str | None, ...]


def _is_pa(x) -> bool:
    return isinstance(x, PA)


def param_values(tree):
    return jax.tree.map(lambda p: p.value, tree, is_leaf=_is_pa)


def param_axes(tree):
    return jax.tree.map(lambda p: p.axes, tree, is_leaf=_is_pa)


class Factory:
    """Creates parameter leaves; real or abstract."""

    def __init__(self, key: jax.Array | None, dtype=jnp.float32, abstract: bool = False):
        self.key = key
        self.dtype = dtype
        self.abstract = abstract

    def _next_key(self):
        self.key, sub = jax.random.split(self.key)
        return sub

    def __call__(
        self,
        shape: Sequence[int],
        axes: tuple[str | None, ...],
        *,
        init: str = "normal",
        scale: float = 0.02,
    ) -> PA:
        shape = tuple(int(s) for s in shape)
        assert len(shape) == len(axes), f"shape {shape} vs axes {axes}"
        if self.abstract:
            return PA(jax.ShapeDtypeStruct(shape, self.dtype), tuple(axes))
        if init == "zeros":
            v = jnp.zeros(shape, self.dtype)
        elif init == "ones":
            v = jnp.ones(shape, self.dtype)
        elif init == "normal":
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            s = scale if scale else 1.0 / np.sqrt(fan_in)
            v = (jax.random.normal(self._next_key(), shape) * s).astype(self.dtype)
        elif init == "fan_in":
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            v = (jax.random.normal(self._next_key(), shape) / np.sqrt(fan_in)).astype(self.dtype)
        else:
            raise ValueError(init)
        return PA(v, tuple(axes))


def make_factory(cfg, key=None, abstract: bool = False) -> Factory:
    dtype = jnp.dtype(cfg.param_dtype)
    return Factory(key, dtype=dtype, abstract=abstract)


# ---------------------------------------------------------------------------
# Logical-axis sharding constraints for activations
# ---------------------------------------------------------------------------

_ACTIVE_RULES: "Rules | None" = None


class Rules:
    """Maps logical axis names -> mesh axis names, with divisibility checks."""

    def __init__(self, mesh, mapping: dict[str, Any]):
        self.mesh = mesh
        self.mapping = mapping  # logical -> mesh axis (str | tuple | None)

    def _axis_size(self, mesh_axes) -> int:
        if mesh_axes is None:
            return 1
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        n = 1
        for a in mesh_axes:
            n *= self.mesh.shape[a]
        return n

    def spec(self, logical: Sequence[str | None], shape: Sequence[int] | None = None):
        from jax.sharding import PartitionSpec as P

        parts = []
        used: set = set()
        for i, name in enumerate(logical):
            mesh_axes = self.mapping.get(name) if name else None
            if mesh_axes is not None and shape is not None:
                # progressive suffix-drop: if the dim doesn't divide the
                # full axis tuple, retry with trailing axes removed (e.g.
                # global_batch=256 on ("data","model","pod")=512 devices
                # still shards 256-way over ("data","model") instead of
                # replicating outright).
                flat = (mesh_axes,) if isinstance(mesh_axes, str) else tuple(mesh_axes)
                while flat and shape[i] % self._axis_size(flat) != 0:
                    flat = flat[:-1]
                mesh_axes = flat or None
            if mesh_axes is not None:
                flat = (mesh_axes,) if isinstance(mesh_axes, str) else tuple(mesh_axes)
                if any(a in used for a in flat):
                    mesh_axes = None  # a mesh axis may appear once per spec
                else:
                    used.update(flat)
                    # singleton tuples unwrap to the bare axis name: some
                    # JAX versions don't canonicalize P(("data",)) ==
                    # P("data"), and specs must compare stably
                    mesh_axes = flat[0] if len(flat) == 1 else flat
            parts.append(mesh_axes)
        return P(*parts)

    def __enter__(self):
        global _ACTIVE_RULES
        self._prev = _ACTIVE_RULES
        _ACTIVE_RULES = self
        return self

    def __exit__(self, *exc):
        global _ACTIVE_RULES
        _ACTIVE_RULES = self._prev


def active_rules() -> "Rules | None":
    """The Rules currently in scope (None in plain CPU tests)."""
    return _ACTIVE_RULES


def constrain(x: jax.Array, *logical: str | None) -> jax.Array:
    """Apply a sharding constraint if logical rules are active (no-op in
    plain CPU tests)."""
    if _ACTIVE_RULES is None:
        return x
    spec = _ACTIVE_RULES.spec(logical, x.shape)
    return jax.lax.with_sharding_constraint(x, spec)


def constrain_axes(x: jax.Array, axes: Sequence[str | None]) -> jax.Array:
    """``constrain`` taking the logical-axes tuple a param/cache leaf
    already carries (no-op without active rules)."""
    if _ACTIVE_RULES is None:
        return x
    return jax.lax.with_sharding_constraint(x, _ACTIVE_RULES.spec(axes, x.shape))


def constrain_tree(tree, axes_tree):
    """Constrain every leaf of ``tree`` to its logical axes under the
    active rules — the whole-pytree form of :func:`constrain_axes`, used
    by the mesh-parametric serving jits to pin cache/state trees to the
    rules' layout (no-op without active rules)."""
    if _ACTIVE_RULES is None:
        return tree
    return jax.tree.map(
        lambda ax, l: constrain_axes(l, ax), axes_tree, tree,
        is_leaf=_is_axes_tuple,
    )


# ---------------------------------------------------------------------------
# chunk-carry protocol (serving chunked prefill)
# ---------------------------------------------------------------------------
#
# Every family exposes a chainable, state-carrying chunk prefill (see
# DESIGN.md §6.2):
#
#   init_chunk_carry(cfg, m, b, cache_len) -> carry
#   chunk_carry_axes(cfg)                  -> logical-axes tree for carry
#   prefill_chunk(cfg, params, batch, carry, offset) -> carry
#
# ``carry`` is a dict holding "cache" (EXACTLY the family's decode
# cache/state tree, so slot surgery consumes it unchanged) plus any
# family extras (moe keeps per-layer expert-usage counts).  ``offset``
# is the (M, B) absolute position of the chunk's first token — families
# with a learned prefix (hybrid meta tokens, vlm image patches) count
# prefix positions in the same stream, substituting prefix embeddings
# for positions below the prefix length.  ``batch["valid"]`` (M, B, C)
# bool, when present, marks the junk suffix of a PADDED final chunk
# (serving tail folding — DESIGN.md §6.3): KV families drop the junk
# cache scatters, moe masks routing, recurrent families make the junk
# steps gate-neutral, so the carry equals the exact-length pass.  The
# helpers below let the serving runtime keep K independent requests
# ("lanes") in ONE carry tree: a (K,) mask selects which lanes actually
# advance each call.


def tree_select_lanes(mask, new_tree, old_tree, axes_tree):
    """Per-lane merge of two carry trees: lane k (along each leaf's
    ``instances`` dim) takes ``new_tree`` where ``mask[k]``, else keeps
    ``old_tree``.  Used by the chunked prefill so one compiled chunk fn
    serves lanes at different prompt offsets — finished/idle lanes ride
    through unchanged."""
    mask = jnp.asarray(mask)

    def _sel(ax, n, o):
        i = ax.index("instances")
        mk = mask.reshape((mask.shape[0],) + (1,) * (n.ndim - i - 1))
        return jnp.where(mk, n, o)

    return jax.tree.map(_sel, axes_tree, new_tree, old_tree,
                        is_leaf=_is_axes_tuple)


def tree_select_slots(mask, new_tree, old_tree, axes_tree):
    """Per-(instance, slot) merge of two grid cache trees: slot (m, b)
    takes ``new_tree`` where ``mask[m, b]``, else keeps ``old_tree``.
    The (M, B) mask lands on each leaf's adjacent ``instances``/``batch``
    dims and broadcasts over the rest.  Used by the ssm decode step to
    freeze a stopped lane's recurrent state (DESIGN.md §6.6): each step
    rewrites that state whole, so the select costs about what the step
    does; K/V caches freeze at the row write instead
    (``layers.cache_update_one``)."""
    mask = jnp.asarray(mask)

    def _sel(ax, n, o):
        i = ax.index("instances")
        assert ax[i + 1] == "batch", ax   # grid leaves: instances then batch
        mk = mask.reshape((1,) * i + mask.shape + (1,) * (n.ndim - i - 2))
        return jnp.where(mk, n, o)

    return jax.tree.map(_sel, axes_tree, new_tree, old_tree,
                        is_leaf=_is_axes_tuple)


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------


def stack_layer_params(layer_trees: list):
    """Stack per-layer PA-trees along a leading L axis (for lax.scan)."""
    def _stack(*ps):
        vals = [p.value for p in ps]
        if isinstance(vals[0], jax.ShapeDtypeStruct):
            v = jax.ShapeDtypeStruct((len(vals),) + vals[0].shape, vals[0].dtype)
        else:
            v = jnp.stack(vals)
        return PA(v, ("layers",) + ps[0].axes)
    return jax.tree.map(_stack, *layer_trees, is_leaf=_is_pa)


def count_params(params) -> int:
    """Total parameter count (excluding the instances axis)."""
    tot = 0
    for leaf in jax.tree.leaves(params):
        n = int(np.prod(leaf.shape))
        tot += n
    return tot


# ---------------------------------------------------------------------------
# NetFuse merging of whole-model checkpoints
# ---------------------------------------------------------------------------
#
# Layer-stacked leaves are (L, M, ...) while top-level leaves are (M, ...);
# the ``axes`` tree records where the instances axis sits, so merging M
# fine-tuned checkpoints (each built with num_instances=1) stacks each leaf
# at the right position.


def _inst_axis(ax: tuple) -> int:
    return ax.index("instances")


_is_axes_leaf = lambda x: isinstance(x, tuple)


def merge_instances(instances, axes_tree, *, like=None, shardings=None):
    """NetFuse-merge M single-instance checkpoints -> one merged pytree.

    Instance i is written into row i of a preallocated grid, in place, as
    it arrives: ``instances`` may be a list or a lazy iterable, and an
    iterable that draws each instance on demand never holds more than
    one of them beside the grid.  ``like``: the grid's abstract tree
    (shapes and dtypes; each instance is cast to it), needed for an
    iterable; by default the instances' own, with M = their number.
    ``shardings``: optional per-leaf shardings, so that a mesh-sharded
    grid is built in place and never whole on one device."""
    if like is None:
        instances = list(instances)
        m = len(instances)
        like = jax.tree.map(
            lambda ax, a: jax.ShapeDtypeStruct(
                a.shape[:_inst_axis(ax)] + (m,) + a.shape[_inst_axis(ax) + 1:],
                a.dtype),
            axes_tree, instances[0], is_leaf=_is_axes_leaf)
    grid = jax.jit(lambda: jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype), like),
        out_shardings=shardings)()

    def put(grid, inst, i):
        return jax.tree.map(
            lambda ax, g, w: jax.lax.dynamic_update_slice_in_dim(
                g, w.astype(g.dtype), i, axis=_inst_axis(ax)),
            axes_tree, grid, inst, is_leaf=_is_axes_leaf)

    put = jax.jit(put, out_shardings=shardings, donate_argnums=0)
    for i, inst in enumerate(instances):
        grid = put(grid, inst, i)
        # free the instance before the iterable draws the next one
        del inst
        jax.block_until_ready(grid)
    return grid


def split_instances(params, axes_tree):
    """Inverse of merge_instances: merged pytree -> list of M=1 pytrees."""
    n = None
    def _probe(ax, leaf):
        nonlocal n
        n = leaf.shape[_inst_axis(ax)]
        return leaf
    jax.tree.map(_probe, axes_tree, params, is_leaf=_is_axes_leaf)
    out = []
    for i in range(n):
        out.append(
            jax.tree.map(
                lambda ax, l, i=i: jnp.take(l, jnp.array([i]), axis=_inst_axis(ax)),
                axes_tree, params, is_leaf=_is_axes_leaf,
            )
        )
    return out


def take_instance(params, axes_tree, i: int):
    """Slice instance i (keeping M=1) from a merged pytree."""
    return jax.tree.map(
        lambda ax, l: jnp.take(l, jnp.array([i]), axis=_inst_axis(ax)),
        axes_tree, params, is_leaf=_is_axes_leaf,
    )


def gather_instances(params, axes_tree, idx):
    """Gather instance rows ``idx`` (k,) from a merged pytree -> a pytree
    whose instances axis is k.  ``idx`` may be traced (jit-friendly); used
    by the serving prefill to batch k requests for k different fine-tuned
    models through ONE fused program (each request rides the instances
    axis — paper §2.1 applied to admission instead of steady-state)."""
    idx = jnp.asarray(idx, jnp.int32)
    return jax.tree.map(
        lambda ax, l: jnp.take(l, idx, axis=_inst_axis(ax)),
        axes_tree, params, is_leaf=_is_axes_leaf,
    )


# ---------------------------------------------------------------------------
# slot surgery on (M, B)-grid trees (KV caches / recurrent states)
# ---------------------------------------------------------------------------
#
# Serving keeps one cache/state tree for the whole (M, B) slot grid; the
# ``cache_axes``/``state_axes`` trees name where the instances/batch dims
# sit on every leaf, so a single pair of helpers covers every family —
# uniform KVCache stacks (dense/moe/vlm/audio) AND the nested recurrent
# state layouts (ssm/hybrid).  Indices may be traced: one jit covers all
# slots.


def _is_axes_tuple(x) -> bool:
    # logical-axes leaves are plain tuples of str/None; NamedTuple pytree
    # nodes (KVCache) must NOT be treated as leaves.
    return isinstance(x, tuple) and not hasattr(x, "_fields")


def tree_take_slot(tree, axes_tree, m, b):
    """Slice grid slot (m, b) from every leaf, keeping singleton dims.

    Shard-safe: when rules are active the sliced singleton leaf is
    re-constrained to its logical axes (the instances/batch dims collapse
    to 1 and replicate via the divisibility guard; other dims — e.g. a
    context-sharded ``cache_seq`` — keep their mesh placement), so slot
    extraction under a mesh never forces a host gather."""
    def _take(ax, leaf):
        i, j = ax.index("instances"), ax.index("batch")
        leaf = jax.lax.dynamic_slice_in_dim(leaf, m, 1, axis=i)
        leaf = jax.lax.dynamic_slice_in_dim(leaf, b, 1, axis=j)
        return constrain_axes(leaf, ax)
    return jax.tree.map(_take, axes_tree, tree, is_leaf=_is_axes_tuple)


def tree_put_slot(grid, axes_tree, one, m, b):
    """Write a single-slot tree (instances=batch=1 dims) into grid slot
    (m, b).  Leaves whose ``cache_seq`` dim is longer/shorter than the
    grid's are prefix-clipped (prefill caches vs. grid context).

    Shard-safe: the updated grid leaf is constrained back to its logical
    axes, so surgery under a mesh preserves every leaf's NamedSharding
    (the dynamic-update lowers to an on-device scatter into the owning
    shards — the grid never round-trips through the host)."""
    def _put(ax, g, o):
        i, j = ax.index("instances"), ax.index("batch")
        if "cache_seq" in ax:
            sa = ax.index("cache_seq")
            s = min(o.shape[sa], g.shape[sa])
            o = jax.lax.slice_in_dim(o, 0, s, axis=sa)
        start = [jnp.int32(0)] * g.ndim
        start[i], start[j] = m, b
        out = jax.lax.dynamic_update_slice(g, o.astype(g.dtype), tuple(start))
        return constrain_axes(out, ax)
    return jax.tree.map(_put, axes_tree, grid, one, is_leaf=_is_axes_tuple)
