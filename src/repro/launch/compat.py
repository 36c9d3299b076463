"""Runtime set-up shared by every entry point: meshes, forced host
devices and the persistent compile cache.

Every mesh in the repo is built by :func:`make_host_mesh` with
``AxisType.Auto`` axes.  ``jax.make_mesh`` defaults to ``Explicit``
axes, under which ``with_sharding_constraint`` (``models.common.
constrain``) asserts a layout instead of imposing one; the model zoo and
the serving engine rely on the latter.
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path

import jax

# <repo>/.jax_cache — a fixed path, so every run of this checkout finds
# what an earlier run compiled (the path is part of the cache key)
_REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache lives in ``<repo>/.jax_cache``
    (listed in ``.gitignore``)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_REPO_CACHE_DIR))
    return str(_REPO_CACHE_DIR)


@contextlib.contextmanager
def mesh_context(mesh, *ctxs):
    """Enter ``jax.set_mesh(mesh)`` plus any extra context managers
    (Rules).

    ``mesh`` may be None (no-op — the single-device path), so callers
    can hold ONE code path for mesh-parametric and plain execution."""
    with contextlib.ExitStack() as stack:
        if mesh is not None:
            stack.enter_context(jax.set_mesh(mesh))
        for c in ctxs:
            if c is not None:
                stack.enter_context(c)
        yield


def make_host_mesh(shape: tuple[int, ...] | None = None,
                   axes: tuple[str, ...] = ("data", "model"), *,
                   devices=None):
    """A mesh with ``Auto`` axes over ``devices`` (default: all visible).

    ``shape=None`` puts every device on the first axis (pure DP serving);
    pass an explicit (data, model) shape to split off tensor parallelism.
    """
    if devices is None:
        devices = jax.devices()
    if shape is None:
        shape = (len(devices),) + (1,) * (len(axes) - 1)
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices)


def force_host_devices_from_argv(argv) -> int:
    """Apply a ``--devices N`` / ``--devices=N`` CLI flag as
    ``--xla_force_host_platform_device_count=N`` BEFORE the first jax
    backend init (the device count locks there; call this at script top,
    before any jax API that touches devices).  N <= 0 or a malformed
    value is left for argparse to reject later — XLA_FLAGS untouched.
    Returns the parsed count (0 if absent/disabled)."""
    n = 0
    for i, a in enumerate(argv):
        if a == "--devices" and i + 1 < len(argv):
            val = argv[i + 1]
        elif a.startswith("--devices="):
            val = a.split("=", 1)[1]
        else:
            continue
        try:
            n = int(val)
        except ValueError:
            return 0
        break
    if n > 0:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}"
        )
    return max(n, 0)


def mesh_from_args(devices: int, mesh_shape: str | None):
    """Serving-mesh construction shared by launch/serve and serve_bench:
    ``mesh_shape`` is a "DxT" string ((data, model) split, e.g. "2x4"),
    ``devices`` a host-platform override already applied by
    :func:`force_host_devices_from_argv`.  Neither set (``devices <= 0``
    counts as unset, matching the flag parser) -> None (the engine's
    plain single-device path)."""
    if devices <= 0 and not mesh_shape:
        return None
    shape = (
        tuple(int(p) for p in mesh_shape.split("x")) if mesh_shape else None
    )
    return make_host_mesh(shape)
