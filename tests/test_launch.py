"""Launch-layer tests: HLO cost model, sharding rules, input specs, and
in-process lowering of every family on a 1x1 mesh (the 512-device meshes
are exercised by launch/dryrun.py, which must own jax initialization)."""
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import api
from repro.configs import registry
from repro.configs.base import SHAPES, ShapeConfig
from repro.launch import hlo_analysis as H
from repro.models.common import Rules


# ---------------------------------------------------------------------------
# HLO cost model
# ---------------------------------------------------------------------------


def test_scan_flops_account_trip_count():
    """The whole reason hlo_analysis exists: XLA's cost_analysis counts a
    while body once; ours multiplies by known_trip_count."""
    D, N = 128, 8

    def f(c, xs):
        return jax.lax.scan(lambda c, x: (c @ x, None), c, xs)[0]

    txt = (
        jax.jit(f)
        .lower(
            jax.ShapeDtypeStruct((D, D), jnp.float32),
            jax.ShapeDtypeStruct((N, D, D), jnp.float32),
        )
        .compile()
        .as_text()
    )
    got = H.analyze_hlo_text(txt)["flops"]
    want = N * 2 * D**3
    assert want <= got <= want * 1.2, (got, want)
    # and XLA's own counts exactly one body:
    assert got >= 7 * (2 * D**3)


def test_dot_flops_exact():
    a = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((32, 16), jnp.float32)
    txt = jax.jit(lambda x, y: x @ y).lower(a, b).compile().as_text()
    got = H.analyze_hlo_text(txt)["flops"]
    assert abs(got - 2 * 64 * 32 * 16) / (2 * 64 * 32 * 16) < 0.05


def test_collective_parsing_synthetic():
    """Collective byte accounting on a hand-written HLO module."""
    txt = """
HloModule m

ENTRY %main (p0: f32[1024]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0)
  %ar = f32[1024]{0} all-reduce(%p0), replica_groups={}, to_apply=%add
  %ag = f32[2048]{0} all-gather(%ar), dimensions={0}
  ROOT %rs = f32[1024]{0} reduce-scatter(%ag), dimensions={0}, to_apply=%add
}
"""
    out = H.analyze_hlo_text(txt)
    assert out["collectives"]["all-reduce"] == 1024 * 4
    assert out["collectives"]["all-gather"] == 2048 * 4     # result moves
    assert out["collectives"]["reduce-scatter"] == 2048 * 4  # operand moves
    assert out["collective_bytes"] == (1024 + 2048 + 2048) * 4


def test_while_trip_multiplies_collectives():
    txt = """
HloModule m

%body (p: (s32[], f32[256])) -> (s32[], f32[256]) {
  %p = (s32[], f32[256]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[256]{0} get-tuple-element(%p), index=1
  %ar = f32[256]{0} all-reduce(%x), to_apply=%add
  ROOT %t = (s32[], f32[256]) tuple(%i, %ar)
}

%cond (p: (s32[], f32[256])) -> pred[] {
  %p = (s32[], f32[256]) parameter(0)
  ROOT %lt = pred[] compare(%p, %p), direction=LT
}

ENTRY %main (p0: (s32[], f32[256])) -> (s32[], f32[256]) {
  %p0 = (s32[], f32[256]) parameter(0)
  ROOT %w = (s32[], f32[256]) while(%p0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
}
"""
    out = H.analyze_hlo_text(txt)
    assert out["collectives"]["all-reduce"] == 5 * 256 * 4


def test_shape_parsing():
    assert H._parse_shape("f32[128,64]{1,0}") == ("f32", [128, 64])
    assert H._parse_shape("bf16[2]") == ("bf16", [2])
    assert H._parse_shape("s32[]") == ("s32", [])
    tup = H._parse_shape("(s32[], f32[4,4]{1,0})")
    assert tup == [("s32", []), ("f32", [4, 4])]
    assert H._nbytes(("bf16", [8, 8])) == 128


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------


class _FakeMesh:
    shape = {"data": 4, "model": 2}


def test_rules_divisibility_guard():
    r = Rules(_FakeMesh(), {"batch": "data", "heads": "model"})
    assert r.spec(("batch", "heads"), (8, 6)) == P("data", "model")
    assert r.spec(("batch", "heads"), (3, 6)) == P(None, "model")   # 3 % 4 != 0
    assert r.spec(("batch", "heads"), (8, 5)) == P("data", None)


def test_rules_duplicate_axis_guard():
    r = Rules(_FakeMesh(), {"seq": "model", "heads": "model"})
    # 'model' can appear only once; first dim wins
    assert r.spec(("seq", "heads"), (4, 4)) == P("model", None)


def test_kv_hd_fallback():
    """8 kv heads on 16-way model axis -> head_dim shards instead."""
    class M:
        shape = {"data": 16, "model": 16}
    r = Rules(M(), {"batch": "data", "kv_heads": "model", "kv_hd": "model"})
    spec = r.spec(("batch", None, "kv_heads", "kv_hd"), (128, 32768, 8, 128))
    assert spec == P("data", None, None, "model")
    spec = r.spec(("batch", None, "kv_heads", "kv_hd"), (128, 32768, 16, 128))
    assert spec == P("data", None, "model", None)


# ---------------------------------------------------------------------------
# input specs: every supported (arch x shape)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", sorted(registry.ASSIGNED))
@pytest.mark.parametrize("shape_name", sorted(SHAPES))
def test_input_specs_all_pairs(arch, shape_name):
    shape = SHAPES[shape_name]
    if not registry.supported(arch, shape):
        with pytest.raises(ValueError):
            registry.config_for_shape(arch, shape)
        return
    cfg = registry.config_for_shape(arch, shape)
    specs = api.input_specs(cfg, shape)
    if shape.kind in ("train", "prefill"):
        toks = specs["batch"]["tokens"]
        assert toks.shape[0] == 1 and toks.shape[1] == shape.global_batch
        if cfg.family == "vlm":
            assert toks.shape[2] + cfg.num_image_patches == shape.seq_len
        else:
            assert toks.shape[2] == shape.seq_len
    else:
        assert specs["tokens"].shape == (1, shape.global_batch, 1)
        assert specs["pos"].shape == (1, shape.global_batch)
        leaves = jax.tree.leaves(specs["cache"])
        assert leaves, "decode cache must be non-empty"
        # cache sized by context (or window/meta+window for SW variants)
        assert all(l.size > 0 for l in leaves)


# ---------------------------------------------------------------------------
# lowering every family in-process (1x1 mesh, smoke configs)
# ---------------------------------------------------------------------------

SMALL_TRAIN = ShapeConfig("small_train", 32, 2, "train")
SMALL_PREFILL = ShapeConfig("small_prefill", 32, 2, "prefill")
SMALL_DECODE = ShapeConfig("small_decode", 64, 2, "decode")


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "olmoe-1b-7b", "xlstm-1.3b",
                                  "hymba-1.5b", "internvl2-26b", "whisper-small"])
@pytest.mark.parametrize("shape", [SMALL_TRAIN, SMALL_PREFILL, SMALL_DECODE])
def test_lower_compile_smoke_mesh(arch, shape):
    from repro.launch.compat import make_host_mesh
    from repro.launch.dryrun import build_lowerable
    from repro.launch.shardings import serve_rules, train_rules

    cfg = registry.get_smoke_config(arch)
    mesh = make_host_mesh((1, 1))
    rules = train_rules(mesh) if shape.kind == "train" else serve_rules(mesh)
    with jax.set_mesh(mesh), rules:
        fn, args, in_sh = build_lowerable(cfg, shape, mesh, rules)
        compiled = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
    txt = compiled.as_text()
    analysis = H.analyze_hlo_text(txt)
    assert analysis["flops"] > 0
    assert analysis["bytes"] > 0


# ---------------------------------------------------------------------------
# runtime set-up: compile cache, per-chip peaks, the merged-grid init
# ---------------------------------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cache_dir_after_one_compile(tmp_path, env_dir):
    """Compile once in a fresh process with the cache helper on; return
    the directory the helper reported (``env_dir`` None: the variable is
    unset)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compat import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "jax.jit(jnp.sin)(jnp.ones(3)).block_until_ready()\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=tmp_path, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.strip().splitlines()[-1]


def test_compile_cache_honours_env_dir(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: that directory receives the cache
    and nothing is set in code."""
    d = tmp_path / "cache"
    assert _cache_dir_after_one_compile(tmp_path, d) == str(d)
    assert os.listdir(d)


def test_compile_cache_defaults_to_fixed_checkout_dir(tmp_path):
    """Unset: the cache goes to <repo>/.jax_cache, which git ignores."""
    want = os.path.join(REPO, ".jax_cache")
    assert _cache_dir_after_one_compile(tmp_path, None) == want
    assert os.listdir(want)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_chip_peaks_keyed_by_device_kind():
    from repro.launch.mesh import chip_peaks

    assert chip_peaks("TPU v5 lite").flops_bf16 == 197e12
    assert chip_peaks("TPU v5 lite").hbm_bw == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        chip_peaks("cpu")


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "olmoe-1b-7b",
                                  "xlstm-1.3b", "hymba-1.5b"])
def test_init_instances_is_per_instance_init(arch):
    """Instance i of the grid is init_instance(i), whatever M is, and the
    grid holds it at the config's param dtype."""
    from repro.models import common as C

    cfg = registry.inference_config(registry.get_smoke_config(arch))
    key = jax.random.PRNGKey(3)
    grid3 = api.init_instances(cfg.with_(num_instances=3), key)
    grid2 = api.init_instances(cfg.with_(num_instances=2), key)
    master = cfg.with_(param_dtype="float32")
    ax = api.axes(cfg.with_(num_instances=1))
    for i in range(3):
        want = api.init_instance(master, key, i)
        got = C.take_instance(grid3, ax, i)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert g.dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(g, np.float32),
                np.asarray(w.astype(jnp.bfloat16), np.float32))
    for a, b in zip(jax.tree.leaves(C.take_instance(grid2, ax, 1)),
                    jax.tree.leaves(C.take_instance(grid3, ax, 1))):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_merge_instances_matches_concatenation():
    """Checkpoints written row by row into the preallocated grid give the
    concatenation of the instances, bit for bit and dtype for dtype."""
    from repro.models import common as C

    cfg1 = registry.get_smoke_config("qwen1.5-0.5b").with_(num_instances=1)
    insts = [api.init(cfg1, jax.random.PRNGKey(i)) for i in range(3)]
    ax = api.axes(cfg1)
    got = C.merge_instances(insts, ax)
    want = jax.tree.map(
        lambda a, *leaves: jnp.concatenate(leaves, a.index("instances")),
        ax, *insts, is_leaf=lambda x: isinstance(x, tuple))
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_init_instances_on_a_mesh_is_built_in_place():
    """With a mesh the grid is built in the engine's serving layout, each
    of four devices holding its own quarter of the instances, and equals
    the grid built on one device."""
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=4'\n"
        "import jax, numpy as np\n"
        "from repro import api\n"
        "from repro.configs import registry\n"
        "from repro.launch.compat import make_host_mesh\n"
        "from repro.launch.shardings import serve_rules, tree_shardings\n"
        "cfg = registry.inference_config(\n"
        "    registry.get_smoke_config('qwen1.5-0.5b')).with_(num_instances=8)\n"
        "key = jax.random.PRNGKey(0)\n"
        "mesh = make_host_mesh((4, 1))\n"
        "grid = api.init_instances(cfg, key, mesh=mesh)\n"
        "ax = api.axes(cfg)\n"
        "want = tree_shardings(serve_rules(mesh), ax, grid)\n"
        "is_ax = lambda x: isinstance(x, tuple)\n"
        "for a, g, s in zip(jax.tree.leaves(ax, is_leaf=is_ax),\n"
        "                   jax.tree.leaves(grid), jax.tree.leaves(want)):\n"
        "    assert g.sharding.is_equivalent_to(s, g.ndim), (g.sharding, s)\n"
        "    for sh in g.addressable_shards:\n"
        "        assert sh.data.shape[a.index('instances')] == 2, sh.data.shape\n"
        "one = api.init_instances(cfg, key)\n"
        "for g, w in zip(jax.tree.leaves(grid), jax.tree.leaves(one)):\n"
        "    np.testing.assert_array_equal(np.asarray(g, np.float32),\n"
        "                                  np.asarray(w, np.float32))\n"
        "print('mesh grid OK')\n"
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "mesh grid OK" in r.stdout
