"""Mesh-parametric serving: the engine must serve the fused (M, B) grid
identically on any mesh.

The ISSUE-2 contract: ``MultiModelServer(mesh=...)`` produces the SAME
greedy token streams on a 1-device mesh as today's single-device code
(bit-for-bit — the mesh only adds trivial sharding annotations) and on a
forced 8-CPU-device (data=2, model=4) mesh, where decode, sampling, slot
surgery and chunked prefill all actually run sharded.  Slot surgery
must preserve every cache leaf's NamedSharding across admissions.  The
main test process keeps the spec-mandated single CPU device, so the
multi-device checks run in a subprocess with
``xla_force_host_platform_device_count=8`` (same harness as
test_sharded_paths.py).
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SERVE_HEADER = textwrap.dedent("""
    from repro import api
    from repro.configs import registry
    from repro.models import common as C
    from repro.serving import MultiModelServer, Request

    M = 2

    def build(arch):
        cfg1 = registry.get_smoke_config(arch).with_(
            num_instances=1, dtype="float32", param_dtype="float32")
        cfg = cfg1.with_(num_instances=M)
        keys = jax.random.split(jax.random.PRNGKey(0), M)
        merged = C.merge_instances(
            [api.init(cfg1, k) for k in keys], api.axes(cfg1))
        return cfg, merged

    def serve(cfg, merged, mesh, n_req=6, max_new=5):
        srv = MultiModelServer(
            cfg, merged, slots_per_instance=2, max_context=64, mesh=mesh)
        rng = np.random.default_rng(0)
        for i in range(n_req):
            prompt = rng.integers(
                1, cfg.vocab_size, size=int(rng.integers(2, 8))).tolist()
            srv.submit(Request(instance=i % M, prompt=prompt,
                               max_new_tokens=max_new))
        res = sorted(srv.run_until_drained(), key=lambda r: r.request_id)
        return [r.tokens for r in res], srv
""")


def _run_subprocess(body: str, *, header: str = ""):
    # header and body are dedented SEPARATELY (their literal indents
    # differ), then concatenated at column 0 — a shared dedent would
    # leave the body nested inside the header's last function.
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro.launch.compat import make_host_mesh
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        assert len(jax.devices()) == 8, jax.devices()
        mesh = make_host_mesh((2, 4))
        """
    ) + header + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, cwd=REPO, timeout=900,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    return r.stdout


@pytest.mark.slow
def test_engine_streams_identical_across_meshes():
    """Greedy token streams: no-mesh == 1-device mesh == 8-device mesh,
    for a KV-cache family (dense tinyllama).  The 1-device comparison
    guards the refactor (mesh=None path untouched); the 8-device one
    proves the sharded decode+sample+surgery pipeline is exact."""
    out = _run_subprocess(
        """
        cfg, merged = build("tinyllama-1.1b")
        ref, _ = serve(cfg, merged, None)
        assert all(len(t) > 0 for t in ref), ref
        one, _ = serve(cfg, merged, make_host_mesh((1, 1)))
        assert one == ref, (one, ref)
        eight, _ = serve(cfg, merged, mesh)
        assert eight == ref, (eight, ref)
        print("dense streams OK")
        """,
        header=_SERVE_HEADER,
    )
    assert "dense streams OK" in out


@pytest.mark.slow
def test_engine_streams_identical_recurrent_family():
    """Same contract for a recurrent-state family (xlstm): the chunked
    state-carrying prefill and nested-state slot surgery run sharded."""
    out = _run_subprocess(
        """
        cfg, merged = build("xlstm-1.3b")
        ref, _ = serve(cfg, merged, None, n_req=4, max_new=4)
        assert all(len(t) > 0 for t in ref), ref
        eight, _ = serve(cfg, merged, mesh, n_req=4, max_new=4)
        assert eight == ref, (eight, ref)
        print("ssm streams OK")
        """,
        header=_SERVE_HEADER,
    )
    assert "ssm streams OK" in out


@pytest.mark.slow
def test_slot_surgery_preserves_leaf_shardings():
    """After admissions + decode steps + slot refills, every grid-cache
    leaf must still carry the init-time NamedSharding (surgery is
    on-device scatter, never a host round-trip that drops placement)."""
    out = _run_subprocess(
        """
        from repro.launch.shardings import serve_rules, tree_shardings

        cfg, merged = build("tinyllama-1.1b")
        _, srv = serve(cfg, merged, mesh, n_req=8, max_new=4)
        rules = serve_rules(mesh)
        want = tree_shardings(rules, api.cache_axes(cfg), srv.cache)
        leaves = jax.tree.leaves(srv.cache)
        wants = jax.tree.leaves(want)
        assert leaves and len(leaves) == len(wants)

        def norm(spec):  # actual array specs strip trailing Nones
            p = list(spec)
            while p and p[-1] is None:
                p.pop()
            return tuple(p)

        for leaf, w in zip(leaves, wants):
            assert isinstance(leaf.sharding, NamedSharding), leaf.sharding
            assert norm(leaf.sharding.spec) == norm(w.spec), (
                leaf.sharding.spec, w.spec)
        # params too: device_put at init, untouched by the step loop
        for leaf in jax.tree.leaves(srv.params):
            assert isinstance(leaf.sharding, NamedSharding), leaf.sharding
        print("surgery shardings OK")
        """,
        header=_SERVE_HEADER,
    )
    assert "surgery shardings OK" in out


@pytest.mark.slow
def test_kernels_under_shard_map_match_plain():
    """fused_matmul / decode_attention shard_map wrappers == the plain
    kernels (interpret mode inside each rank), including the GQA
    fallback when KVH doesn't divide the model axis."""
    out = _run_subprocess(
        """
        from repro.launch.shardings import serve_rules
        from repro.kernels.fused_matmul import fused_matmul, fused_matmul_sharded
        from repro.kernels.decode_attn import (
            decode_attention, decode_attention_sharded)

        rules = serve_rules(mesh)

        x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 64))
        w = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 256))
        b = jax.random.normal(jax.random.PRNGKey(2), (2, 256))
        ref = fused_matmul(x, w, b, interpret=True)
        out = fused_matmul_sharded(x, w, b, rules=rules, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

        q = jax.random.normal(jax.random.PRNGKey(3), (2, 4, 8, 16))
        k = jax.random.normal(jax.random.PRNGKey(4), (2, 4, 32, 4, 16))
        v = jax.random.normal(jax.random.PRNGKey(5), (2, 4, 32, 4, 16))
        kv_len = jnp.full((2, 4), 17, jnp.int32)
        ref = decode_attention(q, k, v, kv_len, interpret=True)
        out = decode_attention_sharded(q, k, v, kv_len, rules=rules,
                                       interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

        # kvh=2 on a 4-way model axis -> GSPMD fallback path
        ref = decode_attention(q, k[:, :, :, :2], v[:, :, :, :2], kv_len,
                               interpret=True)
        out = decode_attention_sharded(q, k[:, :, :, :2], v[:, :, :, :2],
                                       kv_len, rules=rules, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

        # chunk-prefill flash attention (q-len C over [cache, chunk]),
        # kv-head groups over "model", lane offsets replicated per rank
        from repro.kernels.chunk_prefill_attn import (
            chunk_prefill_attention, chunk_prefill_attention_sharded)
        c, sc = 6, 26
        qc = jax.random.normal(jax.random.PRNGKey(6), (2, 4, c, 8, 16))
        kc = jax.random.normal(jax.random.PRNGKey(7), (2, 4, sc + c, 4, 16))
        vc = jax.random.normal(jax.random.PRNGKey(8), (2, 4, sc + c, 4, 16))
        off = jax.random.randint(jax.random.PRNGKey(9), (2, 4), 0, sc)
        ref = chunk_prefill_attention(qc, kc, vc, off, s_cache=sc, window=8,
                                      interpret=True)
        out = chunk_prefill_attention_sharded(qc, kc, vc, off, rules=rules,
                                              s_cache=sc, window=8,
                                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        print("sharded kernels OK")
        """
    )
    assert "sharded kernels OK" in out


# ---------------------------------------------------------------------------
# fast in-process checks (single device, no subprocess)
# ---------------------------------------------------------------------------


class _FakeMesh:
    shape = {"data": 2, "model": 4}
    size = 8


def test_make_host_mesh_axes_are_auto():
    """Every mesh is built with Auto axes: under jax.make_mesh's default
    Explicit axes, ``constrain`` would assert layouts instead of
    imposing them."""
    import jax

    from repro.launch.compat import make_host_mesh

    mesh = make_host_mesh()
    assert mesh.axis_names == ("data", "model")
    assert dict(mesh.shape) == {"data": jax.device_count(), "model": 1}
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,) * 2


def test_scheduler_data_shard_mapping():
    from repro.serving.scheduler import TokenBudgetScheduler, make_scheduler

    s = make_scheduler("token-budget", 4, mesh=_FakeMesh())
    assert [s.data_shard_of(i) for i in range(4)] == [0, 0, 1, 1]
    assert s.num_data_shards == 2
    # no mesh / non-divisible M: everything collapses to shard 0
    assert make_scheduler("fifo", 4).data_shard_of(3) == 0
    assert TokenBudgetScheduler(3, mesh=_FakeMesh()).data_shard_of(2) == 0

    # multi-axis batch meshes follow Rules.spec's suffix-drop: M=2 on
    # ("pod", "data") = (2, 4) shards 2-way over "pod" alone
    class _PodMesh:
        shape = {"pod": 2, "data": 4, "model": 2}
        size = 16

    s = make_scheduler("token-budget", 2, mesh=_PodMesh())
    assert [s.data_shard_of(i) for i in range(2)] == [0, 1]
    assert s.num_data_shards == 2


def test_token_budget_tie_breaks_toward_idle_data_shard():
    """Instances 0/1 live on data shard 0, 2/3 on shard 1.  With equal
    per-instance served counts but shard 0 busier overall, the tie must
    break toward shard 1 (mesh-aware); without a mesh it breaks by
    index."""
    from repro.serving.scheduler import Request, TokenBudgetScheduler

    def prep(sched):
        for i in (0, 2):
            sched.submit(Request(instance=i, prompt=[1]))
        # equal served for the two pending instances; their shard-mates
        # differ: instance 1 (shard 0) served a lot, instance 3 none
        sched.served = [5, 90, 5, 0]

    meshy = TokenBudgetScheduler(4, mesh=_FakeMesh())
    prep(meshy)
    assert [r.instance for r in meshy.select({0: 1, 2: 1})] == [2, 0]

    plain = TokenBudgetScheduler(4)
    prep(plain)
    assert [r.instance for r in plain.select({0: 1, 2: 1})] == [0, 2]


def test_metrics_snapshot_carries_mesh_geometry():
    from repro.serving.metrics import ServerMetrics

    snap = ServerMetrics(2, mesh=_FakeMesh()).snapshot()
    assert snap["mesh"] == {"shape": {"data": 2, "model": 4}, "devices": 8}
    assert snap["tok_per_s_per_device"] == snap["tok_per_s"] / 8
    assert "mesh" not in ServerMetrics(2).snapshot()
