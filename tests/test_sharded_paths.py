"""Multi-device numeric equality for the §Perf sharded code paths.

The shard_map MoE dispatch/combine (`moe._shmap_rows`), the context-
sharded ring-buffer KV insert (`layers._cache_update_sharded`) and the
one-block decode path must produce bit-identical results to the plain
single-device path.  The main test process keeps the spec-mandated single
CPU device, so the real multi-device checks run in a subprocess with
``xla_force_host_platform_device_count=8``.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_subprocess(body: str):
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro.launch.compat import make_host_mesh
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.common import Rules
        assert len(jax.devices()) == 8, jax.devices()
        mesh = make_host_mesh((2, 4))
        """
    ) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, cwd=REPO, timeout=600,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    return r.stdout


@pytest.mark.slow
def test_moe_shard_map_dispatch_matches_single_device():
    """moe_mlp under 8-device serve rules == moe_mlp with no rules."""
    _run_subprocess(
        """
        from repro.configs import registry
        from repro.models import moe
        from repro.launch.shardings import serve_rules, moe_dp_compute

        cfg = registry.get_smoke_config("qwen3-moe-30b-a3b").with_(
            num_instances=2, dtype="float32", param_dtype="float32")
        key = jax.random.PRNGKey(0)
        params = moe.init(cfg, key)
        lp = jax.tree.map(lambda x: x[0], params["layers"])  # one layer
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 16, cfg.d_model))

        ref, aux_ref = moe.moe_mlp(cfg, lp, x)               # no rules: plain vmap

        for make in (serve_rules, lambda m: moe_dp_compute(serve_rules(m))):
            rules = make(mesh)
            with jax.set_mesh(mesh), rules:
                out, aux = jax.jit(lambda l, xx: moe.moe_mlp(cfg, l, xx))(lp, x)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-5)
        print("moe shard_map OK")
        """
    )


@pytest.mark.slow
def test_moe_ep_shmap_matches_single_device():
    """Canonical-EP path (expert-window dispatch + psum combine) == plain
    path, experts sharded 4-way over 'model'."""
    _run_subprocess(
        """
        from repro.configs import registry
        from repro.models import moe
        from repro.launch.shardings import serve_rules, moe_ep_shmap

        # 8 experts on a 4-way model axis -> e_local = 2 per rank
        cfg = registry.get_smoke_config("qwen3-moe-30b-a3b").with_(
            num_instances=2, num_experts=8, num_experts_per_tok=2,
            dtype="float32", param_dtype="float32")
        params = moe.init(cfg, jax.random.PRNGKey(0))
        lp = jax.tree.map(lambda x: x[0], params["layers"])
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 16, cfg.d_model))

        ref, aux_ref = moe.moe_mlp(cfg, lp, x)

        rules = moe_ep_shmap(serve_rules(mesh))
        with jax.set_mesh(mesh), rules:
            out, aux = jax.jit(lambda l, xx: moe.moe_mlp(cfg, l, xx))(lp, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-5)

        # gradients flow through the shard_map + psum
        def loss(l, xx):
            o, a = moe.moe_mlp(cfg, l, xx)
            return jnp.sum(o * o) + a
        with jax.set_mesh(mesh), rules:
            g = jax.jit(jax.grad(loss))(lp, x)
        g_ref = jax.grad(loss)(lp, x)
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-4)
        print("moe ep shard_map OK")
        """
    )


@pytest.mark.slow
def test_sharded_cache_insert_matches_single_device():
    """_cache_update_sharded == plain vmap DUS, cache_seq sharded 4-way."""
    _run_subprocess(
        """
        from repro.models import layers as L
        from repro.launch.shardings import serve_rules

        m, b, s, kvh, hd = 2, 4, 32, 2, 8
        key = jax.random.PRNGKey(0)
        ck = jax.random.normal(key, (m, b, s, kvh, hd))
        cv = jax.random.normal(jax.random.PRNGKey(1), (m, b, s, kvh, hd))
        kn = jax.random.normal(jax.random.PRNGKey(2), (m, b, 1, kvh, hd))
        vn = jax.random.normal(jax.random.PRNGKey(3), (m, b, 1, kvh, hd))
        # positions straddling shard boundaries (s_local = 8)
        pos = jnp.array([[0, 7, 8, 31], [15, 16, 23, 24]], jnp.int32)

        rk, rv = L.cache_update_one(ck, cv, kn, vn, pos)      # no rules

        rules = serve_rules(mesh)
        with jax.set_mesh(mesh), rules:
            sk, sv = jax.jit(L.cache_update_one)(ck, cv, kn, vn, pos)
        np.testing.assert_array_equal(np.asarray(sk), np.asarray(rk))
        np.testing.assert_array_equal(np.asarray(sv), np.asarray(rv))
        print("cache insert OK")
        """
    )


@pytest.mark.slow
@pytest.mark.parametrize("hd", [8, 128])
def test_sharded_stacked_cache_insert_matches_single_device(hd):
    """The same insert into one layer of a stacked cache in its kv_stack
    view (slots-minor for hd=8, as it is for hd=128), with a live mask,
    cache_seq sharded 4-way: bit-identical to the single-device write."""
    _run_subprocess(
        f"""
        from repro.models import layers as L
        from repro.launch.shardings import serve_rules

        nl, m, b, s, kvh, hd = 3, 2, 4, 32, 2, {hd}
        ck = L.kv_stack(jax.random.normal(jax.random.PRNGKey(0), (nl, m, b, s, kvh, hd)))
        cv = L.kv_stack(jax.random.normal(jax.random.PRNGKey(1), (nl, m, b, s, kvh, hd)))
        kn = jax.random.normal(jax.random.PRNGKey(2), (m, b, 1, kvh, hd))
        vn = jax.random.normal(jax.random.PRNGKey(3), (m, b, 1, kvh, hd))
        pos = jnp.array([[0, 7, 8, 31], [15, 16, 23, 24]], jnp.int32)
        live = jnp.array([[True, False, True, True], [True, True, False, True]])
        layer = jnp.int32(1)
        write = lambda ck, cv: L.cache_update_one(
            ck, cv, kn, vn, pos, live=live, layer=layer)

        rk, rv = write(ck, cv)                                # no rules
        rules = serve_rules(mesh)
        with jax.set_mesh(mesh), rules:
            sk, sv = jax.jit(write)(ck, cv)
        np.testing.assert_array_equal(np.asarray(sk), np.asarray(rk))
        np.testing.assert_array_equal(np.asarray(sv), np.asarray(rv))
        print("stacked cache insert OK")
        """
    )


@pytest.mark.slow
def test_decode_step_sharded_matches_single_device():
    """Full dense decode_step (one-block attention + sharded cache) under
    the 8-device serve rules == single-device decode_step."""
    _run_subprocess(
        """
        from repro import api
        from repro.configs import registry
        from repro.launch.shardings import serve_rules

        cfg = registry.get_smoke_config("tinyllama-1.1b").with_(
            num_instances=2, dtype="float32", param_dtype="float32")
        params = api.init(cfg, jax.random.PRNGKey(0))
        ctx = 64
        cache = api.make_cache(cfg, 2, 4, ctx)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 4, 1), 0, cfg.vocab_size)
        pos = jnp.full((2, 4), 17, jnp.int32)

        ref_logits, ref_cache = api.decode_step(cfg, params, cache, toks, pos)

        rules = serve_rules(mesh)
        with jax.set_mesh(mesh), rules:
            out_logits, out_cache = jax.jit(
                lambda p, c, t, q: api.decode_step(cfg, p, c, t, q)
            )(params, cache, toks, pos)
        np.testing.assert_allclose(np.asarray(out_logits),
                                   np.asarray(ref_logits), rtol=2e-5, atol=2e-5)
        for a, bnd in zip(jax.tree.leaves(out_cache), jax.tree.leaves(ref_cache)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(bnd),
                                       rtol=2e-5, atol=2e-5)
        print("decode step OK")
        """
    )


@pytest.mark.slow
def test_flash_attention_shard_map_prefill_matches():
    """Sq>1 attention under serve rules (shard_map over q-heads) == plain
    single-device flash, for both KVH-divisible and GQA-sliced layouts."""
    _run_subprocess(
        """
        from repro.models import layers as L
        from repro.launch.shardings import serve_rules

        def run(h, kvh):
            m, b, sq, skv, hd = 2, 4, 32, 64, 8
            q = jax.random.normal(jax.random.PRNGKey(0), (m, b, sq, h, hd))
            k = jax.random.normal(jax.random.PRNGKey(1), (m, b, skv, kvh, hd))
            v = jax.random.normal(jax.random.PRNGKey(2), (m, b, skv, kvh, hd))
            qp = jnp.broadcast_to(jnp.arange(32, 32 + sq, dtype=jnp.int32), (m, b, sq))
            kp = jnp.broadcast_to(jnp.arange(skv, dtype=jnp.int32), (m, b, skv))
            ref = L.flash_attention(q, k, v, qp, kp, q_chunk=16, kv_chunk=16)
            rules = serve_rules(mesh)   # model axis = 4
            with jax.set_mesh(mesh), rules:
                out = jax.jit(lambda *a: L.flash_attention(
                    *a, q_chunk=16, kv_chunk=16))(q, k, v, qp, kp)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)

        run(h=8, kvh=8)   # kvh divides model axis (4): fully local heads
        run(h=8, kvh=2)   # kvh=2 < 4: per-rank GQA kv-head slice path
        run(h=6, kvh=2)   # h%4 != 0: falls back to the GSPMD path
        print("flash shard_map OK")
        """
    )


def test_flash_attention_single_block_decode_path():
    """sq=1 takes the one-block path (kc == skv) and matches the chunked
    reference numerically (single device, no rules needed)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import layers as L

    m, b, h, kvh, hd, skv = 2, 3, 4, 2, 8, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (m, b, 1, h, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (m, b, skv, kvh, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (m, b, skv, kvh, hd))
    q_pos = jnp.full((m, b, 1), 40, jnp.int32)
    kv_pos = jnp.broadcast_to(jnp.arange(skv, dtype=jnp.int32), (m, b, skv))

    out = L.flash_attention(q, k, v, q_pos, kv_pos)            # one-block path
    # reference: force chunked streaming by faking sq=2 with a dup query
    q2 = jnp.concatenate([q, q], axis=2)
    qp2 = jnp.concatenate([q_pos, q_pos], axis=2)
    ref = L.flash_attention(q2, k, v, qp2, kv_pos, kv_chunk=16)[:, :, :1]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
