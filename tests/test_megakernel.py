"""Decode-layer megakernel (ISSUE 8): one Pallas call per dense layer.

The contract: the fused layer kernel (QKV+RoPE, in-kernel ring-cache
append, flash decode attention, out-proj + residual, both RMS norms,
SwiGLU) and the fused logits+greedy-sampling kernel are BIT-IDENTICAL
to the unfused path — kernel-vs-oracle at the op level, decode_step
parity at the model level, and whole greedy token streams through the
engine for K ∈ {1, 8}, sync and async, no-mesh and an 8-device mesh.

Both sides of every comparison are jitted: an eager oracle differs from
a jitted one by FMA contraction, which is an XLA artifact, not a kernel
bug — the serving engine only ever runs jitted.
"""
import functools
import os
import subprocess
import sys
import textwrap

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.configs import registry
from repro.kernels import ops, ref
from repro.serving import AsyncEngine, MultiModelServer, Request
from repro.kernels.decode_layer import tp_head_plan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _layer_inputs(key, m, b, d, h, kvh, hd, ff, s, dt, bias=False):
    ks = jax.random.split(key, 16)
    r = lambda k, shp: (jax.random.normal(k, shp) * 0.1).astype(dt)
    lp = {
        "attn_norm": jnp.ones((m, d), dt) + r(ks[0], (m, d)),
        "wq": r(ks[1], (m, d, h * hd)),
        "wk": r(ks[2], (m, d, kvh * hd)),
        "wv": r(ks[3], (m, d, kvh * hd)),
        "wo": r(ks[4], (m, h * hd, d)),
        "mlp_norm": jnp.ones((m, d), dt) + r(ks[5], (m, d)),
        "w_gate": r(ks[6], (m, d, ff)),
        "w_up": r(ks[7], (m, d, ff)),
        "w_down": r(ks[8], (m, ff, d)),
    }
    if bias:
        lp["bq"] = r(ks[9], (m, h * hd))
        lp["bk"] = r(ks[10], (m, kvh * hd))
        lp["bv"] = r(ks[11], (m, kvh * hd))
    x = r(ks[12], (m, b, d))
    ck = r(ks[13], (m, b, s, kvh, hd))
    cv = r(ks[14], (m, b, s, kvh, hd))
    pos = jax.random.randint(ks[15], (m, b), 0, 2 * s)
    return lp, x, ck, cv, pos.astype(jnp.int32)


def _assert_layer_identical(lp, x, ck, cv, pos, **kw):
    """Kernel vs JITTED oracle, bitwise on all three outputs."""
    want = jax.jit(functools.partial(ref.decode_layer, **kw))(
        lp, x, ck, cv, pos)
    got = ops.decode_layer(lp, x, ck, cv, pos, **kw)
    for g, w, name in zip(got, want, ("x", "k_cache", "v_cache")):
        np.testing.assert_array_equal(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            err_msg=name)


# ---------------------------------------------------------------------------
# kernel vs oracle: bit-identity sweeps
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("h,kvh", [(4, 2), (4, 4), (4, 1), (8, 2)])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_decode_layer_matches_oracle(h, kvh, dt):
    """GQA, MHA (g=1 hits the XLA gemv-vs-gemm path) and MQA, both
    dtypes: the megakernel output and the appended cache are bitwise
    equal to the unfused jitted reference."""
    hd, d, ff, s = 32, 48, 96, 16
    lp, x, ck, cv, pos = _layer_inputs(
        jax.random.PRNGKey(0), 2, 3, d, h, kvh, hd, ff, s, dt)
    _assert_layer_identical(
        lp, x, ck, cv, pos, num_heads=h, head_dim=hd, rope_theta=10000.0)


@pytest.mark.parametrize("theta", [0.0, 10000.0])
def test_decode_layer_qkv_bias(theta):
    """The qwen-style biased QKV path, with and without RoPE."""
    h, kvh, hd, d, ff, s = 4, 4, 16, 32, 64, 8
    lp, x, ck, cv, pos = _layer_inputs(
        jax.random.PRNGKey(1), 2, 2, d, h, kvh, hd, ff, s,
        jnp.float32, bias=True)
    _assert_layer_identical(
        lp, x, ck, cv, pos, num_heads=h, head_dim=hd, rope_theta=theta)


def test_decode_layer_ring_wrap_at_window_boundary():
    """Positions straddling the ring wrap with a sliding window shorter
    than the cache: the in-kernel validity mask (base/slot arithmetic +
    window cut) must agree with the oracle at every position from fresh
    cache through multiple wraps."""
    h, kvh, hd, d, ff, s, window = 4, 2, 16, 32, 64, 16, 12
    lp, x, ck, cv, _ = _layer_inputs(
        jax.random.PRNGKey(2), 1, 4, d, h, kvh, hd, ff, s, jnp.float32)
    for base in (0, s - 2, s, 2 * s + 3):
        pos = (base + jnp.arange(4, dtype=jnp.int32)[None]).reshape(1, 4)
        _assert_layer_identical(
            lp, x, ck, cv, pos, num_heads=h, head_dim=hd,
            rope_theta=10000.0, window=window)


def test_logits_sample_matches_oracle_with_ties():
    """Fused final-norm + unembed + argmax picks the SAME token as
    jnp.argmax over the f32 logits — including first-occurrence
    tie-breaking forced by duplicated vocab columns (and a vocab size
    that is prime, so the V-blocking clamps to one block)."""
    m, b, d, v = 2, 3, 32, 257
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(ks[0], (m, b, d))
    scale = 1.0 + 0.1 * jax.random.normal(ks[1], (m, d))
    head = jax.random.normal(ks[2], (m, d, v))
    head = head.at[:, :, 100].set(head[:, :, 7])   # exact ties
    head = head.at[:, :, 255].set(head[:, :, 7])
    want = jax.jit(functools.partial(ref.logits_sample))(x, scale, head)
    got = ops.logits_sample(x, scale, head)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.dtype == jnp.int32


def test_logits_sample_blocked_vocab_with_masked_tail():
    """The prime vocab over 128-wide vocab blocks: three blocks, the last
    padded past V and masked.  The greedy token is still jnp.argmax's,
    with first-occurrence ties across block boundaries (7 / 100 / 255),
    a tie between the second block and the tail (130 / 256), and a max
    in the tail alone (256)."""
    m, b, d, v = 2, 3, 32, 257
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(ks[0], (m, b, d))
    scale = 1.0 + 0.1 * jax.random.normal(ks[1], (m, d))
    head = jax.random.normal(ks[2], (m, d, v))
    head = head.at[:, :, 100].set(head[:, :, 7])
    head = head.at[:, :, 255].set(head[:, :, 7])
    big = 50.0 * x[:, 0] * scale                   # (m, d): lane 0 wins
    head = head.at[0, :, 130].set(big[0]).at[0, :, 256].set(big[0])
    head = head.at[1, :, 256].set(big[1])
    want = jax.jit(functools.partial(ref.logits_sample))(x, scale, head)
    assert int(want[0, 0]) == 130 and int(want[1, 0]) == 256
    got = ops.logits_sample(x, scale, head, block_v=128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tp_head_plan():
    """The shared head-grouping recipe (megakernel + decode_attn)."""
    assert tp_head_plan(8, 4, 1) is None      # no model axis
    assert tp_head_plan(6, 2, 4) is None      # q heads don't split
    assert tp_head_plan(8, 4, 4) == "kv"      # kv groups split cleanly
    assert tp_head_plan(8, 4, 2) == "kv"
    assert tp_head_plan(8, 1, 4) == "expand"  # MQA: expand then split
    assert tp_head_plan(8, 2, 4) == "expand"


# ---------------------------------------------------------------------------
# model-level: decode_step / decode_step_sample parity per family
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "qwen1.5-0.5b",
                                  "hymba-1.5b"])
def test_decode_step_parity(arch):
    """use_pallas_kernels=True decode_step is bitwise the unfused one
    (hybrid routes only its global-attention layers through the fused
    attention kernel; dense/vlm take the full megakernel scan)."""
    mc = 192 if arch == "hymba-1.5b" else 48
    cfg = registry.get_smoke_config(arch).with_(num_instances=2)
    params = api.init(cfg, jax.random.PRNGKey(0))
    tok = jnp.array([[1, 2], [3, 4]], jnp.int32)[..., None]
    cache = api.make_cache(cfg, 2, 2, mc)
    pos = jnp.array([[5, 9], [0, 17]], jnp.int32)

    run = lambda f: jax.jit(functools.partial(api.decode_step, cfg.with_(
        use_pallas_kernels=f)))(params, cache, tok, pos)
    logits_u, cache_u = run(False)
    logits_f, cache_f = run(True)
    np.testing.assert_array_equal(np.asarray(logits_f, np.float32),
                                  np.asarray(logits_u, np.float32))
    for a, b in zip(jax.tree.leaves(cache_f), jax.tree.leaves(cache_u)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))

    # fused sampling == argmax over the unfused logits
    tok_f, _ = jax.jit(functools.partial(api.decode_step_sample, cfg.with_(
        use_pallas_kernels=True)))(params, cache, tok, pos)
    np.testing.assert_array_equal(
        np.asarray(tok_f), np.asarray(jnp.argmax(logits_u, -1), np.int32))


# ---------------------------------------------------------------------------
# engine-level: greedy streams bit-identical, megakernel vs unfused
# ---------------------------------------------------------------------------


def _build(arch, m=2, **over):
    cfg = registry.get_smoke_config(arch).with_(num_instances=m, **over)
    params = api.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _server(cfg, params, **kw):
    kw.setdefault("slots_per_instance", 2)
    kw.setdefault("max_context", 48)
    kw.setdefault("temperature", 0.0)
    return MultiModelServer(cfg, params, **kw)


def _reqs():
    # mixed budgets: lanes die mid-block under K=8, so the in-kernel
    # cache append runs under the dead-lane alive-mask
    return [
        Request(instance=0, prompt=[1, 2, 3], max_new_tokens=7),
        Request(instance=1, prompt=[4, 5], max_new_tokens=5),
        Request(instance=0, prompt=[7], max_new_tokens=3),
        Request(instance=1, prompt=[3, 3, 3, 3, 3], max_new_tokens=6),
        Request(instance=0, prompt=[2, 2], max_new_tokens=4),
        Request(instance=1, prompt=[9, 8, 7], max_new_tokens=8),
    ]


def _drain(server, reqs):
    for r in reqs:
        server.submit(Request(r.instance, list(r.prompt), r.max_new_tokens))
    return {r.request_id: r.tokens for r in server.run_until_drained()}


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "qwen1.5-0.5b"])
@pytest.mark.parametrize("k", [1, 8])
def test_greedy_streams_identical_megakernel_vs_unfused(arch, k):
    """Whole greedy streams, token for token, K ∈ {1, 8}: the fused
    decode-layer scan + fused sampling vs the per-op path.  K=8 with
    mixed budgets exercises dead-lane freezing with in-kernel append."""
    cfg, params = _build(arch)
    want = _drain(
        _server(cfg.with_(use_pallas_kernels=False), params,
                decode_steps=k), _reqs())
    assert want and all(len(t) > 0 for t in want.values())
    got = _drain(
        _server(cfg.with_(use_pallas_kernels=True), params,
                decode_steps=k), _reqs())
    assert got == want


def test_greedy_streams_identical_hybrid():
    """hymba rides the fused attention kernel only on its global-attn
    layers — streams still bitwise match the unfused engine."""
    cfg, params = _build("hymba-1.5b")
    run = lambda f: _drain(
        _server(cfg.with_(use_pallas_kernels=f), params,
                max_context=192, decode_steps=4), _reqs())
    want = run(False)
    assert want and all(len(t) > 0 for t in want.values())
    assert run(True) == want


def test_greedy_streams_identical_async():
    """The async frontend over a megakernel K=4 engine streams exactly
    the unfused sync K=1 tokens."""
    import asyncio

    cfg, params = _build("tinyllama-1.1b")
    want = _drain(
        _server(cfg.with_(use_pallas_kernels=False), params,
                decode_steps=1), _reqs())

    async def run(server, reqs):
        engine = AsyncEngine(server)

        async def client(r):
            stream = await engine.submit(
                Request(r.instance, list(r.prompt), r.max_new_tokens))
            toks = [t async for t in stream]
            res = await stream.result()
            assert res.status == "ok"
            return stream.request_id, toks

        out = await asyncio.gather(*(client(r) for r in reqs))
        await engine.aclose()
        return dict(out)

    got = asyncio.run(run(
        _server(cfg.with_(use_pallas_kernels=True), params,
                decode_steps=4), _reqs()))
    assert got == want


# ---------------------------------------------------------------------------
# 8-device mesh subprocess: sharded megakernel parity
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_megakernel_streams_identical_on_mesh():
    """No-mesh unfused == 8-device mesh megakernel, K ∈ {1, 8}, on both
    mesh shapes: (2, 4) forces the data-local shard_map fallback (kv
    heads don't split 4 ways) and (4, 2) takes the 2-phase TP split."""
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro.launch.compat import make_host_mesh
        import numpy as np
        from repro import api
        from repro.configs import registry
        from repro.serving import MultiModelServer, Request

        assert len(jax.devices()) == 8, jax.devices()

        M = 2
        cfg = registry.get_smoke_config("tinyllama-1.1b").with_(
            num_instances=M, dtype="float32", param_dtype="float32")
        params = api.init(cfg, jax.random.PRNGKey(0))

        def serve(mesh, K, fused):
            srv = MultiModelServer(
                cfg.with_(use_pallas_kernels=fused), params,
                slots_per_instance=2, max_context=64,
                mesh=mesh, decode_steps=K, temperature=0.0)
            rng = np.random.default_rng(0)
            for i in range(6):
                prompt = rng.integers(
                    1, cfg.vocab_size, size=int(rng.integers(2, 8))).tolist()
                srv.submit(Request(instance=i % M, prompt=prompt,
                                   max_new_tokens=4 + (i % 3)))
            res = sorted(srv.run_until_drained(), key=lambda r: r.request_id)
            return [r.tokens for r in res]

        ref = serve(None, 1, False)
        assert all(len(t) > 0 for t in ref), ref
        for shape in ((2, 4), (4, 2)):
            mesh = make_host_mesh(shape)
            assert serve(mesh, 1, True) == ref, shape
            assert serve(mesh, 8, True) == ref, shape
        print("megakernel mesh streams OK")
        """
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, cwd=REPO, timeout=900,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "megakernel mesh streams OK" in r.stdout


@pytest.mark.slow
def test_decode_attention_sharded_gqa_mqa():
    """Satellite 1: decode_attention_sharded under every tp_head_plan
    branch — "kv" (GQA groups split), "expand" (MQA), and the
    data-local fallback (q heads don't split) — bitwise equal to the
    plain kernel with no mesh."""
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro.launch.compat import make_host_mesh
        import jax.numpy as jnp
        import numpy as np
        from repro.kernels.decode_attn import (
            decode_attention, decode_attention_sharded)
        from repro.kernels.decode_layer import tp_head_plan
        from repro.launch.shardings import serve_rules

        assert len(jax.devices()) == 8
        mesh = make_host_mesh((2, 4))
        rules = serve_rules(mesh)

        cases = {(8, 4): "kv", (8, 1): "expand", (2, 1): None}
        for (h, kvh), plan in cases.items():
            assert tp_head_plan(h, kvh, 4) == plan, (h, kvh)
            m, b, s, hd = 2, 4, 32, 16
            ks = jax.random.split(jax.random.PRNGKey(h * 10 + kvh), 4)
            q = jax.random.normal(ks[0], (m, b, h, hd))
            k = jax.random.normal(ks[1], (m, b, s, kvh, hd))
            v = jax.random.normal(ks[2], (m, b, s, kvh, hd))
            kv_len = jax.random.randint(ks[3], (m, b), 1, s + 1)
            want = decode_attention(q, k, v, kv_len)
            with jax.set_mesh(mesh), rules:
                got = decode_attention_sharded(
                    q, k, v, kv_len, rules=rules)
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(want), err_msg=str((h, kvh)))
        print("sharded gqa/mqa decode attention OK")
        """
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, cwd=REPO, timeout=900,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "sharded gqa/mqa decode attention OK" in r.stdout
