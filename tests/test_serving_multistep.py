"""Multi-step decode (DESIGN.md §6.6): K fused decode+sample steps per
device call with on-device stop handling.

The ISSUE-7 contract: greedy token streams are bit-identical for K=1
vs K ∈ {2, 4, 8} (dense + one recurrent family, through the sync loop,
the async frontend, and an 8-device CPU mesh subprocess); a lane whose
stop condition hits mid-block freezes on device — its cache rows and
position stop advancing exactly where the one-call-per-token protocol
would stop them; cancellation landing while a block is in flight keeps
its between-steps semantics (partial tokens kept, slot refilled); and
in pure-decode steady state the engine issues exactly
ceil(max_new / K) decode device calls per request wave.
"""
import asyncio
import math
import os
import re
import subprocess
import sys
import textwrap

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.configs import registry
from repro.serving import AsyncEngine, MultiModelServer, Request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build(arch, m=2):
    cfg = registry.get_smoke_config(arch).with_(num_instances=m)
    params = api.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _server(cfg, params, **kw):
    kw.setdefault("slots_per_instance", 2)
    kw.setdefault("max_context", 48)
    kw.setdefault("temperature", 0.0)
    return MultiModelServer(cfg, params, **kw)


def _reqs():
    # more requests than the 4 grid slots and mixed budgets, so the
    # waves exercise mid-block finishes, refills, AND the adaptive
    # horizon's backlog shrink while draining
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


# ---------------------------------------------------------------------------
# K-parity: greedy streams bit-identical across horizons
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "xlstm-1.3b"])
def test_greedy_streams_identical_across_k_sync(arch):
    """K=1 vs K ∈ {2, 4, 8}: same requests, same greedy streams, token
    for token — for a KV-cache family and a recurrent-state family."""
    cfg, params = _build(arch)
    want = _drain(_server(cfg, params, decode_steps=1), _reqs())
    assert want and all(len(t) > 0 for t in want.values())
    for K in (2, 4, 8):
        got = _drain(_server(cfg, params, decode_steps=K), _reqs())
        assert got == want, f"K={K} diverged from K=1"


def test_streams_identical_with_adaptive_horizon_off():
    """The adaptive policy only picks WHICH k each block runs — the
    on-device stop mask alone guarantees parity, proven by forcing the
    full horizon every block."""
    cfg, params = _build("tinyllama-1.1b")
    want = _drain(_server(cfg, params, decode_steps=1), _reqs())
    got = _drain(
        _server(cfg, params, decode_steps=8, adaptive_horizon=False),
        _reqs(),
    )
    assert got == want


def test_greedy_streams_identical_across_k_async():
    """The async frontend over a K=4 engine streams exactly the K=1
    sync tokens: the host unroll keeps per-token on_token semantics."""
    cfg, params = _build("tinyllama-1.1b")
    want = _drain(_server(cfg, params, decode_steps=1), _reqs())

    async def run(server, reqs):
        engine = AsyncEngine(server)

        async def client(r):
            stream = await engine.submit(
                Request(r.instance, list(r.prompt), r.max_new_tokens))
            toks = [t async for t in stream]
            res = await stream.result()
            assert res.status == "ok"
            assert toks == res.tokens
            return stream.request_id, toks

        out = await asyncio.gather(*(client(r) for r in reqs))
        await engine.aclose()
        return dict(out)

    got = asyncio.run(run(_server(cfg, params, decode_steps=4), _reqs()))
    assert got == want


# ---------------------------------------------------------------------------
# device-call accounting: one dispatch per block, ceil(tokens / K) blocks
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k,max_new", [(1, 10), (2, 10), (4, 10), (8, 10),
                                       (4, 8), (8, 3)])
def test_decode_device_calls_ceil_tokens_over_k(k, max_new):
    """Pure-decode steady state (no backlog, prefill done): the engine
    dispatches exactly ceil(max_new / K) fused decode blocks, each
    exactly ONE call through server._step."""
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params, decode_steps=k)
    calls = {"n": 0}
    inner = server._step

    def counting_step(*a, **kw):
        calls["n"] += 1
        return inner(*a, **kw)

    server._step = counting_step
    # one request per instance: both admit in one wave, decode together
    reqs = [Request(instance=i, prompt=[3 + i, 4], max_new_tokens=max_new)
            for i in range(cfg.num_instances)]
    out = _drain(server, reqs)
    assert all(len(t) == max_new for t in out.values())
    want_calls = math.ceil(max_new / k)
    assert calls["n"] == want_calls == server.steps
    assert server.metrics.decode_calls == want_calls
    # scan steps: every block runs its full static length
    assert server.metrics.decode_steps == want_calls * min(
        k, server.decode_steps)
    snap = server.metrics.snapshot()
    assert snap["decode_device_calls"] == want_calls
    assert snap["decode_steps"] >= snap["decode_device_calls"]
    assert snap["tokens_per_device_call"] == pytest.approx(
        cfg.num_instances * max_new / want_calls)


# ---------------------------------------------------------------------------
# on-device stop handling: mid-block freeze of cache / tokens
# ---------------------------------------------------------------------------


def _max_context(cfg):
    # a hybrid grid must hold its meta tokens plus a whole SWA window
    if cfg.family == "hybrid":
        from repro.models import hybrid
        return hybrid.NUM_META_TOKENS + hybrid.swa_window(cfg) + 16
    return 48


@pytest.mark.parametrize("arch,dead_at_entry", [
    ("tinyllama-1.1b", False),
    ("tinyllama-1.1b", True),
    ("hymba-1.5b", True),
    ("xlstm-1.3b", True),
])
def test_midblock_stop_freezes_cache_and_tokens(arch, dead_at_entry):
    """Drive the block function directly: a lane whose budget runs out
    after 2 of 4 scan steps must leave EXACTLY the cache a 2-step block
    leaves (junk steps masked), with its tokens frozen and the emitted
    mask marking the junk rows; a live lane keeps decoding.  A lane dead
    at block entry leaves its cache/state as it found it.  Run for a
    KV-cache family, a hybrid (K/V rows plus mamba states) and a
    recurrent-state family."""
    cfg, params = _build(arch)
    mk = lambda: _server(cfg, params, decode_steps=4,
                         max_context=_max_context(cfg))
    srv = mk()
    M, B = srv.m, srv.b
    tok = jnp.ones((M, B), jnp.int32)
    pos = jnp.zeros((M, B), jnp.int32)
    key = jax.random.PRNGKey(7)
    alive = jnp.ones((M, B), bool)
    if dead_at_entry:
        alive = alive.at[1, 1].set(False)
    # slot (0, 0) has budget for 2 steps; everyone else rides the full 4
    rem = jnp.full((M, B), 10, jnp.int32).at[0, 0].set(2)

    before = jax.tree.map(np.asarray, srv.cache)
    toks4, em4, ok4, cache4, _ = srv._step(
        srv.params, srv.cache, tok, pos, key, alive, rem, 4)
    srv2 = mk()
    toks2, em2, ok2, cache2, _ = srv2._step(
        srv2.params, srv2.cache, tok, pos, key, alive, rem, 2)

    em4 = np.asarray(em4)
    toks4, toks2 = np.asarray(toks4), np.asarray(toks2)
    # emitted = alive at entry of each scan step: 2 real rows, 2 junk
    assert em4[:, 0, 0].tolist() == [True, True, False, False]
    assert em4[:, 1, 0].all()
    # a healthy decode never trips the NaN/Inf token guard (§6.8)
    assert np.asarray(ok4).all() and np.asarray(ok2).all()
    # frozen token after the stop; real rows match the 2-step block
    assert (toks4[:2] == toks2).all()
    assert toks4[2, 0, 0] == toks4[1, 0, 0] == toks4[3, 0, 0]

    def same(x, y):
        for a, b in zip(jax.tree.leaves(x), jax.tree.leaves(y)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # the stopped lane's cache is bit-identical to the 2-step block's —
    # the junk steps wrote nothing
    same(api.take_state(cfg, cache4, 0, 0), api.take_state(cfg, cache2, 0, 0))
    # while a live lane's cache DID advance past the 2-step state
    l4 = jax.tree.leaves(api.take_state(cfg, cache4, 1, 0))
    l2 = jax.tree.leaves(api.take_state(cfg, cache2, 1, 0))
    assert any((np.asarray(a) != np.asarray(b)).any()
               for a, b in zip(l4, l2))
    if dead_at_entry:
        # the dead lane emitted nothing, kept its token, and left its
        # cache/state byte for byte as the block found it
        assert not em4[:, 1, 1].any()
        assert (toks4[:, 1, 1] == 1).all()
        same(api.take_state(cfg, cache4, 1, 1),
             api.take_state(cfg, before, 1, 1))


def test_eos_midblock_matches_k1():
    """EOS landing mid-block: pick a token the greedy stream emits at a
    non-boundary index as eos_id — K=1 and K=4 must stop at the same
    token with finish_reason='stop', other requests unaffected."""
    cfg, params = _build("tinyllama-1.1b")
    probe = _server(cfg, params)
    probe.submit(Request(instance=0, prompt=[5, 6, 7], max_new_tokens=8))
    ref = probe.run_until_drained()[0].tokens
    eos = ref[2]                      # index 2: inside a K=4 block

    def run(K):
        srv = _server(cfg, params, decode_steps=K, eos_id=eos)
        srv.submit(Request(instance=0, prompt=[5, 6, 7], max_new_tokens=8))
        srv.submit(Request(instance=1, prompt=[4, 4], max_new_tokens=6))
        res = {r.request_id: r for r in srv.run_until_drained()}
        return res

    r1, r4 = run(1), run(4)
    assert set(r1) == set(r4)
    for rid in r1:
        assert r1[rid].tokens == r4[rid].tokens
        assert r1[rid].finish_reason == r4[rid].finish_reason
    stopped = r1[0]
    assert stopped.finish_reason == "stop"
    assert stopped.tokens[-1] == eos
    assert len(stopped.tokens) < 8


# ---------------------------------------------------------------------------
# the freeze costs no cache-sized work: the compiled block programs
# ---------------------------------------------------------------------------


def _cache_sized_ops(srv, k):
    """Instruction counts, by opcode, of the compiled k-step decode block
    (cache donated) whose result has as many elements as a cache leaf."""
    m, b = srv.m, srv.b
    z = jnp.zeros((m, b), jnp.int32)
    text = srv._make_block(k).lower(
        srv.params, srv.cache, z, z, jax.random.PRNGKey(0),
        jnp.ones((m, b), bool), z).compile().as_text()
    sizes = {leaf.size for leaf in jax.tree.leaves(srv.cache)}
    counts: dict[str, int] = {}
    for shape, op in re.findall(
            r"= \w+\[([\d,]*)\]\S* ([\w-]+)\(", text):
        if math.prod(int(d) for d in shape.split(",") if d) in sizes:
            counts[op] = counts.get(op, 0) + 1
    return counts


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "hymba-1.5b"])
def test_block_freeze_adds_no_cache_sized_select_or_copy(arch):
    """A stopped lane freezes at its K/V row or state write, not by a
    select over the whole cache: the k=4 block has no cache-sized
    select, and no more cache-sized copies than the k=1 block."""
    cfg, params = _build(arch)
    srv = _server(cfg, params, decode_steps=4, donate=True,
                  max_context=_max_context(cfg))
    one, four = _cache_sized_ops(srv, 1), _cache_sized_ops(srv, 4)
    assert four.get("select", 0) == 0, four
    assert four.get("copy", 0) <= one.get("copy", 0), (one, four)


# ---------------------------------------------------------------------------
# cancellation landing mid-block
# ---------------------------------------------------------------------------


def test_cancel_mid_block_async():
    """A cancel issued while K=4 blocks are landing applies at the next
    step boundary: the client keeps the partial tokens, the slot frees,
    and the freed slot serves a follow-up request correctly."""
    cfg, params = _build("tinyllama-1.1b")
    server = _server(cfg, params, decode_steps=4)

    async def run():
        engine = AsyncEngine(server)
        stream = await engine.submit(
            Request(instance=0, prompt=[1, 2, 3], max_new_tokens=30))
        got = []
        async for t in stream:
            got.append(t)
            if len(got) == 5:         # one token into the second block
                await engine.cancel(stream.request_id)
        res = await stream.result()
        # the freed slot must serve a fresh request end to end
        s2 = await engine.submit(
            Request(instance=0, prompt=[1, 2, 3], max_new_tokens=4))
        toks2 = [t async for t in s2]
        res2 = await s2.result()
        await engine.aclose()
        return got, res, toks2, res2

    got, res, toks2, res2 = asyncio.run(run())
    assert res.status == "cancelled"
    # partial tokens kept; cancel applied between blocks, so the stream
    # saw at least the 5 tokens it consumed and far fewer than max_new
    assert res.tokens[:len(got)] == got
    assert 5 <= len(res.tokens) <= 12
    assert res2.status == "ok" and len(toks2) == 4
    assert not server.busy()

    # and the same follow-up stream through a K=1 engine is identical
    # (the cancelled request left no state behind)
    want = _drain(_server(cfg, params, decode_steps=1),
                  [Request(instance=0, prompt=[1, 2, 3], max_new_tokens=4)])
    assert toks2 == list(want.values())[0]


# ---------------------------------------------------------------------------
# 8-device mesh subprocess: sharded multi-step parity
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_multistep_streams_identical_on_mesh():
    """No-mesh K=1 == 8-device (2, 4) mesh K=1 == mesh K=8: the block's
    scan, stop mask and slot-select all run sharded and exact."""
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
        mesh = make_host_mesh((2, 4))

        M = 2
        cfg = registry.get_smoke_config("tinyllama-1.1b").with_(
            num_instances=M, dtype="float32", param_dtype="float32")
        params = api.init(cfg, jax.random.PRNGKey(0))

        def serve(mesh, K):
            srv = MultiModelServer(
                cfg, params, slots_per_instance=2, max_context=64,
                mesh=mesh, decode_steps=K)
            rng = np.random.default_rng(0)
            for i in range(6):
                prompt = rng.integers(
                    1, cfg.vocab_size, size=int(rng.integers(2, 8))).tolist()
                srv.submit(Request(instance=i % M, prompt=prompt,
                                   max_new_tokens=4 + (i % 3)))
            res = sorted(srv.run_until_drained(), key=lambda r: r.request_id)
            return [r.tokens for r in res]

        ref = serve(None, 1)
        assert all(len(t) > 0 for t in ref), ref
        assert serve(mesh, 1) == ref
        assert serve(mesh, 8) == ref
        print("multistep mesh streams OK")
        """
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, cwd=REPO, timeout=900,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "multistep mesh streams OK" in r.stdout
