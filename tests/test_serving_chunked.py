"""Unified chunked-prefill runtime: per-family greedy-stream equality
chunked-vs-exact (including hybrid, previously untestable because exact
admission compiled per prompt length), compiled-shape caps, mid-prompt
SWA-ring chain correctness at chunk boundaries, MoE capacity-mask
routing parity, and cross-mesh stream identity for hybrid + moe.

The reference stream for each request is the family's EXACT-length
prefill followed by a greedy ``decode_step`` loop on that instance's
isolated (M=1) weights — the path the old serving layer used for
families it could serve exactly.  The chunked runtime must reproduce it
for every family with at most two compiled prefill shapes.
"""
from __future__ import annotations

import functools
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import api
from repro.configs import registry
from repro.models import common as C
from repro.serving import MultiModelServer, Request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mk_batch(cfg, prompt):
    batch = {"tokens": jnp.asarray(prompt, jnp.int32)[None, None]}
    dt = jnp.dtype(cfg.dtype)
    if cfg.family == "vlm":
        batch["image_embeds"] = jnp.zeros(
            (1, 1, cfg.num_image_patches, cfg.vision_embed_dim), dt)
    elif cfg.family == "audio":
        batch["frames"] = jnp.zeros(
            (1, 1, cfg.num_audio_frames, cfg.d_model), dt)
    return batch


def _reference_stream(cfg, pi, prompt, max_new, max_context):
    """Greedy stream from exact-length prefill + decode_step (M=1).

    Like the engine (for every family), the reference prefills
    ``prompt[:-1]`` and re-decodes the last prompt token as its first
    decode step — recurrent state must not integrate that token twice,
    and moe capacity derives from the token count actually prefilled."""
    n = len(prompt)
    prefix = api.prefill_prefix_len(cfg)
    if n > 1:
        kw = {} if cfg.family in ("ssm", "hybrid") else {"cache_len": max_context}
        _, cache = api.prefill(cfg, pi, _mk_batch(cfg, prompt[:-1]), **kw)
    else:
        cache = api.make_cache(cfg, 1, 1, max_context)
    tok, pos = prompt[-1], prefix + n - 1
    out = []
    for _ in range(max_new):
        logits, cache = api.decode_step(
            cfg, pi, cache,
            jnp.full((1, 1, 1), tok, jnp.int32), jnp.full((1, 1), pos, jnp.int32),
        )
        tok = int(jnp.argmax(logits[0, 0]))
        out.append(tok)
        pos += 1
    return out


FAMILY_CASES = [
    # (arch, cfg overrides, max_context, prompt lengths)
    ("tinyllama-1.1b", {}, 64, (1, 3, 7, 12, 18)),
    ("olmoe-1b-7b", {}, 64, (1, 3, 7, 12, 18)),
    # prefix families need n >= 2 for the REFERENCE only (an n=1 prompt
    # would leave the reference's image/frame/meta prefix unprefilled;
    # the serving path itself handles n=1, covered in test_serving.py)
    ("internvl2-26b", {}, 64, (2, 3, 7, 12, 18)),
    ("whisper-small", {}, 64, (2, 3, 7, 12, 18)),
    ("xlstm-1.3b", {}, 64, (1, 3, 7, 12, 18)),
    # num_layers=4 so the config has real SWA layers ({0,2,3} global)
    ("hymba-1.5b", {"num_layers": 4}, 200, (2, 5, 11, 18)),
]


@pytest.mark.parametrize("arch,cfg_kw,max_context,lengths",
                         FAMILY_CASES, ids=[c[0] for c in FAMILY_CASES])
def test_family_stream_chunked_equals_exact(arch, cfg_kw, max_context, lengths):
    """Greedy token streams: chunked serving == exact-length reference,
    for mixed prompt lengths, with at most 2 compiled prefill shapes."""
    cfg = registry.get_smoke_config(arch).with_(num_instances=2, **cfg_kw)
    params = api.init(cfg, jax.random.PRNGKey(0))
    server = MultiModelServer(
        cfg, params, slots_per_instance=2, max_context=max_context,
        temperature=0.0, prefill_chunk=5, prefill_lanes=3, chunk_budget=2,
    )
    rng = np.random.default_rng(0)
    reqs = [
        Request(instance=i % 2,
                prompt=rng.integers(1, cfg.vocab_size, size=l).tolist(),
                max_new_tokens=4)
        for i, l in enumerate(lengths)
    ]
    ids = [server.submit(r) for r in reqs]
    results = {r.request_id: r for r in server.run_until_drained()}
    assert set(results) == set(ids)
    # tail folding: the padded final chunk removes the single-token tail
    # shape — ONE compiled prefill shape per family, down from 2
    assert server.prefill.compiled_shapes <= 1, server.prefill.compiled_shapes

    ax = api.axes(cfg)
    for req, rid in zip(reqs, ids):
        pi = C.take_instance(params, ax, req.instance)
        want = _reference_stream(cfg, pi, req.prompt, req.max_new_tokens,
                                 max_context)
        assert results[rid].tokens == want, (arch, req.prompt, rid)


def test_hybrid_mixed_lengths_one_compile():
    """The acceptance invariant: a mixed-length hybrid workload compiles
    exactly ONE prefill shape (the folded chunk) — admission is
    O(compiled-shapes) = O(1) per family, not O(distinct lengths)."""
    from repro.serving.prefill import ChunkedPrefill

    cfg = registry.get_smoke_config("hymba-1.5b").with_(num_instances=2)
    params = api.init(cfg, jax.random.PRNGKey(0))
    cp = ChunkedPrefill(cfg, max_context=200, chunk=16, lanes=2)
    rng = np.random.default_rng(1)
    for l in (1, 2, 4, 9, 17, 23, 31):
        cp.run(params, [Request(instance=l % 2,
                                prompt=rng.integers(1, 250, size=l).tolist())])
    assert cp.compiled_shapes == 1, cp.compiled_shapes


def test_mixed_length_batch_device_calls_exactly_ceil():
    """A mixed-length admission batch drains in exactly ceil(L_max/chunk)
    device calls — every lane rides every call, the shorter ones on
    padded final chunks; zero per-token tail calls."""
    import math

    from repro.serving.prefill import ChunkedPrefill

    cfg = registry.get_smoke_config("tinyllama-1.1b").with_(num_instances=2)
    params = api.init(cfg, jax.random.PRNGKey(0))
    chunk = 8
    cp = ChunkedPrefill(cfg, max_context=64, chunk=chunk, lanes=4)
    lengths = (5, 9, 20, 26)                 # totals 4, 8, 19, 25
    rng = np.random.default_rng(2)
    for l in lengths:
        cp.start(Request(instance=l % 2,
                         prompt=rng.integers(1, cfg.vocab_size, size=l).tolist()))
    done = cp.advance(params, budget=1_000_000)
    assert len(done) == len(lengths)
    want_calls = math.ceil(max(l - 1 for l in lengths) / chunk)
    assert cp.device_calls == want_calls, (cp.device_calls, want_calls)
    assert cp.compiled_shapes == 1, cp.compiled_shapes


def test_donated_paths_match_non_donated_cpu():
    """Donation (carry + grid cache updated in place) forced ON — on CPU
    the aliasing is not honored but the donated arrays ARE invalidated,
    so this proves the serving programs never read a donated buffer after
    its donation; greedy streams must equal the non-donated path."""
    import warnings

    for arch, ctx in (("tinyllama-1.1b", 64), ("xlstm-1.3b", 64)):
        cfg = registry.get_smoke_config(arch).with_(num_instances=2)
        params = api.init(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(3)
        reqs = [Request(instance=i % 2,
                        prompt=rng.integers(1, cfg.vocab_size,
                                            size=int(l)).tolist(),
                        max_new_tokens=4)
                for i, l in enumerate((1, 3, 7, 12, 18))]

        def serve(donate):
            srv = MultiModelServer(
                cfg, params, slots_per_instance=2, max_context=ctx,
                temperature=0.0, prefill_chunk=5, prefill_lanes=3,
                chunk_budget=2, donate=donate,
            )
            for r in reqs:
                srv.submit(Request(r.instance, list(r.prompt),
                                   r.max_new_tokens))
            res = sorted(srv.run_until_drained(), key=lambda x: x.request_id)
            return [r.tokens for r in res], srv

        plain, _ = serve(donate=False)
        with warnings.catch_warnings():
            # XLA:CPU reports the unusable donations; semantics still hold
            warnings.simplefilter("ignore")
            donated, srv = serve(donate=True)
        assert donated == plain, (arch, donated, plain)
        assert srv.prefill.compiled_shapes == 1


@pytest.mark.parametrize("arch,ctx,total,pallas", [
    ("xlstm-1.3b", 64, 10, False),
    ("hymba-1.5b", 200, 134, False),
    # the kernel-routed paths: hybrid's chunk attention goes through the
    # Pallas chunk_prefill_attn kernel and xlstm's sLSTM through the
    # Pallas cell — the ±1e30 gate-forcing must neutralize junk steps
    # inside the kernels too (interpret mode, hence slow)
    pytest.param("xlstm-1.3b", 64, 10, True, marks=pytest.mark.slow),
    pytest.param("hymba-1.5b", 200, 134, True, marks=pytest.mark.slow),
], ids=["xlstm", "hybrid", "xlstm-pallas", "hybrid-pallas"])
def test_padded_final_chunk_recurrent_carry_matches_exact(arch, ctx, total, pallas):
    """Recurrent carries through a PADDED final chunk (junk suffix +
    validity mask) equal the exact-length chunking — per state leaf, for
    both recurrent families (xLSTM cells, hybrid mamba+ring)."""
    kw = {"num_instances": 1, "dtype": "float32", "param_dtype": "float32",
          "use_pallas_kernels": pallas}
    if arch == "hymba-1.5b":
        kw["num_layers"] = 4
    cfg = registry.get_smoke_config(arch).with_(**kw)
    params = api.init(cfg, jax.random.PRNGKey(0))
    prefix = api.prefill_prefix_len(cfg)
    rng = np.random.default_rng(4)
    prompt = rng.integers(1, cfg.vocab_size, size=total - prefix).tolist()
    chunk = 4
    assert total % chunk != 0          # the final chunk is partial

    def toks_at(i, c):
        t = np.zeros((1, 1, c), np.int32)
        for j in range(c):
            p = i + j
            if prefix <= p < total:
                t[0, 0, j] = prompt[p - prefix]
        return jnp.asarray(t)

    # jitted, as the serving runtime runs it: one compile per chunk width
    # instead of an eager dispatch (and executable) per op per chunk
    step = jax.jit(functools.partial(api.prefill_chunk, cfg))
    exact = api.init_chunk_carry(cfg, 1, 1, ctx)
    i = 0
    while i < total:
        c = min(chunk, total - i)
        exact = step(params, {"tokens": toks_at(i, c)},
                     exact, jnp.full((1, 1), i, jnp.int32))
        i += c

    padded = api.init_chunk_carry(cfg, 1, 1, ctx)
    i = 0
    while i < total:
        rem = min(chunk, total - i)
        valid = jnp.asarray((np.arange(chunk) < rem)[None, None])
        padded = step(params, {"tokens": toks_at(i, chunk), "valid": valid},
                      padded, jnp.full((1, 1), i, jnp.int32))
        i += rem

    flat_e = jax.tree_util.tree_leaves_with_path(exact)
    flat_p = jax.tree.leaves(padded)
    for (path, le), lp_ in zip(flat_e, flat_p):
        np.testing.assert_allclose(
            np.asarray(lp_, np.float32), np.asarray(le, np.float32),
            rtol=1e-5, atol=1e-5,
            err_msg=f"{arch} leaf {jax.tree_util.keystr(path)}",
        )


def test_hybrid_swa_ring_chains_across_chunk_boundaries():
    """Mid-prompt chain correctness for the SWA ring: a prompt LONGER
    than the sliding window (the ring wraps mid-prompt, evicting early
    positions) must produce the same next-token logits as one
    exact-length prefill.  This is the capability the old exact-length
    hybrid path could not provide."""
    from repro.models import hybrid as H

    cfg = registry.get_smoke_config("hymba-1.5b").with_(num_layers=4)
    params = api.init(cfg, jax.random.PRNGKey(0))
    w = H.swa_window(cfg)
    prompt = list((np.arange(w + 13) % 250 + 1).astype(int))  # wraps the ring
    r = H.NUM_META_TOKENS
    max_context = r + len(prompt) + 8
    total = r + len(prompt)

    carry = api.init_chunk_carry(cfg, 1, 1, max_context)
    i, chunk = 0, 16
    while i < total:
        c = chunk if total - i >= chunk else 1
        toks = np.zeros((1, 1, c), np.int32)
        for j in range(c):
            if i + j >= r:
                toks[0, 0, j] = prompt[i + j - r]
        carry = api.prefill_chunk(
            cfg, params, {"tokens": jnp.asarray(toks)}, carry,
            jnp.full((1, 1), i, jnp.int32),
        )
        i += c

    _, exact = api.prefill(cfg, params, _mk_batch(cfg, prompt))
    tok = jnp.full((1, 1, 1), prompt[-1], jnp.int32)
    pos = jnp.full((1, 1), total - 1, jnp.int32)
    l_exact, _ = api.decode_step(cfg, params, exact, tok, pos)
    l_chunk, _ = api.decode_step(cfg, params, carry["cache"], tok, pos)
    np.testing.assert_allclose(np.asarray(l_chunk), np.asarray(l_exact),
                               rtol=5e-4, atol=5e-4)


def test_submit_accepts_to_cache_length_and_errors_past_it():
    """Chunked admission is length-agnostic: any prompt whose positions
    (prefix + tokens) fit max_context is accepted — no bucket-derived
    limit — and one past that raises a clean ValueError."""
    cfg = registry.get_smoke_config("tinyllama-1.1b").with_(num_instances=1)
    params = api.init(cfg, jax.random.PRNGKey(0))
    server = MultiModelServer(
        cfg, params, slots_per_instance=1, max_context=48,
        temperature=0.0, prefill_chunk=8,
    )
    limit = server.prefill.max_prompt_len()
    assert limit == 48
    server.submit(Request(instance=0, prompt=[1] * limit, max_new_tokens=1))
    results = server.run_until_drained()
    assert len(results) == 1 and len(results[0].tokens) >= 1
    with pytest.raises(ValueError, match="exceeds the serving context"):
        server.submit(Request(instance=0, prompt=[1] * (limit + 1)))


def test_tail_lane_not_starved_by_chunkable_lanes():
    """Chunk and tail rounds alternate: a lane one call from completion
    finishes within two budget units even while another lane still has
    many full chunks left."""
    from repro.serving.prefill import ChunkedPrefill

    cfg = registry.get_smoke_config("tinyllama-1.1b").with_(num_instances=1)
    params = api.init(cfg, jax.random.PRNGKey(0))
    cp = ChunkedPrefill(cfg, max_context=64, chunk=4, lanes=2)
    short = Request(instance=0, prompt=[1, 2])          # 1 tail call left
    long = Request(instance=0, prompt=list(range(1, 30)))  # 7 full chunks
    cp.start(long)
    cp.start(short)
    done = cp.advance(params, budget=2)
    assert any(req is short for req, _ in done), "tail lane was starved"


def test_context_smaller_than_learned_prefix_rejected_at_construction():
    """A context that can't even hold the learned prefix (vlm image
    patches) fails loudly at construction, not with a nonsensical
    negative limit at submit time."""
    from repro.serving.prefill import ChunkedPrefill

    cfg = registry.get_smoke_config("internvl2-26b")
    with pytest.raises(ValueError, match="learned prefix"):
        ChunkedPrefill(cfg, max_context=cfg.num_image_patches)


# ---------------------------------------------------------------------------
# MoE capacity masks
# ---------------------------------------------------------------------------


def _layer0(params):
    return jax.tree.map(lambda t: t[0], params["layers"])


def test_moe_chunked_routing_matches_exact():
    """Chained counts + real-length capacity make chunked routing route
    (and drop) exactly as one exact-length pass — even at a capacity
    factor low enough to force drops."""
    from repro.models import moe

    cfg = registry.get_smoke_config("olmoe-1b-7b").with_(capacity_factor=0.5)
    params = api.init(cfg, jax.random.PRNGKey(0))
    lp = _layer0(params)
    s = 12
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 1, s, cfg.d_model))
    exact, _ = moe.moe_mlp(cfg, lp, x)

    limit = jnp.full((1, 1), moe.capacity(cfg, s), jnp.int32)
    counts = jnp.zeros((1, 1, cfg.num_experts), jnp.int32)
    outs = []
    for i in range(0, s, 4):
        y, _, counts = moe.moe_mlp(cfg, lp, x[:, :, i:i + 4],
                                   counts=counts, limit=limit)
        outs.append(y)
    got = jnp.concatenate(outs, axis=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exact),
                               rtol=1e-5, atol=1e-5)


def test_moe_validity_mask_matches_unpadded():
    """Padded tokens masked out of routing neither consume capacity nor
    shift real tokens' positions-in-expert: a padded call with a
    validity mask equals the unpadded exact pass (the old bucketed-path
    caveat, closed)."""
    from repro.models import moe

    cfg = registry.get_smoke_config("olmoe-1b-7b").with_(capacity_factor=0.5)
    params = api.init(cfg, jax.random.PRNGKey(0))
    lp = _layer0(params)
    s_real, s_pad = 8, 12
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 1, s_pad, cfg.d_model))
    limit = jnp.full((1, 1), moe.capacity(cfg, s_real), jnp.int32)
    counts = jnp.zeros((1, 1, cfg.num_experts), jnp.int32)
    valid = (jnp.arange(s_pad) < s_real)[None, None]

    padded, _, new_counts = moe.moe_mlp(cfg, lp, x, valid=valid,
                                        counts=counts, limit=limit)
    exact, _, _ = moe.moe_mlp(cfg, lp, x[:, :, :s_real],
                              counts=counts, limit=limit)
    np.testing.assert_allclose(np.asarray(padded[:, :, :s_real]),
                               np.asarray(exact), rtol=1e-5, atol=1e-5)
    # masked tokens produce zero output and advance no expert counts
    np.testing.assert_array_equal(np.asarray(padded[:, :, s_real:]), 0.0)
    assert int(np.asarray(new_counts).sum()) == s_real * cfg.num_experts_per_tok


@pytest.mark.slow
def test_moe_ep_shmap_masked_chainable_routing():
    """The experts_compute='ep' shard_map variant (per-rank expert-window
    dispatch + token-space psum) now understands the masked/chainable
    routing: chunked counts+limit plus a validity mask route exactly like
    the plain path — serving no longer has to raise on the ep placement
    (ROADMAP nicety, closed)."""
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro.launch.compat import make_host_mesh
        import jax.numpy as jnp
        import numpy as np
        from repro.configs import registry
        from repro.models import moe
        from repro.launch.shardings import serve_rules, moe_ep_shmap

        mesh = make_host_mesh((2, 4))
        # 8 experts on a 4-way model axis -> e_local = 2 per rank; low
        # capacity factor so the keep/drop rule actually fires
        cfg = registry.get_smoke_config("qwen3-moe-30b-a3b").with_(
            num_instances=2, num_experts=8, num_experts_per_tok=2,
            dtype="float32", param_dtype="float32", capacity_factor=0.5)
        params = moe.init(cfg, jax.random.PRNGKey(0))
        lp = jax.tree.map(lambda x: x[0], params["layers"])
        s_real, s_pad = 12, 16
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, s_pad, cfg.d_model))
        valid = (jnp.arange(s_pad) < s_real)[None, None]
        limit = jnp.full((2, 4), moe.capacity(cfg, s_real), jnp.int32)
        counts0 = jnp.zeros((2, 4, cfg.num_experts), jnp.int32)

        ref_out, _, ref_counts = moe.moe_mlp(
            cfg, lp, x, valid=valid, counts=counts0, limit=limit)

        rules = moe_ep_shmap(serve_rules(mesh))
        with jax.set_mesh(mesh), rules:
            out, _, cnts = jax.jit(lambda l, xx: moe.moe_mlp(
                cfg, l, xx, valid=valid, counts=counts0, limit=limit))(lp, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(np.asarray(cnts), np.asarray(ref_counts))

        # the plain (non-chunked) ep path is unchanged
        r0, _ = moe.moe_mlp(cfg, lp, x)
        with jax.set_mesh(mesh), rules:
            o0, _ = jax.jit(lambda l, xx: moe.moe_mlp(cfg, l, xx))(lp, x)
        np.testing.assert_allclose(np.asarray(o0), np.asarray(r0),
                                   rtol=2e-5, atol=2e-5)
        print("ep masked routing OK")
        """
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, cwd=REPO, timeout=900,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "ep masked routing OK" in r.stdout


# ---------------------------------------------------------------------------
# cross-mesh stream identity (hybrid — new under the chunked runtime)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_hybrid_and_moe_streams_identical_across_meshes():
    """Hybrid + moe greedy streams: no-mesh == 1-device mesh == 8-device
    mesh.  The chunked runtime is the first admission path that can
    serve hybrid at all lengths, and the moe leg runs the masked
    capacity routing through its shard_map dispatch — both must hold
    the cross-mesh contract dense/ssm already do."""
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro.launch.compat import make_host_mesh
        import jax.numpy as jnp
        import numpy as np
        assert len(jax.devices()) == 8, jax.devices()
        mesh = make_host_mesh((2, 4))

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

        def serve(cfg, merged, mesh, max_context):
            srv = MultiModelServer(
                cfg, merged, slots_per_instance=2, max_context=max_context,
                prefill_chunk=16, chunk_budget=2, mesh=mesh)
            rng = np.random.default_rng(0)
            for i in range(4):
                prompt = rng.integers(
                    1, cfg.vocab_size, size=int(rng.integers(2, 9))).tolist()
                srv.submit(Request(instance=i % M, prompt=prompt,
                                   max_new_tokens=3))
            res = sorted(srv.run_until_drained(), key=lambda r: r.request_id)
            assert srv.prefill.compiled_shapes == 1
            return [r.tokens for r in res]

        for arch, ctx in (("hymba-1.5b", 200), ("olmoe-1b-7b", 64)):
            cfg, merged = build(arch)
            ref = serve(cfg, merged, None, ctx)
            assert all(len(t) > 0 for t in ref), (arch, ref)
            one = serve(cfg, merged, make_host_mesh((1, 1)), ctx)
            assert one == ref, (arch, one, ref)
            eight = serve(cfg, merged, mesh, ctx)
            assert eight == ref, (arch, eight, ref)
            print(arch, "streams OK")
        """
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, cwd=REPO, timeout=1200,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "hymba-1.5b streams OK" in r.stdout
    assert "olmoe-1b-7b streams OK" in r.stdout
