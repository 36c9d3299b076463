"""Correctness tests for the fusion-aware primitives (attention etc.)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L


def _naive_attention(q, k, v, q_pos, kv_pos, window=0, causal=True):
    """O(S^2) reference with explicit masks. Shapes as flash_attention."""
    m, b, sq, h, hd = q.shape
    kvh = k.shape[3]
    g = h // kvh
    qg = q.reshape(m, b, sq, kvh, g, hd).astype(jnp.float32)
    s = jnp.einsum("mbqkgd,mbckd->mbkgqc", qg, k.astype(jnp.float32)) / np.sqrt(hd)
    valid = (kv_pos >= 0)[:, :, None, :]
    if causal:
        valid = valid & (kv_pos[:, :, None, :] <= q_pos[:, :, :, None])
    if window > 0:
        valid = valid & (q_pos[:, :, :, None] - kv_pos[:, :, None, :] < window)
    s = jnp.where(valid[:, :, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows -> zero output (flash uses l=max(l,eps))
    any_valid = valid.any(axis=-1)[:, :, None, None, :, None]
    o = jnp.einsum("mbkgqc,mbckd->mbkgqd", p, v.astype(jnp.float32))
    o = jnp.where(any_valid, o, 0.0)
    return jnp.moveaxis(o, -2, 2).reshape(m, b, sq, h, hd)


def _mk(m=1, b=2, sq=32, skv=32, h=4, kvh=2, hd=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (m, b, sq, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (m, b, skv, kvh, hd), jnp.float32)
    v = jax.random.normal(ks[2], (m, b, skv, kvh, hd), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("window", [0, 7])
@pytest.mark.parametrize("m,h,kvh", [(1, 4, 2), (3, 4, 4), (2, 8, 2)])
def test_flash_attention_causal(m, h, kvh, window):
    b, s = 2, 48
    q, k, v = _mk(m, b, s, s, h, kvh)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (m, b, s))
    out = L.flash_attention(q, k, v, pos, pos, window=window, q_chunk=16, kv_chunk=16)
    ref = _naive_attention(q, k, v, pos, pos, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_flash_attention_chunk_invariance():
    q, k, v = _mk(2, 2, 64, 64, 4, 2)
    pos = jnp.broadcast_to(jnp.arange(64, dtype=jnp.int32), (2, 2, 64))
    o1 = L.flash_attention(q, k, v, pos, pos, q_chunk=64, kv_chunk=64)
    o2 = L.flash_attention(q, k, v, pos, pos, q_chunk=8, kv_chunk=16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=2e-5, atol=2e-5)


def test_flash_attention_decode_matches_prefill():
    """Decoding token-by-token through the ring-buffer cache must match
    full prefill attention at every step."""
    m, b, s, h, kvh, hd = 2, 2, 16, 4, 2, 8
    q, k, v = _mk(m, b, s, s, h, kvh, hd, seed=3)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (m, b, s))
    ref = _naive_attention(q, k, v, pos, pos)

    ck = jnp.zeros((m, b, s, kvh, hd))
    cv = jnp.zeros((m, b, s, kvh, hd))
    for t in range(s):
        pt = jnp.full((m, b), t, jnp.int32)
        ck, cv = L.cache_update_one(
            ck, cv, k[:, :, t : t + 1], v[:, :, t : t + 1], pt
        )
        kv_pos = L.cache_slot_positions(pt, s)
        out_t = L.flash_attention(
            q[:, :, t : t + 1], ck, cv, pt[..., None], kv_pos, kv_chunk=8
        )
        np.testing.assert_allclose(
            np.asarray(out_t[:, :, 0]), np.asarray(ref[:, :, t]), rtol=2e-4, atol=2e-4
        )


@pytest.mark.parametrize("s,hd", [(8, 16), (200, 16), (16, 128)])
def test_cache_update_one_writes_one_slot_of_live_lanes(s, hd):
    """The K/V insert writes each live lane's row at slot pos % S of its
    layer and nothing else, whether given one layer or the stacked cache
    in its kv_stack view (slots-minor for hd=16, including a ring longer
    than one 128-slot window; as it is for hd=128)."""
    nl, m, b, kvh, layer = 3, 2, 3, 2, 1
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    ck, cv = (jax.random.normal(k, (nl, m, b, s, kvh, hd)) for k in ks[:2])
    kn, vn = (jax.random.normal(k, (m, b, 1, kvh, hd)) for k in ks[2:4])
    pos = jax.random.randint(ks[4], (m, b), 0, 3 * s)
    live = jnp.array([[True, False, True], [False, True, True]])

    want = [np.array(c) for c in (ck, cv)]
    for mi in range(m):
        for bi in range(b):
            if live[mi, bi]:
                for w, n in zip(want, (kn, vn)):
                    w[layer, mi, bi, int(pos[mi, bi]) % s] = n[mi, bi, 0]

    got = jax.jit(lambda a, c: L.cache_update_one(
        L.kv_stack(a), L.kv_stack(c), kn, vn, pos, live=live,
        layer=jnp.int32(layer)))(ck, cv)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(L.kv_unstack(g, kvh, hd)), w)
    got = L.cache_update_one(ck[layer], cv[layer], kn, vn, pos, live=live)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w[layer])


def test_ring_buffer_sliding_window_decode():
    """With cache size == window, ring-buffer decode == sliding-window
    attention over the full sequence."""
    m, b, s, w, h, kvh, hd = 1, 2, 24, 8, 2, 2, 4
    q, k, v = _mk(m, b, s, s, h, kvh, hd, seed=4)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (m, b, s))
    ref = _naive_attention(q, k, v, pos, pos, window=w)

    ck = jnp.zeros((m, b, w, kvh, hd))
    cv = jnp.zeros((m, b, w, kvh, hd))
    for t in range(s):
        pt = jnp.full((m, b), t, jnp.int32)
        ck, cv = L.cache_update_one(ck, cv, k[:, :, t : t + 1], v[:, :, t : t + 1], pt)
        kv_pos = L.cache_slot_positions(pt, w)
        out_t = L.flash_attention(
            q[:, :, t : t + 1], ck, cv, pt[..., None], kv_pos, window=w, kv_chunk=4
        )
        np.testing.assert_allclose(
            np.asarray(out_t[:, :, 0]), np.asarray(ref[:, :, t]), rtol=2e-4, atol=2e-4
        )


def test_cache_slot_positions():
    pos = jnp.array([[2]], jnp.int32)          # 3 tokens written, cache size 4
    p = L.cache_slot_positions(pos, 4)
    np.testing.assert_array_equal(np.asarray(p[0, 0]), [0, 1, 2, -1])
    pos = jnp.array([[5]], jnp.int32)          # wrapped: slots hold 4,5,2,3
    p = L.cache_slot_positions(pos, 4)
    np.testing.assert_array_equal(np.asarray(p[0, 0]), [4, 5, 2, 3])


def test_rope_relative_shift_invariance():
    """RoPE inner products depend only on relative positions."""
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    q = jax.random.normal(ks[0], (1, 1, 4, 2, 16))
    k = jax.random.normal(ks[1], (1, 1, 4, 2, 16))
    p0 = jnp.broadcast_to(jnp.arange(4, dtype=jnp.int32), (1, 1, 4))
    scores = lambda qq, kk: jnp.einsum("mbshd,mbthd->mbhst", qq, kk)
    s0 = scores(L.rope(q, p0, 1e4), L.rope(k, p0, 1e4))
    s1 = scores(L.rope(q, p0 + 100, 1e4), L.rope(k, p0 + 100, 1e4))
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), rtol=1e-3, atol=1e-4)


def test_gqa_attention_merged_equals_per_instance():
    """The NetFuse invariant at the attention-block level: merged M-instance
    attention == per-instance attention."""
    m, b, s, d, h, kvh, hd = 3, 2, 16, 32, 4, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    p = {
        "wq": jax.random.normal(ks[0], (m, d, h * hd)) * 0.1,
        "wk": jax.random.normal(ks[1], (m, d, kvh * hd)) * 0.1,
        "wv": jax.random.normal(ks[2], (m, d, kvh * hd)) * 0.1,
        "wo": jax.random.normal(ks[3], (m, h * hd, d)) * 0.1,
    }
    x = jax.random.normal(ks[4], (m, b, s, d))
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (m, b, s))
    kw = dict(num_heads=h, num_kv_heads=kvh, head_dim=hd, rope_theta=1e4)
    out, _ = L.gqa_attention(x, p, positions=pos, **kw)
    for i in range(m):
        pi = {k_: v_[i : i + 1] for k_, v_ in p.items()}
        oi, _ = L.gqa_attention(x[i : i + 1], pi, positions=pos[i : i + 1], **kw)
        np.testing.assert_allclose(
            np.asarray(out[i]), np.asarray(oi[0]), rtol=2e-4, atol=2e-4
        )


def test_norms():
    m, b, d = 2, 3, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (m, b, d))
    sc = 1 + 0.1 * jax.random.normal(ks[1], (m, d))
    bi = 0.1 * jax.random.normal(ks[2], (m, d))
    y = L.rms_norm(x, sc)
    ref = x / np.sqrt((np.asarray(x) ** 2).mean(-1, keepdims=True) + 1e-5) * np.asarray(sc)[:, None]
    np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-3, atol=1e-4)
    y2 = L.layer_norm(x, sc, bi)
    assert y2.shape == x.shape
    # normalized-then-affine: per-row mean equals mean of bias + scale*0-mean
    xn = (np.asarray(x) - np.asarray(x).mean(-1, keepdims=True)) / np.sqrt(
        np.asarray(x).var(-1, keepdims=True) + 1e-5
    )
    ref2 = xn * np.asarray(sc)[:, None] + np.asarray(bi)[:, None]
    np.testing.assert_allclose(np.asarray(y2), ref2, rtol=2e-3, atol=1e-4)
