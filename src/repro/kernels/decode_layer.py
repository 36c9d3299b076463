"""Pallas TPU megakernel: ONE ``pallas_call`` per dense decode layer.

The serving decode step previously lowered every layer to ~8 separate
kernels (3 QKV matmuls, rope, flash attention, out-proj, 2 norms +
SwiGLU) plus a cache scatter.  Here the whole layer runs fused over the
(M, B) grid — one grid cell per lane, the lane's layer weights resident
in VMEM for the duration:

  rms(attn_norm) -> QKV (+bias) -> RoPE -> in-kernel ring append
  -> flash decode attention over the ring cache -> out-proj -> residual
  -> rms(mlp_norm) -> SwiGLU -> residual

Positions are scalar-prefetched per lane (the ``chunk_prefill_attn``
offset machinery): RoPE angles, the ring slot (pos % S) and the
slot-validity mask are all derived in-kernel from ``pos[m, b]`` alone —
mirroring ``layers.cache_slot_positions`` — so no position arrays are
staged.  The KV append happens in-kernel via ``input_output_aliases``
on the cache operands: the (S, KVH, hd) block is already in VMEM for
attention, so the append is a vector select into the aliased output and
the separate per-step cache scatter disappears.

A second small kernel (``logits_sample``) fuses final-norm + logits
projection + greedy argmax, blocked over the vocab with a running
(max, argmax) carried in VMEM scratch — a steady-state decode scan step
is ~``num_layers + 1`` launches.

Sharded variants (``*_sharded``) run under ``shard_map`` consistent with
``decode_attention_sharded``: (M, B) lanes ride the data axes, kv-head
groups / mlp slices ride "model" (the shared ``tp_head_plan`` recipe).
The mid-layer reduction (out-proj over sharded heads, down-proj over the
sharded ffn) cannot live inside one kernel, so the layer splits into an
attention-phase kernel and an FFN-phase kernel with a psum after each —
2 launches + 2 collectives per layer per rank.

Everything is validated in the Pallas interpreter on CPU (see ops.py); at
smoke/serving shapes the per-lane weights fit VMEM outright — see
DESIGN.md §6.7 for the VMEM budget per block shape and the ff/V blocking
a full-size TPU variant needs.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def tp_head_plan(h: int, kvh: int, n_model: int) -> str | None:
    """ONE tensor-parallel head-grouping recipe, shared by
    ``decode_attention_sharded`` and the megakernel's shard_map variant.

    q heads are laid out kvh-major, so a contiguous H-split into
    ``n_model`` groups always keeps a q head on the same rank as its kv
    head.  Returns ``"kv"`` when the kv heads split evenly over the
    model axis, ``"expand"`` when they don't (GQA/MQA with kvh <
    n_model or non-dividing: expand KV to q heads — per-rank bytes go
    kvh*hd -> h_l*hd, a win since h_l*n_model = h = g*kvh >= kvh), or
    ``None`` when the q heads themselves can't split.
    """
    if n_model <= 1 or h % n_model:
        return None
    return "kv" if kvh % n_model == 0 else "expand"


# ---------------------------------------------------------------------------
# in-kernel subroutines (shared between the phase variants)
# ---------------------------------------------------------------------------


def _rms(x, scale, eps):
    """rms_norm on a (1, D) row — f32 stats, result cast back, exactly
    ``layers.rms_norm``."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope_rows(x, pos, theta):
    """RoPE on (H, hd) head rows at one scalar position — mirrors
    ``layers.rope`` (f32 angles, cos/sin cast to x.dtype)."""
    hd = x.shape[-1]
    half = hd // 2
    i = jax.lax.broadcasted_iota(jnp.int32, (1, half), 1).astype(jnp.float32)
    freqs = jnp.exp(-math.log(theta) * i / half)
    ang = pos.astype(jnp.float32) * freqs
    cos = jnp.cos(ang).astype(x.dtype)
    sin = jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[:, :half], x[:, half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _ring_valid(pos, s_cache, window):
    """(1, S) bool mask of ring slots valid AFTER writing ``pos`` at slot
    ``pos % S`` — the in-kernel form of ``cache_slot_positions`` plus the
    flash mask (validity + sliding window; causality is implied, every
    live slot position is <= pos)."""
    slots = jax.lax.broadcasted_iota(jnp.int32, (1, s_cache), 1)
    cur = pos % s_cache
    base = pos - cur
    p = jnp.where(slots <= cur, base + slots, base - s_cache + slots)
    valid = p >= 0
    if window > 0:
        valid = valid & (pos - p < window)
    return valid


def _attend(qh, k_cache, v_cache, valid, *, kvh, g, hd, out_dtype):
    """Flash decode attention over the full in-VMEM cache block: one
    softmax per kv head — the einsum contraction structure mirrors the
    unfused Sq=1 ``_flash_body`` (single KV block, kvh as a batch dim)
    so the f32 reduction order matches op-for-op."""
    scale = 1.0 / math.sqrt(hd)
    # dummy (m=1, b=1, q=1) dims so the einsum SPECS — and with them
    # XLA's degenerate-dim lowering and f32 reduction order — are the
    # unfused path's, character for character (g=1 einsums otherwise
    # lower to a gemv with a different accumulation order)
    qg = qh.reshape(1, 1, 1, kvh, g, hd)
    kb = k_cache[None, None]                                # (1,1,S,KVH,hd)
    vb = v_cache[None, None]
    s = jnp.einsum("mbqkgd,mbckd->mbkgqc", qg, kb,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[None, None, None, None], s, NEG_INF)
    mx = s.max(axis=-1)                                     # (1,1,KVH,G,1)
    p = jnp.exp(s - mx[..., None])
    l = p.sum(axis=-1)
    pv = jnp.einsum("mbkgqc,mbckd->mbkgqd", p.astype(vb.dtype), vb,
                    preferred_element_type=jnp.float32)
    o = (pv / jnp.maximum(l, 1e-30)[..., None]).astype(out_dtype)
    return o.reshape(1, kvh * g * hd)                       # kvh-major


# ---------------------------------------------------------------------------
# the decode-layer kernel (phases: "full" = whole layer, "attn" = the
# pre-psum half used by the sharded variant)
# ---------------------------------------------------------------------------


def _layer_kernel(pos_ref, *refs, h, kvh, hd, eps, theta, window, has_bias,
                  phase):
    refs = list(refs)
    x_ref, an_ref, wq_ref, wk_ref, wv_ref = refs[:5]
    del refs[:5]
    if has_bias:
        bq_ref, bk_ref, bv_ref = refs[:3]
        del refs[:3]
    wo_ref = refs.pop(0)
    if phase == "full":
        mn_ref, wg_ref, wu_ref, wd_ref = refs[:4]
        del refs[:4]
    ck_ref, cv_ref, out_ref, ko_ref, vo_ref = refs

    mi, bi = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[mi, bi]
    s_cache = ck_ref.shape[2]
    g = h // kvh
    x = x_ref[0, 0]                                         # (1, D)

    n = _rms(x, an_ref[0], eps)
    q = jnp.dot(n, wq_ref[0])                               # (1, H*hd)
    k = jnp.dot(n, wk_ref[0])
    v = jnp.dot(n, wv_ref[0])
    if has_bias:
        q = q + bq_ref[0].astype(q.dtype)
        k = k + bk_ref[0].astype(k.dtype)
        v = v + bv_ref[0].astype(v.dtype)
    qh = q.reshape(h, hd)
    kh = k.reshape(kvh, hd)
    vh = v.reshape(kvh, hd)
    if theta > 0:
        qh = _rope_rows(qh, pos, theta)
        kh = _rope_rows(kh, pos, theta)

    # in-kernel ring append: the cache block is in VMEM (aliased with the
    # output) so the slot write is a vector select, not a scatter
    slot = pos % s_cache
    sl = jax.lax.broadcasted_iota(jnp.int32, (s_cache, 1, 1), 0)
    k_cache = jnp.where(sl == slot, kh[None].astype(ck_ref.dtype), ck_ref[0, 0])
    v_cache = jnp.where(sl == slot, vh[None].astype(cv_ref.dtype), cv_ref[0, 0])
    ko_ref[0, 0] = k_cache
    vo_ref[0, 0] = v_cache

    valid = _ring_valid(pos, s_cache, window)
    o = _attend(qh, k_cache, v_cache, valid, kvh=kvh, g=g, hd=hd,
                out_dtype=x.dtype)
    attn = jnp.dot(o, wo_ref[0])                            # (1, D)
    if phase == "attn":
        out_ref[0, 0] = attn                                # pre-psum partial
        return
    x2 = x + attn
    n2 = _rms(x2, mn_ref[0], eps)
    hm = jax.nn.silu(jnp.dot(n2, wg_ref[0])) * jnp.dot(n2, wu_ref[0])
    out_ref[0, 0] = x2 + jnp.dot(hm, wd_ref[0])


def _ffn_kernel(x_ref, mn_ref, wg_ref, wu_ref, wd_ref, o_ref, *, eps):
    """FFN phase of the sharded variant: rms(mlp_norm) + SwiGLU over the
    rank-local ff slice; the down-proj output is a pre-psum partial."""
    x = x_ref[0, 0]
    n2 = _rms(x, mn_ref[0], eps)
    hm = jax.nn.silu(jnp.dot(n2, wg_ref[0])) * jnp.dot(n2, wu_ref[0])
    o_ref[0, 0] = jnp.dot(hm, wd_ref[0])


def _layer_call(lp, x, ck, cv, pos, *, num_heads, head_dim, rope_theta,
                window, eps, interpret, phase):
    m, b, d = x.shape
    s_cache, kvh = ck.shape[2], ck.shape[3]
    h, hd = num_heads, head_dim
    has_bias = "bq" in lp

    # vectors get a singleton row dim ((M, 1, n) scales and biases, an
    # (M, B, 1, D) residual) so every block's last two dims equal the
    # array's, as the TPU's (8, 128) tiling rule demands
    row = lambda a: a.reshape(m, 1, a.shape[-1])
    mat = lambda mi, bi, pr: (mi, 0, 0)
    lane4 = lambda mi, bi, pr: (mi, bi, 0, 0)
    lane5 = lambda mi, bi, pr: (mi, bi, 0, 0, 0)
    cache_spec = pl.BlockSpec((1, 1, s_cache, kvh, hd), lane5)
    row_spec = lambda n: pl.BlockSpec((1, 1, n), mat)
    x_spec = pl.BlockSpec((1, 1, 1, d), lane4)

    in_specs = [
        x_spec,
        row_spec(d),
        pl.BlockSpec((1, d, h * hd), mat),
        pl.BlockSpec((1, d, kvh * hd), mat),
        pl.BlockSpec((1, d, kvh * hd), mat),
    ]
    ops = [x.reshape(m, b, 1, d), row(lp["attn_norm"]), lp["wq"], lp["wk"],
           lp["wv"]]
    if has_bias:
        in_specs += [row_spec(h * hd), row_spec(kvh * hd),
                     row_spec(kvh * hd)]
        ops += [row(lp["bq"]), row(lp["bk"]), row(lp["bv"])]
    in_specs.append(pl.BlockSpec((1, h * hd, d), mat))
    ops.append(lp["wo"])
    if phase == "full":
        ff = lp["w_gate"].shape[2]
        in_specs += [
            row_spec(d),
            pl.BlockSpec((1, d, ff), mat),
            pl.BlockSpec((1, d, ff), mat),
            pl.BlockSpec((1, ff, d), mat),
        ]
        ops += [row(lp["mlp_norm"]), lp["w_gate"], lp["w_up"], lp["w_down"]]
    in_specs += [cache_spec, cache_spec]
    ops += [ck, cv]

    # alias the cache operands with the cache outputs (indices count the
    # scalar-prefetch operand): the append is in place, no HBM round-trip
    n_in = 1 + len(ops)
    out, k_out, v_out = pl.pallas_call(
        functools.partial(
            _layer_kernel, h=h, kvh=kvh, hd=hd, eps=eps, theta=rope_theta,
            window=window, has_bias=has_bias, phase=phase,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m, b),
            in_specs=in_specs,
            out_specs=[x_spec, cache_spec, cache_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((m, b, 1, d), x.dtype),
            jax.ShapeDtypeStruct(ck.shape, ck.dtype),
            jax.ShapeDtypeStruct(cv.shape, cv.dtype),
        ],
        input_output_aliases={n_in - 2: 1, n_in - 1: 2},
        interpret=interpret_mode(interpret),
    )(pos.astype(jnp.int32), *ops)
    return out.reshape(m, b, d), k_out, v_out


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "head_dim", "rope_theta", "window", "eps", "interpret"))
def decode_layer(lp, x, ck, cv, pos, *, num_heads, head_dim, rope_theta,
                 window: int = 0, eps: float = 1e-5,
                 interpret: bool | None = None):
    """One fused dense decode layer for the whole (M, B) grid.

    lp: the dense layer param dict (attn_norm, wq/wk/wv[+bq/bk/bv], wo,
    mlp_norm, w_gate/w_up/w_down — leading M axis).  x: (M, B, D)
    residual stream for the single decode position; ck/cv:
    (M, B, S, KVH, hd) ring cache BEFORE this token; pos: (M, B) int32
    absolute positions.  Returns (x_out, k_out, v_out) with the new
    token's K/V appended at slot ``pos % S``.
    """
    return _layer_call(
        lp, x, ck, cv, pos, num_heads=num_heads, head_dim=head_dim,
        rope_theta=rope_theta, window=window, eps=eps, interpret=interpret,
        phase="full",
    )


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _ffn_call(x, mlp_norm, w_gate, w_up, w_down, *, eps, interpret=None):
    m, b, d = x.shape
    ff = w_gate.shape[2]
    mat = lambda mi, bi: (mi, 0, 0)
    x_spec = pl.BlockSpec((1, 1, 1, d), lambda mi, bi: (mi, bi, 0, 0))
    return pl.pallas_call(
        functools.partial(_ffn_kernel, eps=eps),
        grid=(m, b),
        in_specs=[
            x_spec,
            pl.BlockSpec((1, 1, d), mat),
            pl.BlockSpec((1, d, ff), mat),
            pl.BlockSpec((1, d, ff), mat),
            pl.BlockSpec((1, ff, d), mat),
        ],
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct((m, b, 1, d), x.dtype),
        interpret=interpret_mode(interpret),
    )(x.reshape(m, b, 1, d), mlp_norm.reshape(m, 1, d), w_gate, w_up,
      w_down).reshape(m, b, d)


def decode_layer_sharded(lp, x, ck, cv, pos, *, rules, num_heads, head_dim,
                         rope_theta, window: int = 0, eps: float = 1e-5,
                         **kw):
    """``decode_layer`` under ``shard_map``, consistent with
    ``decode_attention_sharded``: (M, B) lanes ride the data axes,
    kv-head groups and the ffn slice ride "model" (``tp_head_plan``).

    The out-proj contracts sharded heads and the down-proj contracts the
    sharded ffn, so the layer splits into the attention-phase kernel and
    the FFN-phase kernel with a psum after each — 2 launches + 2
    collectives per layer per rank.  MQA head expansion would change the
    cache-out shape, so non-dividing kv heads (and a non-dividing ffn)
    fall back to the unsharded megakernel.
    """
    m, b, d = x.shape
    kvh = ck.shape[3]
    h, hd = num_heads, head_dim
    ff = lp["w_gate"].shape[2]
    n_model = rules._axis_size(rules.mapping.get("kv_heads"))
    plan = tp_head_plan(h, kvh, n_model)
    x_spec = rules.spec(("instances", "batch", None), x.shape)
    pos_spec = rules.spec(("instances", "batch"), pos.shape)
    if plan != "kv" or ff % n_model:
        # no tensor-parallel split — but a bare pallas_call under GSPMD
        # is NOT safe: the partitioner splits the (M, B) grid while the
        # kernel indexes the scalar-prefetched pos with global program
        # ids.  Run data-local instead: lanes shard over the data axes,
        # weights/caches replicated over "model"
        rep = lambda a: rules.spec(
            ("instances",) + (None,) * (a.ndim - 1), a.shape)
        lp_specs = {kk: rep(a) for kk, a in lp.items()}
        return jax.shard_map(
            lambda lp_l, x_l, ck_l, cv_l, pos_l: decode_layer(
                lp_l, x_l, ck_l, cv_l, pos_l, num_heads=num_heads,
                head_dim=hd, rope_theta=rope_theta, window=window, eps=eps,
                **kw),
            mesh=rules.mesh,
            in_specs=(lp_specs, x_spec, rep(ck), rep(cv), pos_spec),
            out_specs=(x_spec, rep(ck), rep(cv)),
            check_vma=False,
        )(dict(lp), x, ck, cv, pos)

    model_ax = rules.mapping.get("kv_heads")
    cache_spec = rules.spec(
        ("instances", "batch", None, "kv_heads", None), ck.shape)
    specs = {
        "attn_norm": rules.spec(("instances", None), lp["attn_norm"].shape),
        "wq": rules.spec(("instances", None, "heads_flat"), lp["wq"].shape),
        "wk": rules.spec(("instances", None, "kv_flat"), lp["wk"].shape),
        "wv": rules.spec(("instances", None, "kv_flat"), lp["wv"].shape),
        "wo": rules.spec(("instances", "heads_flat", None), lp["wo"].shape),
        "mlp_norm": rules.spec(("instances", None), lp["mlp_norm"].shape),
        "w_gate": rules.spec(("instances", None, "mlp"), lp["w_gate"].shape),
        "w_up": rules.spec(("instances", None, "mlp"), lp["w_up"].shape),
        "w_down": rules.spec(("instances", "mlp", None), lp["w_down"].shape),
    }
    if "bq" in lp:
        specs["bq"] = rules.spec(("instances", "heads_flat"), lp["bq"].shape)
        specs["bk"] = rules.spec(("instances", "kv_flat"), lp["bk"].shape)
        specs["bv"] = rules.spec(("instances", "kv_flat"), lp["bv"].shape)
    lp_in = {kk: lp[kk] for kk in specs}

    def body(lp_l, x_l, ck_l, cv_l, pos_l):
        h_l = lp_l["wq"].shape[2] // hd
        attn_part, nk, nv = _layer_call(
            lp_l, x_l, ck_l, cv_l, pos_l, num_heads=h_l, head_dim=hd,
            rope_theta=rope_theta, window=window, eps=eps, phase="attn",
            **kw)
        x2 = x_l + jax.lax.psum(attn_part, model_ax)
        down = _ffn_call(
            x2, lp_l["mlp_norm"], lp_l["w_gate"], lp_l["w_up"],
            lp_l["w_down"], eps=eps, **kw)
        return x2 + jax.lax.psum(down, model_ax), nk, nv

    return jax.shard_map(
        body, mesh=rules.mesh,
        in_specs=({kk: specs[kk] for kk in lp_in}, x_spec, cache_spec,
                  cache_spec, pos_spec),
        out_specs=(x_spec, cache_spec, cache_spec),
        check_vma=False,
    )(lp_in, x, ck, cv, pos)


# ---------------------------------------------------------------------------
# fused final-norm + logits + greedy sampling
# ---------------------------------------------------------------------------


def _logits_kernel(x_ref, sc_ref, hd_ref, tok_ref, val_out_ref, val_ref,
                   idx_ref, *, eps, nv, bv, v):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        val_ref[...] = jnp.full_like(val_ref, NEG_INF)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    n = _rms(x_ref[0], sc_ref[0], eps)                      # (B, D)
    logits = jnp.dot(n.astype(jnp.float32), hd_ref[0].astype(jnp.float32),
                     preferred_element_type=jnp.float32)   # (B, bv)
    ii = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    if v % bv:
        # the last vocab block overhangs V: its tail columns are padding
        logits = jnp.where(vi * bv + ii < v, logits, NEG_INF)
    bm = logits.max(axis=-1, keepdims=True)                 # (B, 1)
    first = jnp.where(logits == bm, ii, jnp.int32(2**31 - 1)).min(
        axis=-1, keepdims=True)
    # strict > keeps the earliest block's max; within a block ``first``
    # is the earliest index — together exactly jnp.argmax tie-breaking
    take = bm > val_ref[...]
    idx_ref[...] = jnp.where(take, vi * bv + first, idx_ref[...])
    val_ref[...] = jnp.where(take, bm, val_ref[...])

    @pl.when(vi == nv - 1)
    def _done():
        tok_ref[0] = idx_ref[...]
        val_out_ref[0] = val_ref[...]


@functools.partial(jax.jit, static_argnames=("eps", "block_v", "interpret"))
def _logits_argmax_parts(x, scale, head, *, eps: float = 1e-5,
                         block_v: int = 1024, interpret: bool | None = None):
    """Returns (tok (M,B) int32, val (M,B) f32): the greedy argmax and
    its logit value (the value feeds the sharded cross-rank combine).

    Grid (M, V/bv): one program multiplies an instance's B lanes by a
    (D, bv) vocab block of its head.  ``block_v`` is a multiple of 128
    (the TPU lane tile); a vocab that it does not divide gets a padded,
    masked last block."""
    m, b, d = x.shape
    v = head.shape[2]
    bv = v if v <= block_v else block_v
    nv = pl.cdiv(v, bv)
    lane_rows = pl.BlockSpec((1, b, 1), lambda mi, vi: (mi, 0, 0))
    tok, val = pl.pallas_call(
        functools.partial(_logits_kernel, eps=eps, nv=nv, bv=bv, v=v),
        grid=(m, nv),
        in_specs=[
            pl.BlockSpec((1, b, d), lambda mi, vi: (mi, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda mi, vi: (mi, 0, 0)),
            pl.BlockSpec((1, d, bv), lambda mi, vi: (mi, 0, vi)),
        ],
        out_specs=[lane_rows, lane_rows],
        out_shape=[
            jax.ShapeDtypeStruct((m, b, 1), jnp.int32),
            jax.ShapeDtypeStruct((m, b, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, 1), jnp.float32),
            pltpu.VMEM((b, 1), jnp.int32),
        ],
        interpret=interpret_mode(interpret),
    )(x, scale.reshape(m, 1, d), head)
    return tok[..., 0], val[..., 0]


def logits_sample(x, scale, head, *, eps: float = 1e-5, **kw):
    """Fused final-norm + logits projection + greedy argmax.

    x: (M, B, D) post-layers residual; scale: (M, D) final-norm scale;
    head: (M, D, V) unembedding.  Returns (M, B) int32 greedy tokens —
    bit-identical tie-breaking with ``jnp.argmax`` over the f32 logits
    (greedy == top-1, so the temperature<=0 top-k sampler reduces to
    this; stochastic sampling stays on the XLA path).
    """
    tok, _ = _logits_argmax_parts(x, scale, head, eps=eps, **kw)
    return tok


def logits_sample_sharded(x, scale, head, *, rules, eps: float = 1e-5, **kw):
    """``logits_sample`` under shard_map: vocab slices ride "model", each
    rank computes its local (max, argmax) in the kernel, and a tiny
    all-gather picks the global first-occurrence argmax."""
    m, b, d = x.shape
    v = head.shape[2]
    ax = rules.mapping.get("vocab")
    n_model = rules._axis_size(ax)
    x_spec = rules.spec(("instances", "batch", None), x.shape)
    sc_spec = rules.spec(("instances", None), scale.shape)
    out_spec = rules.spec(("instances", "batch"), (m, b))
    if n_model <= 1 or v % n_model:
        # data-local fallback — a bare pallas_call under GSPMD splits
        # the grid out from under the kernel's program-id indexing
        head_rep = rules.spec(("instances", None, None), head.shape)
        return jax.shard_map(
            lambda x_l, sc_l, hd_l: logits_sample(x_l, sc_l, hd_l, eps=eps,
                                                  **kw),
            mesh=rules.mesh,
            in_specs=(x_spec, sc_spec, head_rep),
            out_specs=out_spec, check_vma=False,
        )(x, scale, head)

    head_spec = rules.spec(("instances", None, "vocab"), head.shape)

    def body(x_l, sc_l, hd_l):
        tok_l, val_l = _logits_argmax_parts(x_l, sc_l, hd_l, eps=eps, **kw)
        base = jax.lax.axis_index(ax) * hd_l.shape[2]
        vals = jax.lax.all_gather(val_l, ax)                # (n, m_l, b_l)
        toks = jax.lax.all_gather(tok_l + base, ax)
        best = vals.max(axis=0)
        cand = jnp.where(vals == best, toks, jnp.int32(2**31 - 1))
        return cand.min(axis=0).astype(jnp.int32)

    return jax.shard_map(
        body, mesh=rules.mesh,
        in_specs=(x_spec, sc_spec, head_spec),
        out_specs=out_spec, check_vma=False,
    )(x, scale, head)
