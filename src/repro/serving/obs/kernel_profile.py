"""Achieved-vs-roofline profiling of the serving Pallas kernels.

The dry-run roofline (``launch/hlo_analysis.py``) predicts what each
compiled program *should* cost from first-order FLOP/byte counts; this
module closes the loop by **timing the actual kernels** at serving
shapes and reporting achieved FLOP/s and bytes/s against the same
roofline envelope (``launch/mesh.py`` peaks of the device's kind), so a
block-shape tune or a kernel rewrite is a measured win, not a vibe.

Seven kernels — the fused serving hot spots:

* ``fused_matmul``       — the merged (M, T, D) @ (M, D, F) projection,
* ``decode_attn``        — one fused grid decode step's attention,
* ``chunk_prefill_attn`` — flash attention over [cache, chunk],
* ``mlstm_chunk``        — chunkwise mLSTM admission scan,
* ``slstm_cell``         — the sLSTM recurrent cell scan,
* ``decode_layer``       — the whole-dense-decode-layer megakernel
  (QKV+RoPE, cache append, flash decode, out-proj, both norms, SwiGLU),
* ``logits_sample``      — fused final-norm + unembed + greedy argmax.

Shapes derive from a ``ModelConfig`` + serving geometry
(:func:`serving_shapes`), so the profile measures what the engine
actually launches.  On non-TPU backends the kernels execute in the
Pallas **interpreter** — the achieved numbers then characterize the
interpreter, not silicon; every record carries ``backend``/``interpret``
flags so a table can never pass off CPU figures as TPU ones.

FLOP/byte models are first-order and dense-equivalent (masked attention
positions count; see each ``_model_*``), matching the philosophy of the
HLO cost model: a roofline tool, not a cycle simulator.
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.launch.mesh import chip_peaks

KERNELS = ("fused_matmul", "decode_attn", "chunk_prefill_attn",
           "mlstm_chunk", "slstm_cell", "decode_layer", "logits_sample")


def _nbytes(*arrays) -> int:
    return int(sum(a.size * a.dtype.itemsize for a in arrays))


def serving_shapes(cfg, *, slots: int = 4, max_context: int = 128,
                   chunk: int = 32, prefill_lanes: int = 4) -> dict:
    """Kernel input geometry at this config's serving shapes: M from the
    merged instance count, B from the grid slots, S from the serving
    context, C from the prefill chunk."""
    m = max(cfg.num_instances, 1)
    hd = cfg.head_dim
    # recurrent families project to an inner width (ssm.d_inner); attn
    # families have no mlstm/slstm path but still get well-formed shapes
    di = int((cfg.mlstm_proj_factor or 2.0) * cfg.d_model)
    return {
        "fused_matmul": dict(m=m, t=slots, d=cfg.d_model,
                             f=cfg.d_ff or 4 * cfg.d_model),
        "decode_attn": dict(m=m, b=slots, h=cfg.num_heads,
                            kvh=cfg.num_kv_heads, hd=hd, s=max_context),
        "chunk_prefill_attn": dict(m=m, b=prefill_lanes, c=chunk,
                                   h=cfg.num_heads, kvh=cfg.num_kv_heads,
                                   hd=hd, s_cache=max_context - chunk),
        "mlstm_chunk": dict(m=m, b=prefill_lanes, h=cfg.num_heads,
                            s=chunk, hd=di // cfg.num_heads,
                            chunk=min(cfg.mlstm_chunk or 64, chunk)),
        "slstm_cell": dict(m=m, b=prefill_lanes, s=chunk,
                           d=di, h=cfg.num_heads),
        "decode_layer": dict(m=m, b=slots, d=cfg.d_model, h=cfg.num_heads,
                             kvh=cfg.num_kv_heads, hd=hd, s=max_context,
                             ff=cfg.d_ff or 4 * cfg.d_model,
                             window=cfg.sliding_window or 0),
        "logits_sample": dict(m=m, b=slots, d=cfg.d_model,
                              v=cfg.vocab_size),
    }


# -- per-kernel builders: (callable, flops, bytes, shape string) -------------


def _mk_fused_matmul(m, t, d, f, dtype):
    from repro.kernels.fused_matmul import fused_matmul
    x = jnp.ones((m, t, d), dtype)
    w = jnp.ones((m, d, f), dtype)
    interpret = interpret_mode()
    return (lambda: fused_matmul(x, w, interpret=interpret),
            2.0 * m * t * d * f,
            _nbytes(x, w) + m * t * f * x.dtype.itemsize,
            f"({m},{t},{d})@({m},{d},{f})", interpret)


def _mk_decode_attn(m, b, h, kvh, hd, s, dtype):
    from repro.kernels.decode_attn import decode_attention
    q = jnp.ones((m, b, h, hd), dtype)
    k = jnp.ones((m, b, s, kvh, hd), dtype)
    v = jnp.ones((m, b, s, kvh, hd), dtype)
    kv_len = jnp.full((m, b), s, jnp.int32)
    interpret = interpret_mode()
    return (lambda: decode_attention(q, k, v, kv_len, interpret=interpret),
            4.0 * m * b * h * s * hd,
            _nbytes(q, k, v) + q.size * q.dtype.itemsize,
            f"q({m},{b},{h},{hd}) kv S={s}", interpret)


def _mk_chunk_prefill_attn(m, b, c, h, kvh, hd, s_cache, dtype):
    from repro.kernels.chunk_prefill_attn import chunk_prefill_attention
    t = s_cache + c
    q = jnp.ones((m, b, c, h, hd), dtype)
    k = jnp.ones((m, b, t, kvh, hd), dtype)
    v = jnp.ones((m, b, t, kvh, hd), dtype)
    offset = jnp.full((m, b), s_cache, jnp.int32)
    interpret = interpret_mode()
    return (lambda: chunk_prefill_attention(
                q, k, v, offset, s_cache=s_cache, interpret=interpret),
            4.0 * m * b * c * h * t * hd,       # dense-equivalent
            _nbytes(q, k, v) + q.size * q.dtype.itemsize,
            f"q({m},{b},{c},{h},{hd}) cache S={s_cache}", interpret)


def _mk_mlstm_chunk(m, b, h, s, hd, chunk, dtype):
    from repro.kernels.mlstm_chunk import mlstm_chunkwise
    q = jnp.ones((m, b, h, s, hd), dtype)
    k = jnp.ones((m, b, h, s, hd), dtype)
    v = jnp.ones((m, b, h, s, hd), dtype)
    lf = jnp.zeros((m, b, h, s), jnp.float32)
    li = jnp.zeros((m, b, h, s), jnp.float32)
    interpret = interpret_mode()
    # per chunk cs: intra-chunk qk^T + a.v (4 cs^2 hd) and inter-chunk
    # q@C + k^T v state update (4 cs hd^2) -> S * 4 hd (cs + hd)
    cs = min(chunk, s)
    return (lambda: mlstm_chunkwise(q, k, v, lf, li, chunk=cs,
                                    interpret=interpret),
            m * b * h * s * 4.0 * hd * (cs + hd),
            _nbytes(q, k, v, lf, li) + q.size * q.dtype.itemsize
            + m * b * h * (hd * hd + hd + 1) * 4,
            f"qkv({m},{b},{h},{s},{hd}) chunk={cs}", interpret)


def _mk_slstm_cell(m, b, s, d, h, dtype):
    from repro.kernels.slstm_cell import slstm_cell
    hd = d // h
    pre = jnp.ones((m, b, s, 4, d), dtype)
    r = jnp.ones((m, 4, h, hd, hd), dtype)
    state = (jnp.zeros((m, b, d), jnp.float32),
             jnp.zeros((m, b, d), jnp.float32),
             jnp.zeros((m, b, d), dtype),
             jnp.zeros((m, b, d), jnp.float32))
    interpret = interpret_mode()
    # per step: 4 recurrent head matmuls (8 H hd^2) + ~16 D elementwise
    return (lambda: slstm_cell(pre, r, state, num_heads=h,
                               interpret=interpret),
            m * b * s * (8.0 * h * hd * hd + 16.0 * d),
            _nbytes(pre, r) + m * b * s * d * pre.dtype.itemsize,
            f"pre({m},{b},{s},4,{d}) H={h}", interpret)


def _mk_decode_layer(m, b, d, h, kvh, hd, s, ff, window, dtype):
    from repro.kernels.decode_layer import decode_layer
    lp = {
        "attn_norm": jnp.ones((m, d), dtype),
        "wq": jnp.ones((m, d, h * hd), dtype),
        "wk": jnp.ones((m, d, kvh * hd), dtype),
        "wv": jnp.ones((m, d, kvh * hd), dtype),
        "wo": jnp.ones((m, h * hd, d), dtype),
        "mlp_norm": jnp.ones((m, d), dtype),
        "w_gate": jnp.ones((m, d, ff), dtype),
        "w_up": jnp.ones((m, d, ff), dtype),
        "w_down": jnp.ones((m, ff, d), dtype),
    }
    x = jnp.ones((m, b, d), dtype)
    ck = jnp.zeros((m, b, s, kvh, hd), dtype)
    cv = jnp.zeros((m, b, s, kvh, hd), dtype)
    pos = jnp.full((m, b), s - 1, jnp.int32)
    interpret = interpret_mode()
    # per lane: qkv proj + attention over the full ring + out proj + swiglu
    flops = m * b * (2.0 * d * (h + 2 * kvh) * hd + 4.0 * h * hd * s
                     + 2.0 * h * hd * d + 6.0 * d * ff)
    return (lambda: decode_layer(lp, x, ck, cv, pos, num_heads=h,
                                 head_dim=hd, rope_theta=10000.0,
                                 window=window, interpret=interpret),
            flops,
            _nbytes(x, ck, cv, pos, *lp.values())
            + _nbytes(x, ck, cv),                  # x/cache written back
            f"x({m},{b},{d}) H={h}/{kvh} S={s} ff={ff}", interpret)


def _mk_logits_sample(m, b, d, v, dtype):
    from repro.kernels.decode_layer import logits_sample
    x = jnp.ones((m, b, d), dtype)
    scale = jnp.ones((m, d), dtype)
    head = jnp.ones((m, d, v), dtype)
    interpret = interpret_mode()
    return (lambda: logits_sample(x, scale, head, interpret=interpret),
            2.0 * m * b * d * v,
            _nbytes(x, scale, head) + m * b * 4,
            f"x({m},{b},{d}) V={v}", interpret)


_BUILDERS = {
    "fused_matmul": _mk_fused_matmul,
    "decode_attn": _mk_decode_attn,
    "chunk_prefill_attn": _mk_chunk_prefill_attn,
    "mlstm_chunk": _mk_mlstm_chunk,
    "slstm_cell": _mk_slstm_cell,
    "decode_layer": _mk_decode_layer,
    "logits_sample": _mk_logits_sample,
}


def profile_kernel(name: str, *, dtype: str = "bfloat16", repeats: int = 3,
                   device_kind: str | None = None, **shape) -> dict:
    """Time one kernel at the given shape; returns achieved FLOP/s and
    bytes/s against the roofline envelope of ``device_kind``'s peaks
    (default: the kind of ``jax.devices()[0]``; a kind without published
    peaks raises KeyError).  The first (compile/trace)
    call is excluded; ``wall_s`` is the min of ``repeats`` settled
    calls (min, not mean: dispatch noise only ever adds time)."""
    device_kind = device_kind or jax.devices()[0].device_kind
    peaks = chip_peaks(device_kind)
    fn, flops, nbytes, shape_str, interpret = _BUILDERS[name](
        **shape, dtype=jnp.dtype(dtype))
    jax.block_until_ready(fn())              # compile + warmup
    wall = float("inf")
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        wall = min(wall, time.perf_counter() - t0)
    intensity = flops / nbytes
    t_compute = flops / peaks.flops_bf16
    t_memory = nbytes / peaks.hbm_bw
    roofline_flops = flops / max(t_compute, t_memory)
    achieved_flops = flops / wall
    return {
        "kernel": name,
        "shape": shape_str,
        "dtype": str(dtype),
        "backend": jax.default_backend(),
        "interpret": interpret,
        "peaks_of": device_kind,
        "wall_s": wall,
        "flops": flops,
        "bytes": nbytes,
        "intensity": intensity,
        "achieved_flops_per_s": achieved_flops,
        "achieved_bytes_per_s": nbytes / wall,
        "roofline_flops_per_s": roofline_flops,
        "frac_of_roofline": achieved_flops / roofline_flops,
        "bound": "compute" if t_compute >= t_memory else "memory",
    }


def profile_serving_kernels(cfg, *, slots: int = 4, max_context: int = 128,
                            chunk: int = 32, prefill_lanes: int = 4,
                            repeats: int = 3, kernels=KERNELS,
                            device_kind: str | None = None) -> list[dict]:
    """Profile every serving kernel at this config's shapes (the grid
    and admission geometry the engine actually launches)."""
    shapes = serving_shapes(cfg, slots=slots, max_context=max_context,
                            chunk=chunk, prefill_lanes=prefill_lanes)
    return [
        profile_kernel(k, dtype=cfg.dtype, repeats=repeats,
                       device_kind=device_kind, **shapes[k])
        for k in kernels
    ]


def format_table(rows) -> str:
    """Markdown achieved-vs-roofline table (roofline_table --achieved)."""
    out = [
        "| kernel | shape | wall (ms) | GFLOP/s | GB/s | % roofline "
        "| bound | backend |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        be = r["backend"] + (" (interpret)" if r["interpret"] else "")
        out.append(
            f"| {r['kernel']} | {r['shape']} | {1e3 * r['wall_s']:.3f} "
            f"| {r['achieved_flops_per_s'] / 1e9:.2f} "
            f"| {r['achieved_bytes_per_s'] / 1e9:.2f} "
            f"| {100 * r['frac_of_roofline']:.2f}% "
            f"| {r['bound']} | {be} |"
        )
    return "\n".join(out)


def validate_profile(rows) -> None:
    """Every figure finite and positive (CI bench-smoke contract)."""
    for r in rows:
        for f in ("wall_s", "flops", "bytes", "achieved_flops_per_s",
                  "achieved_bytes_per_s", "roofline_flops_per_s",
                  "frac_of_roofline"):
            v = r[f]
            assert isinstance(v, (int, float)) and np.isfinite(v) and v > 0, (
                r["kernel"], f, v)
