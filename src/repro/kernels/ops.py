"""jit'd dispatch wrappers for the Pallas kernels.

On a TPU the kernels compile with Mosaic; on any other backend they run
in the Pallas interpreter (``repro.kernels.interpret_mode``), which
validates BlockSpec indexing and in-kernel math.  ``use_pallas``
gates whether the model zoo routes through the kernels or the plain-XLA
reference path (default: reference — kernels are validated/benched
explicitly, and the dry-run rooflines stay pure-XLA so the §Perf kernel
deltas are attributable).
"""
from __future__ import annotations

from repro.kernels import ref
from repro.kernels.chunk_prefill_attn import (
    chunk_prefill_attention as _chunk_prefill_pl,
    chunk_prefill_attention_sharded as _chunk_prefill_sh,
)
from repro.kernels.decode_attn import decode_attention as _decode_attention_pl
from repro.kernels.decode_attn import decode_attention_sharded as _decode_attention_sh
from repro.kernels.decode_layer import decode_layer as _decode_layer_pl
from repro.kernels.decode_layer import decode_layer_sharded as _decode_layer_sh
from repro.kernels.decode_layer import logits_sample as _logits_sample_pl
from repro.kernels.decode_layer import logits_sample_sharded as _logits_sample_sh
from repro.kernels.fused_matmul import fused_matmul as _fused_matmul_pl
from repro.kernels.fused_matmul import fused_matmul_sharded as _fused_matmul_sh
from repro.kernels.group_norm import group_rms_norm as _group_rms_norm_pl
from repro.kernels.slstm_cell import slstm_cell as _slstm_cell_pl


def fused_matmul(x, w, b=None, *, use_pallas: bool = True, rules=None, **kw):
    """``rules=`` (a models.common.Rules) runs the kernel under
    shard_map on the rules' mesh — instances data-parallel, output
    features tensor-parallel; see fused_matmul_sharded."""
    if not use_pallas:
        return ref.fused_matmul(x, w, b)
    if rules is not None:
        return _fused_matmul_sh(x, w, b, rules=rules, **kw)
    return _fused_matmul_pl(x, w, b, **kw)


def group_rms_norm(x, scale, *, eps: float = 1e-5, use_pallas: bool = True, **kw):
    if not use_pallas:
        return ref.group_rms_norm(x, scale, eps)
    return _group_rms_norm_pl(x, scale, eps=eps, **kw)


def decode_attention(q, k, v, kv_len, *, use_pallas: bool = True, rules=None, **kw):
    """``rules=`` runs the kernel under shard_map — (M, B) data-parallel,
    kv-head groups tensor-parallel; see decode_attention_sharded."""
    if not use_pallas:
        return ref.decode_attention(q, k, v, kv_len)
    if rules is not None:
        return _decode_attention_sh(q, k, v, kv_len, rules=rules, **kw)
    return _decode_attention_pl(q, k, v, kv_len, **kw)


def decode_layer(lp, x, ck, cv, pos, *, num_heads, head_dim, rope_theta,
                 window: int = 0, eps: float = 1e-5, use_pallas: bool = True,
                 rules=None, **kw):
    """Fused dense decode layer — ONE pallas_call per layer over the
    (M, B) grid, KV append in-kernel (kernels/decode_layer.py).
    ``rules=`` runs the attention/FFN phase pair under shard_map —
    (M, B) data-parallel, head/ffn slices tensor-parallel."""
    if not use_pallas:
        return ref.decode_layer(
            lp, x, ck, cv, pos, num_heads=num_heads, head_dim=head_dim,
            rope_theta=rope_theta, window=window, eps=eps)
    if rules is not None:
        return _decode_layer_sh(
            lp, x, ck, cv, pos, rules=rules, num_heads=num_heads,
            head_dim=head_dim, rope_theta=rope_theta, window=window, eps=eps, **kw)
    return _decode_layer_pl(
        lp, x, ck, cv, pos, num_heads=num_heads, head_dim=head_dim,
        rope_theta=rope_theta, window=window, eps=eps, **kw)


def logits_sample(x, scale, head, *, eps: float = 1e-5,
                  use_pallas: bool = True, rules=None, **kw):
    """Fused final-norm + logits projection + greedy argmax
    (kernels/decode_layer.py).  ``rules=`` shards the vocab over "model"
    with a cross-rank argmax combine."""
    if not use_pallas:
        return ref.logits_sample(x, scale, head, eps=eps)
    if rules is not None:
        return _logits_sample_sh(x, scale, head, rules=rules, eps=eps, **kw)
    return _logits_sample_pl(x, scale, head, eps=eps, **kw)


def chunk_prefill_attention(q, k, v, offset, *, s_cache: int, pin: int = 0,
                            window: int = 0, sink: int = 0,
                            use_pallas: bool = True, rules=None, **kw):
    """Chunked-prefill flash attention over [cache-before, chunk]
    (kernels/chunk_prefill_attn.py).  ``rules=`` runs the kernel under
    shard_map — (M, B) lanes data-parallel, kv-head groups
    tensor-parallel; see chunk_prefill_attention_sharded."""
    if not use_pallas:
        return ref.chunk_prefill_attention(
            q, k, v, offset, s_cache=s_cache, pin=pin, window=window, sink=sink)
    if rules is not None:
        return _chunk_prefill_sh(
            q, k, v, offset, rules=rules, s_cache=s_cache, pin=pin,
            window=window, sink=sink, **kw)
    return _chunk_prefill_pl(
        q, k, v, offset, s_cache=s_cache, pin=pin, window=window, sink=sink, **kw)


def slstm_cell(pre, r, state, *, num_heads: int, use_pallas: bool = True, **kw):
    if not use_pallas:
        return ref.slstm_cell(pre, r, state, num_heads=num_heads)
    return _slstm_cell_pl(pre, r, state, num_heads=num_heads, **kw)


def mlstm_chunkwise(q, k, v, lf, li, *, use_pallas: bool = True, **kw):
    if not use_pallas:
        return ref.mlstm_chunkwise(q, k, v, lf, li, **kw)
    from repro.kernels.mlstm_chunk import mlstm_chunkwise as _pl
    return _pl(q, k, v, lf, li, **kw)
