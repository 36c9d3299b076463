"""Operations and bytes that the served work requires, from the
configuration's shapes and the positions served: never from what an
implementation happens to move.  Weights and cache are bfloat16.

A decode step requires, for every instance with a live lane, its weights
read once (an untied embedding only at the rows it looks up; a tied one is
read whole as the head), and for every live lane its cached K/V rows read
and its new row written.  A token at position
p (0-based) attends over p + 1 positions.  A prefilled token needs no
logits: its prompt's last position is decoded by the first decode step.
"""
from __future__ import annotations

from bench.model import Dense

BYTES = 2       # bfloat16


def layer_matmul_params(d: Dense) -> int:
    hd = d.head_dim
    attn = d.d_model * hd * (2 * d.heads + 2 * d.kv_heads)
    return d.layers * (attn + 3 * d.d_model * d.d_ff)


def head_params(d: Dense) -> int:
    return d.d_model * d.vocab


def weight_bytes_per_instance(d: Dense) -> int:
    """Every weight a decode step reads for one instance: the layers, the
    final norm and the head, which is the embedding table where tied (an
    untied table is read only at the rows looked up, ``decode_step``)."""
    hd = d.head_dim
    norms = d.layers * 2 * d.d_model + d.d_model
    bias = d.layers * hd * (d.heads + 2 * d.kv_heads) if d.qkv_bias else 0
    return BYTES * (layer_matmul_params(d) + head_params(d) + norms + bias)


def kv_row_bytes(d: Dense) -> int:
    """K and V of one position, all layers."""
    return BYTES * d.layers * 2 * d.kv_heads * d.head_dim


def attn_flops(d: Dense, ctx: int) -> int:
    """Scores and weighted sum of one query over ``ctx`` positions, all layers."""
    return 4 * d.layers * d.heads * d.head_dim * ctx


def decode_step(d: Dense, *, instances: int, lanes: int, ctx_sum: int):
    """(FLOPs, bytes) of one decode step: ``instances`` with a live lane,
    ``lanes`` live lanes whose attention spans ``ctx_sum`` positions in all
    (each lane's own position + 1)."""
    flops = lanes * 2 * (layer_matmul_params(d) + head_params(d)) \
        + attn_flops(d, ctx_sum)
    rows_read = ctx_sum - lanes             # the new row is computed
    nbytes = (instances * weight_bytes_per_instance(d)
              + (0 if d.tied else lanes * BYTES * d.d_model)  # embedding rows
              + rows_read * kv_row_bytes(d)
              + lanes * kv_row_bytes(d))                 # rows written
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peaks) -> tuple[float, str]:
    """The least time the chip needs, and which bound sets it."""
    t_c, t_m = flops / peaks.flops_bf16, nbytes / peaks.hbm_bytes
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
