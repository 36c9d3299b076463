"""Pallas TPU kernels for the NetFuse hot spots (Mosaic on a TPU, the
Pallas interpreter elsewhere; see ops.py for dispatch)."""
import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Whether a Pallas call runs in the interpreter.  ``None`` decides
    from the backend: compiled Mosaic on a TPU, the interpreter on any
    other backend.  Every kernel entry point resolves its ``interpret``
    argument here."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


# submodules import interpret_mode from this package, so they load after it
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.chunk_prefill_attn import (  # noqa: E402
    chunk_prefill_attention,
    chunk_prefill_attention_sharded,
)
from repro.kernels.decode_layer import (  # noqa: E402
    decode_layer,
    decode_layer_sharded,
    logits_sample,
    logits_sample_sharded,
    tp_head_plan,
)
