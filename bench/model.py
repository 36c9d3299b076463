"""A configuration file, ``bench/configs/<config>.json``, read into the sizes
that the weights, the reference and the FLOP/byte counts share.

The file holds the published ``config.json`` keys as they are run (the keys
changed from the source are listed under ``reduced``, with the source's values
under ``source_values``), plus ``serving``: the grid and engine settings of the
deployment, and ``check``: the limit of the comparison that decides
``correct``.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

CONFIG_DIR = Path(__file__).resolve().parent / "configs"


@dataclasses.dataclass(frozen=True)
class Dense:
    """A llama-family decoder as the configuration file states it."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    tied: bool
    rope_theta: float
    norm_eps: float
    instances: int

    @property
    def head_dim(self) -> int:
        return self.d_model // self.heads


def load(name: str) -> dict:
    return json.loads((CONFIG_DIR / f"{name}.json").read_text())


def dense(cfg: dict) -> Dense:
    if cfg["architecture"] != "dense" or cfg["hidden_act"] != "silu":
        raise ValueError(f"not a dense SwiGLU decoder: {cfg['architecture']}")
    return Dense(
        layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        qkv_bias=cfg["qkv_bias"], tied=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        instances=cfg["serving"]["instances"],
    )
