"""The ``dense_gqa`` kind: a uniform decoder of grouped-query attention and
a SwiGLU MLP in every layer, as a configuration file under
``bench/configs/`` states it with the Hugging Face keys.

A kind module exports the program's ``ModelConfig`` for a configuration
file and the operations its work needs, from the file's sizes:
``model_config``, ``linear_shapes``, ``mpgemm_ops_per_token``,
``attention_ops`` and ``head_ops``.
"""
from __future__ import annotations


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig, uniform_layers

    n = cfg["num_hidden_layers"]
    return ModelConfig(
        name=cfg["name"], n_layers=n, d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        layers=uniform_layers(n, rope_theta=float(cfg["rope_theta"])),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=cfg["dtype"],
    )


def linear_shapes(cfg: dict) -> list[tuple[int, int]]:
    """(out, in) of every ternary linear in one decoder layer."""
    d, h, kv, hd, f = (cfg["hidden_size"], cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"],
                       cfg["intermediate_size"])
    return [(h * hd, d), (kv * hd, d), (kv * hd, d), (d, h * hd),
            (f, d), (f, d), (d, f)]


def mpgemm_ops_per_token(cfg: dict) -> float:
    """Ternary-matmul operations one token needs through the whole stack."""
    per_layer = sum(2.0 * m * k for m, k in linear_shapes(cfg))
    return per_layer * cfg["num_hidden_layers"]


def attention_ops(cfg: dict, keys: int) -> float:
    """Operations of one query token attending ``keys`` cached positions in
    every layer (scores and the weighted sum of values)."""
    return (4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * keys
            * cfg["num_hidden_layers"])


def head_ops(cfg: dict) -> float:
    """Operations of one row of output logits."""
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
