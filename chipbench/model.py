"""The program's model configuration for a benchmark configuration file
that uses the published config's key names, and the seed a run's weights
are drawn from."""
from __future__ import annotations

import numpy as np


def seed32(seed: int) -> int:
    """A seed JAX's PRNG takes, drawn from a run's seed of any size."""
    return int(np.random.default_rng(seed).integers(0, 2**31 - 1))


def model_config(cfg: dict):
    from repro.models.config import ModelConfig
    if cfg["architectures"] != ["Qwen3ForCausalLM"]:
        raise ValueError(f"no mapping for {cfg['architectures']}")
    return ModelConfig(
        name=cfg["name"], family="transformer",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), qk_norm=True,
        qkv_bias=cfg["attention_bias"], act=cfg["hidden_act"],
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"])
