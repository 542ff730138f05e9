"""Qwen3ForCausalLM (huggingface.co/Qwen/Qwen3-4B, config.json): the
program's model configuration for a configuration file with the published
key names, the model FLOPs a trained token needs, the plain reference's
loss and gradients (`reference/qwen3.py`), and the names of the RMSNorm
gain leaves in the program's parameter tree."""
from __future__ import annotations

from chipbench.reference.qwen3 import loss_and_grads  # noqa: F401

# RMSNorm gains, stored as offsets from 1: before attention and the MLP,
# on each query and key head, and the final norm
NORM_LEAVES = ("ln", "qn", "kn", "final_ln")


def model_config(cfg: dict):
    from repro.models.config import ModelConfig
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


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs per trained token, forward and backward (3x forward):
    every matmul of the layers and of the (tied) head, and causal
    attention's score and value products. Recomputation is not counted."""
    d = cfg["hidden_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    ff, v, n = cfg["intermediate_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]
    proj = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = 3 * d * ff
    # causal: each query sees on average (seq_len + 1) / 2 keys
    attn = 2 * h * hd * (seq_len + 1) / 2
    fwd = 2 * (n * (proj + mlp) + d * v) + 2 * n * attn
    return 3.0 * fwd
