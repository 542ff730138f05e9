"""MellumForCausalLM (huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct,
config.json) on one device's share of its experts: the program's model
configuration for a configuration file with the published key names, the
model FLOPs a trained token needs and those of the grouped expert
matmuls, the plain reference's loss and gradients (`reference/mellum2.py`),
and the names of the RMSNorm gain leaves in the program's parameter tree.

A configuration states the published `layer_types`, `mlp_layer_types`
and `rope_parameters` whole and runs the first `num_hidden_layers` of
them; `num_experts` is how many experts this device holds, from
`expert_offset` on, of the `num_experts_published` the router chooses
among.
"""
from __future__ import annotations

from chipbench.reference import mellum2 as ref

# RMSNorm gains, stored as offsets from 1: before attention and the MLP,
# and the final norm
NORM_LEAVES = ("ln", "final_ln")

# the reference's reading of each configuration `model_config` was given,
# by name
_SPECS: dict = {}


def _pattern(cfg: dict) -> int:
    """The period of the layers' attention pattern: every period-th layer
    is full attention, the others sliding; what the program can run."""
    n = cfg["num_hidden_layers"]
    kinds = cfg["layer_types"][:n]
    period = kinds.index("full_attention") + 1
    want = ["full_attention" if (i + 1) % period == 0
            else "sliding_attention" for i in range(n)]
    if kinds != want or set(cfg["mlp_layer_types"][:n]) != {"sparse"}:
        raise ValueError(f"layer pattern {kinds} is not sliding layers "
                         "then one full layer, repeated, every MLP sparse")
    return period


def model_config(cfg: dict):
    from repro.models.config import ModelConfig
    rp = cfg["rope_parameters"]
    full, sliding = rp["full_attention"], rp["sliding_attention"]
    if (full["rope_type"], sliding["rope_type"]) != ("yarn", "default") \
            or full["rope_theta"] != sliding["rope_theta"]:
        raise ValueError(f"rope_parameters {rp}: the program runs YaRN on "
                         "full layers and the default RoPE on sliding "
                         "ones, with one theta")
    if not cfg["norm_topk_prob"]:
        raise ValueError("the program renormalizes the top-k weights")
    _SPECS[cfg["name"]] = ref.spec(cfg)
    return ModelConfig(
        name=cfg["name"], family="transformer",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        vocab=cfg["vocab_size"], rope_theta=float(full["rope_theta"]),
        qk_norm=False, qkv_bias=cfg["attention_bias"],
        sliding_window=cfg["sliding_window"], global_every=_pattern(cfg),
        n_experts=cfg["num_experts_published"],
        top_k=cfg["num_experts_per_tok"],
        d_ff_expert=cfg["moe_intermediate_size"],
        experts_held=cfg["num_experts"], expert_offset=cfg["expert_offset"],
        yarn_factor=float(full["factor"]),
        yarn_original_max_pos=full["original_max_position_embeddings"],
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=float(full["attention_factor"]),
        act=cfg["hidden_act"], norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"])


def held_pairs_per_token(cfg: dict) -> float:
    """(token, expert) pairs a token gives this device's experts, on
    average over uniform routing: top-k times the share held."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["num_experts_published"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs per trained token, forward and backward (3x forward):
    the attention projections, the router, the held experts' share of the
    expert matmuls, the untied head over the vocabulary held, and causal
    attention's score and value products over the keys each layer sees
    (the last `sliding_window` on sliding layers). Recomputation is not
    counted."""
    d = cfg["hidden_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    n, v = cfg["num_hidden_layers"], cfg["vocab_size"]
    proj = d * h * hd + 2 * d * kv * hd + h * hd * d
    router = d * cfg["num_experts_published"]
    experts = held_pairs_per_token(cfg) * 3 * d * cfg["moe_intermediate_size"]
    w = cfg["sliding_window"]
    keys = 0.0
    for kind in cfg["layer_types"][:n]:
        # query i sees min(i + 1, window) keys; the mean over positions
        seen = seq_len if kind == "full_attention" else min(w, seq_len)
        keys += (seen * (seen + 1) / 2 + (seq_len - seen) * seen) / seq_len
    fwd = 2 * (n * (proj + router + experts) + d * v) + 2 * 2 * h * hd * keys
    return 3.0 * fwd


def gmm_flops(cfg: dict, held_pairs: float) -> float:
    """FLOPs the grouped matmuls (`gmm`, `tgmm`) do for `held_pairs`
    (token, expert) pairs routed to the held experts, summed over the
    layers: the gate, up and down matrices in the forward, again in the
    backward's recomputation of the forward (the trainer rematerializes
    each layer), and for the gradients of their inputs and of the
    weights: 4 x 3 x 2 x pairs x hidden x expert width."""
    return 24.0 * held_pairs * cfg["hidden_size"] \
        * cfg["moe_intermediate_size"]


def loss_and_grads(cfg_items: tuple, params, tokens):
    """The reference's loss, gradients and gradient norm for `tokens`.
    `cfg_items` are the configuration's top-level numbers and strings; the
    reference reads the configuration whole, as `model_config` was given
    it (its nested groups too), so both read one source."""
    items = dict(cfg_items)
    sp = _SPECS.get(items["name"])
    if sp is None:
        raise LookupError(f"no configuration {items['name']!r} went through "
                          "model_config first")
    return ref.loss_and_grads(sp, params, tokens)
