"""The Qwen2-architecture decoder (Qwen1.5): how the program runs a
configuration of this family, and what a training step of it costs.

* ``model_config(c)`` — the program's ``ModelConfig`` with every size from the
  configuration file (Hugging Face keys);
* ``flops_per_token(c, seq)`` — model FLOPs of one trained token, by
  ``flops.py``'s rule: 3 x (2 x the matmul parameters) plus the attention
  products over the positions a causal query sees.
"""

from __future__ import annotations

from dataclasses import replace


def model_config(c: dict):
    from repro.configs import ARCHS
    return replace(
        ARCHS[c["program"]["arch"]],
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"], dtype=c["torch_dtype"],
    )


def matmul_params(c: dict) -> int:
    D, H = c["hidden_size"], c["num_attention_heads"]
    Hkv, F, V, L = c["num_key_value_heads"], c["intermediate_size"], c["vocab_size"], \
        c["num_hidden_layers"]
    hd = D // H
    attn = D * H * hd + 2 * D * Hkv * hd + H * hd * D
    mlp = 3 * D * F
    return L * (attn + mlp) + D * V


def attention_flops_per_token(c: dict, seq: int) -> float:
    """Forward + backward QK^T and PV, causal: (seq + 1) / 2 keys per query."""
    H, D, L = c["num_attention_heads"], c["hidden_size"], c["num_hidden_layers"]
    hd = D // H
    forward = 2 * 2 * H * hd * (seq + 1) / 2
    return 3 * forward * L


def flops_per_token(c: dict, seq: int) -> float:
    return 6 * matmul_params(c) + attention_flops_per_token(c, seq)
