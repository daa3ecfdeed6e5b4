"""Model FLOPs of the Llama family: what the algorithm needs for a token
of a dense MHA/GQA decoder with a SwiGLU FFN, whatever implements it.
Every parameter of a block works on every token, so the parameters used
are the parameters held; a family with experts counts those a token
uses."""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matmul for every token: all but the
    embedding table (a gather) and the norm scales."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d


def total_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    norms = (2 * cfg["num_hidden_layers"] + 1) * d
    return matmul_params(cfg) + cfg["vocab_size"] * d + norms


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward, no recomputation: 6 per matmul parameter, plus
    causal attention (QK^T and PV, forward 2 x 2 x seq/2 x width per token
    and layer, backward twice that): 6 x L x seq x width."""
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return 6.0 * matmul_params(cfg) + 6.0 * cfg["num_hidden_layers"] * seq * width


def serve_flops(cfg: dict, prompt_lens, decode_positions) -> float:
    """Forward only.  ``prompt_lens``: true lengths of the prompts
    prefilled; ``decode_positions``: for every token decoded, how many
    cache rows it attended.  2 per matmul parameter and token, plus
    attention 4 x width x rows attended (causal: p(p+1)/2 for a prompt)."""
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    layers = cfg["num_hidden_layers"]
    tokens = sum(prompt_lens) + len(decode_positions)
    rows = sum(p * (p + 1) // 2 for p in prompt_lens) + sum(decode_positions)
    return 2.0 * matmul_params(cfg) * tokens + 4.0 * width * layers * rows
