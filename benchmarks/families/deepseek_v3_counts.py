"""Model FLOPs of the DeepSeek-V3 family, and what its own kernels need:
what the algorithm needs, whatever implements it.

A token uses the attention's matrices, and of a layer's FFN either the
dense SwiGLU (the leading ``first_k_dense_replace`` layers) or the
router, the shared expert and the ``num_experts_per_tok`` experts it was
routed to -- not the experts the layer holds.  The head works once for a
token that is SAMPLED (the last position of a prompt, every decoded
token), not once for a prompt token.  Attention is counted in the
EXPANDED widths, ``2 x heads x (qk + v)`` a row attended, prefill and
decode alike: the absorbed form a decode step runs does about 3.4 times
that (it scores on ``kv_lora_rank + rope`` lanes and sums
``kv_lora_rank``-wide values), so a share of the peak computed from
these counts can only read low."""

from __future__ import annotations

ITEMSIZE = 2  # the configurations state bfloat16


def attention_params(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    latent = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return (d * h * qk + d * latent
            + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def dense_ffn_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_layer_params_used(cfg: dict) -> int:
    """What one token uses of an expert layer: the router, the shared
    expert and the experts it was routed to."""
    return (cfg["hidden_size"] * cfg["n_routed_experts"]
            + cfg["n_shared_experts"] * expert_params(cfg)
            + cfg["num_experts_per_tok"] * expert_params(cfg))


def expert_layer_params_held(cfg: dict) -> int:
    return (cfg["hidden_size"] * cfg["n_routed_experts"]
            + (cfg["n_shared_experts"] + cfg["n_routed_experts"]) * expert_params(cfg))


def layer_split(cfg: dict) -> tuple[int, int]:
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def matmul_params_used(cfg: dict) -> int:
    """Per token, without the head: every layer's attention and the FFN
    part a token goes through."""
    dense, sparse = layer_split(cfg)
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + dense * dense_ffn_params(cfg)
            + sparse * expert_layer_params_used(cfg))


def total_params(cfg: dict) -> int:
    """Parameters held (embedding and head, every expert; norm scales
    and the selection bias left out: under a hundredth of a percent)."""
    dense, sparse = layer_split(cfg)
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + dense * dense_ffn_params(cfg)
            + sparse * expert_layer_params_held(cfg)
            + 2 * cfg["vocab_size"] * cfg["hidden_size"])


def attention_width(cfg: dict) -> int:
    """QK^T and PV of the expanded form, multiply-adds a row attended."""
    return cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])


def serve_flops(cfg: dict, prompt_lens, decode_positions) -> float:
    """Forward only.  ``prompt_lens``: true lengths of the prompts
    prefilled; ``decode_positions``: for every token decoded, how many
    cache rows it attended."""
    tokens = sum(prompt_lens) + len(decode_positions)
    sampled = len(prompt_lens) + len(decode_positions)
    rows = sum(p * (p + 1) // 2 for p in prompt_lens) + sum(decode_positions)
    return (2.0 * matmul_params_used(cfg) * tokens
            + 2.0 * cfg["vocab_size"] * cfg["hidden_size"] * sampled
            + 2.0 * attention_width(cfg) * cfg["num_hidden_layers"] * rows)


# -- the family's kernels: operations and bytes from the shapes ----------------


def latent_row_bytes(cfg: dict) -> int:
    """A cache row as the algorithm needs it: the compressed key/value
    and the shared rope key (1152 B at 512 + 64 bf16 lanes; the chip
    stores it padded to 640 lanes, which the need does not count)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * ITEMSIZE


def latent_decode_need(cfg: dict, visible_rows: float) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's absorbed decode attention over slots
    whose visible rows sum to ``visible_rows``: every row read once; the
    score on latent + rope lanes and the value on latent lanes, for every
    head."""
    latent, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    flops = 2.0 * cfg["num_attention_heads"] * (2 * latent + rope) * visible_rows
    return flops, float(latent_row_bytes(cfg)) * visible_rows


def grouped_matmul_need(cfg: dict, rows: float, groups: float) -> tuple[float, float]:
    """(FLOPs, bytes) of the routed experts' SwiGLU over ``rows`` (token,
    expert) rows that touch ``groups`` experts: three matmuls a row; the
    weights of every expert touched read once, a row's input read and
    its output written once."""
    d = cfg["hidden_size"]
    flops = 2.0 * expert_params(cfg) * rows
    nbytes = groups * expert_params(cfg) * ITEMSIZE + rows * 2 * d * ITEMSIZE
    return flops, nbytes


def mla_prefill_need(cfg: dict, prompt_len: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's causal attention over a prompt in
    the expanded widths: QK^T on qk lanes and PV on v lanes over the
    lower triangle; Q, K, V read and O written once."""
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    v = cfg["v_head_dim"]
    pairs = prompt_len * (prompt_len + 1) // 2
    flops = 2.0 * h * (qk + v) * pairs
    nbytes = float(prompt_len * h * (2 * qk + 2 * v) * ITEMSIZE)
    return flops, nbytes
