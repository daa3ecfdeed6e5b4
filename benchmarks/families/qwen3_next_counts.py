"""Model FLOPs of the Qwen3-Next family, and what its own kernels need:
what the algorithm needs, whatever implements it.

A token uses every mixer's matrices (a Gated-DeltaNet layer's two in
projections and its out projection, an attention layer's four), and of
every layer's expert layer the router, the shared expert with its gate
and the routed experts it chose THAT ARE HELD HERE: of its
``num_experts_per_tok`` choices over ``router_width`` experts,
``num_experts / router_width`` fall on this configuration's share on
average (2.5 of 10 at 128 of 512), and the others are computed
elsewhere.  The head works once for a token that is SAMPLED (the last
position of a prompt, every decoded token).  The delta rule is ``7 x Dk
x Dv`` operations a token, layer and value head in its token-by-token
form (a product for the decay; a product and a sum each for ``S^T k``,
for ``k d^T`` and for ``S^T q``).  Attention is counted over the rows
attended, in the attention layers only.

The kernels' needs are in the configuration's stated dtypes: bfloat16
rows (the program hands its delta-rule kernels ``q`` and ``k`` in
float32 after their normalisation: the need counts 2 bytes, so a share
computed from it can only read low) and the float32 state
(``assumed.gdn_state_dtype``)."""

from __future__ import annotations

ITEMSIZE = 2  # the configurations state bfloat16
STATE_ITEMSIZE = 4  # assumed.gdn_state_dtype: float32
CHUNK = 64  # rows a chunk of the chunked delta rule


def key_dim(cfg: dict) -> int:
    return cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]


def value_dim(cfg: dict) -> int:
    return cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]


def conv_dim(cfg: dict) -> int:
    return 2 * key_dim(cfg) + value_dim(cfg)


def router_width(cfg: dict) -> int:
    return cfg.get("router_width", cfg["num_experts"])


def layer_split(cfg: dict) -> tuple[int, int]:
    """(Gated-DeltaNet layers, attention layers)."""
    attn = sum(1 for layer in range(cfg["num_hidden_layers"])
               if (layer + 1) % cfg["full_attention_interval"] == 0)
    return cfg["num_hidden_layers"] - attn, attn


def gdn_matmul_params(cfg: dict) -> int:
    """in_proj_qkvz (H x (conv_dim + value_dim)), in_proj_ba (H x 2 Hv),
    out_proj (value_dim x H)."""
    h = cfg["hidden_size"]
    return (h * (conv_dim(cfg) + value_dim(cfg))
            + h * 2 * cfg["linear_num_value_heads"] + value_dim(cfg) * h)


def gdn_other_params(cfg: dict) -> int:
    """The convolution, dt_bias, A_log, the gated norm's scale."""
    hv = cfg["linear_num_value_heads"]
    return (conv_dim(cfg) * cfg["linear_conv_kernel_dim"] + 2 * hv
            + cfg["linear_value_head_dim"])


def gdn_params(cfg: dict) -> int:
    """A Gated-DeltaNet mixer whole (33,718,464 at the published widths)."""
    return gdn_matmul_params(cfg) + gdn_other_params(cfg)


def attention_matmul_params(cfg: dict) -> int:
    """q_proj (query and gate), k_proj, v_proj, o_proj."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * heads * 2 * hd + 2 * h * kv * hd + heads * hd * h


def attention_params(cfg: dict) -> int:
    """An attention mixer whole, its q/k norms too (27,263,488)."""
    return attention_matmul_params(cfg) + 2 * cfg["head_dim"]


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices (3,145,728)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_expert_intermediate_size"]


def expert_layer_fixed_matmul_params(cfg: dict) -> int:
    """The router, the shared expert and its gate."""
    h = cfg["hidden_size"]
    return h * router_width(cfg) + shared_params(cfg) + h


def expert_layer_fixed_params(cfg: dict) -> int:
    """An expert layer outside its routed experts, a block's two layer
    norms counted with it (4,200,448)."""
    return expert_layer_fixed_matmul_params(cfg) + 2 * cfg["hidden_size"]


def experts_used_here(cfg: dict) -> float:
    """Of a token's choices, those that fall on the experts held."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] / router_width(cfg)


def matmul_params_used(cfg: dict) -> float:
    """Per token, without the head."""
    gdn, attn = layer_split(cfg)
    ffn = (expert_layer_fixed_matmul_params(cfg)
           + experts_used_here(cfg) * expert_params(cfg))
    return (gdn * (gdn_matmul_params(cfg) + ffn)
            + attn * (attention_matmul_params(cfg) + ffn))


def total_params(cfg: dict) -> int:
    """Parameters held: the embedding and the head, every layer with the
    experts it holds, the final norm (3,667,251,328 as the cell runs)."""
    gdn, attn = layer_split(cfg)
    h = cfg["hidden_size"]
    ffn = expert_layer_fixed_params(cfg) + cfg["num_experts"] * expert_params(cfg)
    return (2 * cfg["vocab_size"] * h + h
            + gdn * (gdn_params(cfg) + ffn) + attn * (attention_params(cfg) + ffn))


def delta_rule_flops(cfg: dict) -> int:
    """A token in one Gated-DeltaNet layer, the token-by-token form."""
    return (7 * cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"])


def attention_width(cfg: dict) -> int:
    """QK^T and PV, multiply-adds a row attended in one attention layer."""
    return 2 * cfg["num_attention_heads"] * cfg["head_dim"]


def serve_flops(cfg: dict, prompt_lens, decode_positions) -> float:
    """Forward only.  ``prompt_lens``: true lengths of the prompts
    prefilled; ``decode_positions``: for every token decoded, how many
    cache rows it attended."""
    gdn, attn = layer_split(cfg)
    tokens = sum(prompt_lens) + len(decode_positions)
    sampled = len(prompt_lens) + len(decode_positions)
    rows = sum(p * (p + 1) // 2 for p in prompt_lens) + sum(decode_positions)
    return (2.0 * matmul_params_used(cfg) * tokens
            + 2.0 * cfg["vocab_size"] * cfg["hidden_size"] * sampled
            + float(gdn * delta_rule_flops(cfg)) * tokens
            + 2.0 * attention_width(cfg) * attn * rows)


# -- the family's kernels: operations and bytes from the shapes ----------------


def gdn_state_bytes(cfg: dict) -> int:
    """One slot's ``S`` in one Gated-DeltaNet layer (2,097,152 at 32
    heads of 128 x 128 float32)."""
    return (cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"] * STATE_ITEMSIZE)


def conv_state_bytes(cfg: dict) -> int:
    """One slot's last ``K - 1`` conv inputs in one layer (49,152)."""
    return (cfg["linear_conv_kernel_dim"] - 1) * conv_dim(cfg) * ITEMSIZE


def state_slot_bytes(cfg: dict) -> int:
    """What one slot holds of recurrent state, all Gated-DeltaNet
    layers: what the engine's ``state_slot_bytes`` gauge reads
    (12,877,824 as the cell runs)."""
    return layer_split(cfg)[0] * (gdn_state_bytes(cfg) + conv_state_bytes(cfg))


def _row_bytes(cfg: dict) -> int:
    """A token's operands of the delta rule in one layer: q, k, v in and
    o out at the stated dtype, g and beta a head in float32."""
    return ((2 * key_dim(cfg) + 2 * value_dim(cfg)) * ITEMSIZE
            + 2 * cfg["linear_num_value_heads"] * STATE_ITEMSIZE)


def gdn_update_need(cfg: dict, slots: float) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's decode step over ``slots`` slots
    that decoded a token: each one's state read once and written once,
    its row operands once."""
    flops = float(delta_rule_flops(cfg)) * slots
    nbytes = slots * (2 * gdn_state_bytes(cfg) + _row_bytes(cfg))
    return flops, float(nbytes)


def gdn_chunk_need(cfg: dict, true_len: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's chunked delta rule over a prompt of
    ``true_len`` TRUE tokens (not the bucket's rows), in chunks of
    ``CHUNK``: a chunk and head's ``K K^T`` and ``Q K^T`` (4 C^2 Dk), the
    inverse of its unit lower triangle by squarings (log2(C) - 1
    squarings and as many products: 4 (log2(C) - 1) C^3), three products
    with the state (6 C Dk Dv) and two with the corrections (4 C^2 Dv);
    the row operands read and written once, the state read and written
    once."""
    c, dk, dv = CHUNK, cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    squarings = c.bit_length() - 2
    per_chunk = (4 * c * c * dk + 4 * squarings * c ** 3
                 + 6 * c * dk * dv + 4 * c * c * dv)
    chunks = -(-true_len // c)
    flops = float(per_chunk) * chunks * cfg["linear_num_value_heads"]
    nbytes = true_len * _row_bytes(cfg) + 2 * gdn_state_bytes(cfg)
    return flops, float(nbytes)


def grouped_matmul_need(cfg: dict, rows: float, groups: float) -> tuple[float, float]:
    """(FLOPs, bytes) of the HELD routed experts' SwiGLU over ``rows``
    (token, expert) rows that touch ``groups`` held experts: three
    matmuls a row; the weights of every expert touched read once, a
    row's input read and its output written once."""
    d = cfg["hidden_size"]
    flops = 2.0 * expert_params(cfg) * rows
    nbytes = groups * expert_params(cfg) * ITEMSIZE + rows * 2 * d * ITEMSIZE
    return flops, nbytes
