"""Model FLOPs of the Jamba family, and what its own kernels need: what
the algorithm needs, whatever implements it.

A token uses every matrix of every layer (``num_experts`` is 1: no
layer holds experts): a Mamba layer's in, x, dt and out projections and
its MLP, an attention layer's four projections and its MLP.  The tied
head works once for a token that is SAMPLED (the last position of a
prompt, every decoded token), not once for a prompt token.  The
recurrence is ``7 x d_inner x d_state`` operations a token and Mamba
layer (``exp``, two products and a sum for ``h``; a product for ``D_t
B_t x_t``; a product and a sum for ``y``).  Attention is counted over
the rows attended, in the TWO attention layers only.

The kernels' needs are in the configuration's stated dtypes: bfloat16
rows (the program hands its scan the step size in float32: the need
counts 2 bytes, so a share computed from it can only read low) and the
float32 recurrent state (``assumed.ssm_state_dtype``)."""

from __future__ import annotations

ITEMSIZE = 2  # the configurations state bfloat16
STATE_ITEMSIZE = 4  # assumed.ssm_state_dtype: float32


def d_inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_split(cfg: dict) -> tuple[int, int]:
    """(Mamba layers, attention layers)."""
    attn = sum(1 for layer in range(cfg["num_hidden_layers"])
               if layer % cfg["attn_layer_period"] == cfg["attn_layer_offset"])
    return cfg["num_hidden_layers"] - attn, attn


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def mamba_matmul_params(cfg: dict) -> int:
    """in (H x 2C), x (C x (R + 2N)), dt (R x C), out (C x H)."""
    h, c = cfg["hidden_size"], d_inner(cfg)
    r, n = cfg["mamba_dt_rank"], cfg["mamba_d_state"]
    return h * 2 * c + c * (r + 2 * n) + r * c + c * h


def mamba_other_params(cfg: dict) -> int:
    """The convolution and its bias, the dt bias, A_log, D, the three
    small norms."""
    c, n, r = d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    return c * cfg["mamba_d_conv"] + c + c + c * n + c + r + 2 * n


def attention_params(cfg: dict) -> int:
    h = cfg["hidden_size"]
    kv = cfg["num_key_value_heads"] * head_dim(cfg)
    return 2 * h * h + 2 * h * kv


def matmul_params_used(cfg: dict) -> int:
    """Per token, without the head."""
    mamba, attn = layer_split(cfg)
    return (mamba * (mamba_matmul_params(cfg) + mlp_params(cfg))
            + attn * (attention_params(cfg) + mlp_params(cfg)))


def total_params(cfg: dict) -> int:
    """Parameters held: the embedding once (it is the head too), every
    layer with its two norms, the final norm."""
    mamba, attn = layer_split(cfg)
    h = cfg["hidden_size"]
    return (cfg["vocab_size"] * h + h
            + mamba * (mamba_matmul_params(cfg) + mamba_other_params(cfg)
                       + mlp_params(cfg) + 2 * h)
            + attn * (attention_params(cfg) + mlp_params(cfg) + 2 * h))


def recurrence_flops(cfg: dict) -> int:
    """A token in one Mamba layer."""
    return 7 * d_inner(cfg) * cfg["mamba_d_state"]


def attention_width(cfg: dict) -> int:
    """QK^T and PV, multiply-adds a row attended in one attention layer."""
    return 2 * cfg["num_attention_heads"] * head_dim(cfg)


def serve_flops(cfg: dict, prompt_lens, decode_positions) -> float:
    """Forward only.  ``prompt_lens``: true lengths of the prompts
    prefilled; ``decode_positions``: for every token decoded, how many
    cache rows it attended."""
    mamba, attn = layer_split(cfg)
    tokens = sum(prompt_lens) + len(decode_positions)
    sampled = len(prompt_lens) + len(decode_positions)
    rows = sum(p * (p + 1) // 2 for p in prompt_lens) + sum(decode_positions)
    return (2.0 * matmul_params_used(cfg) * tokens
            + 2.0 * cfg["vocab_size"] * cfg["hidden_size"] * sampled
            + float(mamba * recurrence_flops(cfg)) * tokens
            + 2.0 * attention_width(cfg) * attn * rows)


# -- the family's kernels: operations and bytes from the shapes ----------------


def ssm_state_bytes(cfg: dict) -> int:
    """One slot's ``h`` in one Mamba layer (327,680 at 5120 x 16 float32)."""
    return d_inner(cfg) * cfg["mamba_d_state"] * STATE_ITEMSIZE


def conv_state_bytes(cfg: dict) -> int:
    """One slot's last ``K - 1`` conv inputs in one Mamba layer (30,720)."""
    return (cfg["mamba_d_conv"] - 1) * d_inner(cfg) * ITEMSIZE


def state_slot_bytes(cfg: dict) -> int:
    """What one slot holds of recurrent state, all Mamba layers: what
    the engine's ``state_slot_bytes`` gauge reads (9,318,400)."""
    return layer_split(cfg)[0] * (ssm_state_bytes(cfg) + conv_state_bytes(cfg))


def _coefficients_bytes(cfg: dict) -> int:
    """A (C x N) and Dskip (C), read once a call."""
    return (d_inner(cfg) * cfg["mamba_d_state"] + d_inner(cfg)) * ITEMSIZE


def selective_scan_need(cfg: dict, true_len: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's recurrence over a prompt of
    ``true_len`` TRUE tokens (not the bucket's rows): x, the step size
    and z read and y written once a token, B and C a token, A and Dskip
    once, the final state written once."""
    c, n = d_inner(cfg), cfg["mamba_d_state"]
    flops = float(recurrence_flops(cfg)) * true_len
    nbytes = (true_len * (4 * c + 2 * n) * ITEMSIZE
              + _coefficients_bytes(cfg) + ssm_state_bytes(cfg))
    return flops, float(nbytes)


def state_update_need(cfg: dict, slots: float) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's decode step over ``slots`` slots
    that decoded a token: each one's state read once and written once,
    its row operands (x, the step size, z in; y out; B, C) once, A and
    Dskip once a call."""
    c, n = d_inner(cfg), cfg["mamba_d_state"]
    flops = float(recurrence_flops(cfg)) * slots
    nbytes = (slots * (2 * ssm_state_bytes(cfg) + (4 * c + 2 * n) * ITEMSIZE)
              + _coefficients_bytes(cfg))
    return flops, float(nbytes)
