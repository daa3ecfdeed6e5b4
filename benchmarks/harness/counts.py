"""What the algorithm needs, from the shapes, whatever implements it:
operations and bytes of the kernels, and model FLOPs per token.  A share
above 100 % of a roofline is a fault here or in the time, never clipped."""

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


def flash_causal_flops(batch: int, seq: int, heads: int, head_dim: int,
                       backward: bool = False) -> float:
    """Causal attention of ``batch`` sequences of ``seq`` tokens: QK^T and
    PV over the lower triangle, seq(seq+1)/2 pairs, 2 x 2 x head_dim each.
    The backward pass (dQ, dK, dV and the recomputed scores the algorithm
    needs: 5 matmuls to the forward's 2) is 2.5 times the forward."""
    pairs = seq * (seq + 1) // 2
    fwd = 4.0 * batch * heads * head_dim * pairs
    return fwd * 2.5 if backward else fwd


def flash_bytes(batch: int, seq: int, heads: int, kv_heads: int,
                head_dim: int, itemsize: int = 2,
                backward: bool = False) -> float:
    """Least traffic: read Q, K, V and write O once (backward: read Q, K,
    V, O, dO, write dQ, dK, dV)."""
    q = batch * seq * heads * head_dim * itemsize
    kv = batch * seq * kv_heads * head_dim * itemsize
    return (3 * q + 4 * kv) if backward else (2 * q + 2 * kv)


def decode_attention_bytes(visible_rows: int, kv_heads: int, head_dim: int,
                           itemsize: int = 2) -> float:
    """One layer's decode attention over slots whose visible cache rows
    sum to ``visible_rows``: every visible K and V row is read once."""
    return 2.0 * visible_rows * kv_heads * head_dim * itemsize


def decode_attention_flops(visible_rows: int, heads: int, head_dim: int) -> float:
    return 4.0 * visible_rows * heads * head_dim


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    tc = flops / peak["bf16_flops_per_s"]
    tb = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tb else (tb, "memory")
