"""What the algorithm needs of the kernels, from the shapes, whatever
implements it: operations and bytes, and the least time at the roofline.
A share above 100 % of a roofline is a fault here or in the time, never
clipped.  What a token of a *model* needs (model FLOPs) depends on the
architecture and is counted beside its family (``families/``), reached
through ``ctx.family().counts``."""

from __future__ import annotations


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
