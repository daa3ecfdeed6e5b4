"""The DeepSeek-V3 family's plain reference (``model_type: deepseek_v3``:
DeepSeek-V3, Kanana-2-30B-A3B): multi-head latent attention and a
sigmoid-routed mixture of experts with shared experts, in straightforward
``jax.numpy`` and float32 at ``HIGHEST``.  No kernels, no cache, no
batching tricks: the EXPANDED form of the attention only, and every
expert layer as a masked sum over ALL its experts.

The equations, per layer, ``x`` the RMS-normed input (published modeling
code of ``deepseek_v3`` in ``transformers``; sizes from the
configuration file):

    q            = W_q x                  per head [q_nope ; q_rope]
    [c ; k_r]    = W_kv_a x;  c <- RMSNorm(c) (own scale);  k_r shared by all heads
    rope         on q_rope (per head) and k_r, pairs (2i, 2i+1) (rope_interleave)
    [k_nope ; v] = W_kv_b c               per head
    k            = [k_nope ; k_r]
    o            = causal softmax(q . k / sqrt(qk width)) v;  y = W_o o
    FFN, layer < first_k_dense_replace:   SwiGLU(intermediate_size)
    FFN, the others:  s = sigmoid(W_r x);  the num_experts_per_tok largest of
                      s + b chosen (b = e_score_correction_bias, choice only);
                      w = s_chosen / sum(s_chosen) * routed_scaling_factor;
                      y = sum_e w_e SwiGLU_e(x) + SwiGLU_shared(x)

Departures, each noted where it is made: the rope rotates the pairs in
place (the published code permutes them to the half-split layout first;
the permutation is the same on both sides of every dot product and
cancels); ``e_score_correction_bias`` is drawn by the seed's rule like
every other leaf (published checkpoints start it at zero); the
renormalisation's published ``+ 1e-20`` is left out (sigmoid scores are
positive, and the sum of six of them is far from float32's smallest
normal); ``n_group = topk_group = 1``, so group-limited choice is the
identity and is not written.

It imports nothing of ``torchdistx_tpu`` and takes nothing the program
made.  ``leaf_plan`` names every parameter as the program's
``DeepseekV3`` does, in construction order, so that the seed's rule
arrives at the bits ``deferred_init`` -> ``materialize`` makes.  A
layer's weights are alive one layer at a time.  No ``TrainReference``:
the family has no training cell.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from harness.reference import HIGHEST, PRECISIONS, leaf, linear, rms_norm

__all__ = ["PRECISIONS", "Arch", "leaf_plan", "ServeReference"]


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the reference needs, under the published names."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    first_k_dense_replace: int
    routed_scaling_factor: float
    rope_theta: float
    rms_norm_eps: float
    max_position_embeddings: int
    dtype: str = "bfloat16"
    init_std: float = 0.02

    @classmethod
    def from_config(cls, cfg: dict) -> "Arch":
        ints = ("vocab_size", "hidden_size", "num_hidden_layers",
                "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "intermediate_size",
                "moe_intermediate_size", "n_routed_experts",
                "n_shared_experts", "num_experts_per_tok",
                "first_k_dense_replace", "max_position_embeddings")
        floats = ("routed_scaling_factor", "rope_theta", "rms_norm_eps")
        return cls(
            **{k: int(cfg[k]) for k in ints},
            **{k: float(cfg[k]) for k in floats},
            dtype=str(cfg.get("torch_dtype", "bfloat16")),
            init_std=float(cfg.get("initializer_range", 0.02)),
        )

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


# -- the parameters, in construction order ------------------------------------


def block_leaves(a: Arch, layer: int):
    """One block's leaves in construction order: ``(name, shape, drawn)``;
    a norm scale is not drawn (ones).  Matrices of a linear layer are
    (out, in); the expert stacks are (experts, in, out) for gate and up
    and (experts, out-of-gate, in-of-model) for down, as the program
    holds them."""
    d, h = a.hidden_size, a.num_attention_heads
    out = [
        ("attn_norm.weight", (d,), False),
        ("attn.wq.weight", (h * a.qk_head_dim, d), True),
        ("attn.wkv_a.weight", (a.kv_lora_rank + a.qk_rope_head_dim, d), True),
        ("attn.kv_norm.weight", (a.kv_lora_rank,), False),
        ("attn.wkv_b.weight",
         (h * (a.qk_nope_head_dim + a.v_head_dim), a.kv_lora_rank), True),
        ("attn.wo.weight", (d, h * a.v_head_dim), True),
        ("mlp_norm.weight", (d,), False),
    ]
    if layer < a.first_k_dense_replace:
        f = a.intermediate_size
        out += [("mlp.w_gate.weight", (f, d), True),
                ("mlp.w_up.weight", (f, d), True),
                ("mlp.w_down.weight", (d, f), True)]
    else:
        e, f = a.n_routed_experts, a.moe_intermediate_size
        fs = a.n_shared_experts * f
        out += [("mlp.router.weight", (e, d), True),
                ("mlp.e_score_correction_bias", (e,), True),
                ("mlp.w_gate", (e, d, f), True),
                ("mlp.w_up", (e, d, f), True),
                ("mlp.w_down", (e, f, d), True),
                ("mlp.shared.w_gate.weight", (fs, d), True),
                ("mlp.shared.w_up.weight", (fs, d), True),
                ("mlp.shared.w_down.weight", (d, fs), True)]
    return out


def leaf_plan(a: Arch):
    """Every parameter as ``(name, shape, counter)``; ``counter`` is None
    for a norm scale (ones), else the leaf's number in the key stream."""
    plan = [("tok_emb.weight", (a.vocab_size, a.hidden_size), 0)]
    c = 1
    for layer in range(a.num_hidden_layers):
        for name, shape, drawn in block_leaves(a, layer):
            plan.append((f"blocks.{layer}.{name}", shape, c if drawn else None))
            c += int(drawn)
    plan.append(("norm.weight", (a.hidden_size,), None))
    plan.append(("lm_head.weight", (a.vocab_size, a.hidden_size), c))
    return plan


def block_weights_from_seed(a: Arch, seed: int, layer: int, plan=None) -> dict:
    """The leaves of block ``layer`` by their names within the block."""
    pre = f"blocks.{layer}."
    return {name[len(pre):]: leaf(a, seed, counter, shape)
            for name, shape, counter in (plan or leaf_plan(a))
            if name.startswith(pre)}


# -- the mathematics --------------------------------------------------------


def rope_tables(a: Arch, length: int):
    r = a.qk_rope_head_dim
    inv = 1.0 / (a.rope_theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = jnp.outer(jnp.arange(length, dtype=jnp.float32), inv)
    return jnp.cos(ang), jnp.sin(ang)  # (T, r/2) each


def rope(x, cos, sin):
    """x (B, T, H, r): the pairs (2i, 2i+1) rotated in place."""
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).reshape(x.shape)


def attention(q, k, v, scale):
    """Causal softmax attention, float32: q, k (B, T, H, qk), v (B, T, H,
    v).  A head of a row at a time: the scores are T x T."""
    t = q.shape[1]
    mask = jnp.tril(jnp.ones((t, t), bool))

    def one_head(qkv):
        q1, k1, v1 = qkv  # (T, qk), (T, qk), (T, v)
        s = jnp.einsum("td,sd->ts", q1, k1, precision=HIGHEST) * scale
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("ts,sd->td", p, v1, precision=HIGHEST)

    def one_row(qkv):
        heads_first = [jnp.swapaxes(x, 0, 1) for x in qkv]  # (H, T, .)
        return jnp.swapaxes(jax.lax.map(one_head, heads_first), 0, 1)

    o = jax.lax.map(one_row, (q, k, v))  # (B, T, H, v)
    return o.reshape(*o.shape[:2], -1)


def swiglu(x, w_gate, w_up, w_down, precision):
    """Matrices (out, in), as ``linear`` takes them."""
    h = jax.nn.silu(linear(x, w_gate, precision)) * linear(x, w_up, precision)
    return linear(h, w_down, precision)


def experts(a: Arch, precision: str, x, w):
    """The expert layer on the normed ``x`` (B, T, D): every expert on
    every token, summed under the router's weights (zero where the
    expert was not chosen), plus the shared expert."""
    scores = jax.nn.sigmoid(linear(x, w["mlp.router.weight"], precision))
    bias = w["mlp.e_score_correction_bias"].astype(jnp.float32)
    _, chosen = jax.lax.top_k(scores + bias, a.num_experts_per_tok)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    picked = picked * a.routed_scaling_factor
    onehot = jax.nn.one_hot(chosen, a.n_routed_experts, dtype=jnp.float32)
    weights = jnp.einsum("btk,btke->bte", picked, onehot)  # (B, T, E)

    def add_expert(y, ew):
        w_gate, w_up, w_down, we = ew  # (D, F), (D, F), (F, D), (B, T)
        out = swiglu(x, w_gate.T, w_up.T, w_down.T, precision)
        return y + we[..., None] * out, None

    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (w["mlp.w_gate"], w["mlp.w_up"], w["mlp.w_down"],
         jnp.moveaxis(weights, -1, 0)))
    return y + swiglu(x, w["mlp.shared.w_gate.weight"],
                      w["mlp.shared.w_up.weight"],
                      w["mlp.shared.w_down.weight"], precision)


def block(a: Arch, precision: str, layer_is_dense: bool, x, w):
    """One decoder block.  x (B, T, D) float32; ``w`` maps a block's leaf
    names (without the ``blocks.N.`` prefix) to arrays."""
    b, t, _ = x.shape
    h_, nope, r = a.num_attention_heads, a.qk_nope_head_dim, a.qk_rope_head_dim
    cos, sin = rope_tables(a, t)
    h = rms_norm(x, w["attn_norm.weight"], a.rms_norm_eps)
    q = linear(h, w["attn.wq.weight"], precision).reshape(b, t, h_, a.qk_head_dim)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], cos, sin)], axis=-1)
    ckr = linear(h, w["attn.wkv_a.weight"], precision)
    c = rms_norm(ckr[..., :a.kv_lora_rank], w["attn.kv_norm.weight"],
                 a.rms_norm_eps)
    k_r = rope(ckr[..., a.kv_lora_rank:].reshape(b, t, 1, r), cos, sin)
    kv = linear(c, w["attn.wkv_b.weight"], precision).reshape(
        b, t, h_, nope + a.v_head_dim)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, t, h_, r))], axis=-1)
    o = attention(q, k, kv[..., nope:], 1.0 / math.sqrt(a.qk_head_dim))
    x = x + linear(o, w["attn.wo.weight"], precision)
    h = rms_norm(x, w["mlp_norm.weight"], a.rms_norm_eps)
    if layer_is_dense:
        return x + swiglu(h, w["mlp.w_gate.weight"], w["mlp.w_up.weight"],
                          w["mlp.w_down.weight"], precision)
    return x + experts(a, precision, h, w)


def head_logits(a: Arch, precision: str, x, norm_w, head_w):
    return linear(rms_norm(x, norm_w, a.rms_norm_eps), head_w, precision)


# -- serving: logits of whole sequences, weights never all alive ------------


class ServeReference:
    """Logits of whole (N, T) sequences, float32, a layer's weights alive
    at a time; ``logits_rows`` hands them out a row at a time, since
    (N, T, vocab) in one piece is too much."""

    def __init__(self, arch: Arch, seed: int, precision: str = "f32"):
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
        self.a, self.seed, self.precision = arch, seed, precision
        a = arch
        self.plan = leaf_plan(a)
        self._embed = jax.jit(
            lambda emb, tokens: jnp.take(emb, tokens, axis=0).astype(jnp.float32))
        self._dense = jax.jit(lambda x, w: block(a, precision, True, x, w))
        self._sparse = jax.jit(lambda x, w: block(a, precision, False, x, w))
        self._head = jax.jit(
            lambda x, norm_w, head_w: head_logits(a, precision, x, norm_w, head_w))

    def hidden(self, tokens):
        a = self.a
        emb = leaf(a, self.seed, 0, (a.vocab_size, a.hidden_size))
        x = self._embed(emb, jnp.asarray(tokens, jnp.int32))
        del emb
        for layer in range(a.num_hidden_layers):
            w = block_weights_from_seed(a, self.seed, layer, self.plan)
            step = self._dense if layer < a.first_k_dense_replace else self._sparse
            x = step(x, w)
            del w
        return x

    def logits_rows(self, tokens):
        """Yield (row index, (T, vocab) float32 device array)."""
        a = self.a
        x = self.hidden(tokens)
        _, shape, counter = self.plan[-1]
        head_w = leaf(a, self.seed, counter, shape)
        norm_w = jnp.ones((a.hidden_size,), a.jdtype)
        for i in range(x.shape[0]):
            yield i, self._head(x[i:i + 1], norm_w, head_w)[0]
