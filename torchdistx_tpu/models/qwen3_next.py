"""Qwen3-Next-family hybrid decoder (``model_type: qwen3_next``;
Qwen3-Next-80B-A3B): Gated-DeltaNet layers with a gated softmax
attention layer every ``full_attention_interval`` layers, every layer's
feed-forward an expert layer with a gated shared expert, untied head.

The equations (sizes of Qwen3-Next-80B-A3B: hidden ``H`` 2048; Gated
DeltaNet 16 key heads and 32 value heads of 128, so ``key_dim`` 2048,
``value_dim`` 4096, each key head serves 2 value heads, conv width 4;
attention 16 query / 2 KV heads of 256, rotary on the first 64 lanes
(``partial_rotary_factor`` 0.25, theta 1e7, the rotate-half pairing
``(i, i + 32)``); 512 experts of 512, top 10, one shared expert of 512;
``rms_norm_eps`` 1e-6; vocabulary 151,936)::

    x0 = E[tokens]
    layer l:  a  = x + Mixer_l(RMSNorm(x; input_layernorm))
              x' = a + MoE(RMSNorm(a; post_attention_layernorm))
    Mixer_l = Attention if (l + 1) % 4 == 0 else GatedDeltaNet          # full_attention_interval 4
    RMSNorm(u; w) = u / sqrt(mean(u^2) + eps) * s,  s = 1 + w published (w starts at 0): the program stores s (starts at 1)

    GatedDeltaNet(u):
      [q, k, v, z] = split(W_qkvz u)            # 2048, 2048, 4096, 4096; no bias
      [b, a]       = split(W_ba u)              # 32, 32
      [q, k, v]_t  = silu(sum_{j<4} w_conv[:, j] * [q, k, v]_{t-3+j})     # depthwise causal over the 8192 lanes, no bias
      per value head h (key head h // 2):  q = l2norm(q_h) / sqrt(128),  k = l2norm(k_h)      # eps 1e-6
      beta_t = sigmoid(b_t[h]);   g_t = -exp(A_log[h]) * softplus(a_t[h] + dt_bias[h])        # float32
      S' = exp(g_t) * S_{t-1}                   # S: (128 key, 128 value), float32, S_{-1} = 0
      d  = beta_t * (v_t - S'^T k_t)            # (128,)
      S_t = S' + k_t d^T
      o_t = S_t^T q_t                           # (128,)
      y = RMSNorm(o_t; w_norm over the 128) * silu(z_t[h])               # this one multiplies by w, not 1 + w
      out = W_out concat_h(y)                   # 4096 -> 2048

    Attention(u):
      [qg] = W_q u -> per head (query 256, gate 256);  k = W_k u, v = W_v u (2 x 256);  no bias
      q = rope64(RMSNorm(query; q_norm)),  k = rope64(RMSNorm(k; k_norm))                      # norms over the 256
      o = causal softmax(q k^T / sqrt(256)) v   (8 query heads a KV head)
      out = W_o (o * sigmoid(gate))

    MoE(u):  p = softmax(W_r u) over 512 (float32);  top 10;  w = p_top / sum(p_top)           # norm_topk_prob
             y = sum_i w_i * E_{e_i}(u) + sigmoid(w_sg . u) * E_shared(u),   E(u) = W_down(silu(W_gate u) * W_up u)
    logits = RMSNorm(x_L; norm) @ W_head^T

The column order inside ``W_qkvz`` / ``W_ba`` is the program's own
(contiguous ``q | k | v | z`` and ``b | a``; the published code
interleaves them by key head): with seeded weights it is the same
distribution, and a checkpoint loader would permute once.

**A share of the experts** (``experts_held=(lo, hi)``): the expert
layers hold experts ``lo .. hi - 1`` of ``n_experts`` and compute ``y_here =
sum_{i: lo <= e_i < hi} w_i E_{e_i}(u) + shared term``, the weights
still renormalised over all ``top_k`` choices (``nn/moe.py``); that
partial result goes on to the next layer.  It is what one chip of an
expert-parallel group computes before the exchange; nothing here stands
in for the exchange.

**What a Gated-DeltaNet layer keeps of the context** is a
``serve.kv_cache.RecurrentState``: ``conv``, the last 3 rows of the
convolution's input ``[q, k, v]`` (flat, ``(B, 3 * 8192)``, oldest
first), and ``ssm``, ``S`` as ``(B, 32, 128, 128)`` **float32** (the
state accumulates over thousands of steps): 2,146,304 B a slot and
layer, constant in the context length.  An attention layer keeps the
ordinary ``(k, v)`` pair at head 256.  ``init_cache`` returns the two
kinds layer by layer and the serve engine's slab stores them side by
side.

**A prefill is told the true length** (``models/jamba.py`` has the
contract): ``forward_cached(..., logits_at=)`` takes ``logits_at + 1`` as
the number of REAL rows; rows at and past it leave ``S`` untouched
(``g = 0``, ``beta = 0``) and ``conv`` is taken at the true length, so
the same prompt in two buckets writes the same state.

Refused by name (``Qwen3NextConfig.__post_init__``): ``mlp_only_layers``
non-empty, ``decoder_sparse_step`` != 1, ``use_sliding_window``,
``rope_scaling``, tied embeddings, ``norm_topk_prob`` false, a
multi-token-prediction module.  Training is not supported: neither the
chunked delta rule nor the grouped matmul has a backward here.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .. import nn
from ..nn.moe import MoE
from ..ops.attention import cached_attention, slot_cached_attention
from ..ops.gated_delta import gated_delta_chunk, gated_delta_update
from ..serve.kv_cache import RecurrentState
from .llama import _hf_normal, _rope_freqs, apply_rope, apply_rope_at

__all__ = ["Qwen3NextConfig", "Qwen3Next", "qwen3_next_configs"]


@dataclasses.dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    full_attention_interval: int = 4
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    gdn_key_heads: int = 16
    gdn_value_heads: int = 32
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    d_conv: int = 4
    n_experts: int = 512
    top_k: int = 10
    moe_ffn_dim: int = 512
    shared_ffn_dim: int = 512
    experts_held: Optional[tuple] = None  # (lo, hi) of n_experts; None = all
    max_seq_len: int = 262144
    norm_eps: float = 1e-6
    dtype: object = jnp.bfloat16
    use_flash: Optional[bool] = None  # None = auto: kernels on a TPU
    # what the program does one way only (refused otherwise, by name)
    mlp_only_layers: tuple = ()
    decoder_sparse_step: int = 1
    use_sliding_window: bool = False
    rope_scaling: Optional[dict] = None
    tie_word_embeddings: bool = False
    norm_topk_prob: bool = True
    mtp_layers: int = 0

    def __post_init__(self) -> None:
        if tuple(self.mlp_only_layers):
            raise ValueError(
                f"mlp_only_layers={list(self.mlp_only_layers)} is not "
                "supported: every layer's feed-forward is the expert layer "
                "(mlp_only_layers: [])"
            )
        if self.decoder_sparse_step != 1:
            raise ValueError(
                f"decoder_sparse_step={self.decoder_sparse_step} is not "
                "supported: every layer is an expert layer "
                "(decoder_sparse_step: 1)"
            )
        if self.use_sliding_window:
            raise ValueError(
                "use_sliding_window is not supported: the attention layers "
                "attend the whole context (use_sliding_window: false)"
            )
        if self.rope_scaling is not None:
            raise ValueError(
                "rope_scaling is not supported: the rotary table is the "
                "plain one (rope_scaling: null)"
            )
        if self.tie_word_embeddings:
            raise ValueError(
                "tie_word_embeddings=true is not supported: the head is a "
                "matrix of its own (tie_word_embeddings: false)"
            )
        if not self.norm_topk_prob:
            raise ValueError(
                "norm_topk_prob=false is not supported: the chosen experts' "
                "weights are renormalised (norm_topk_prob: true)"
            )
        if self.mtp_layers:
            raise ValueError(
                f"mtp_layers={self.mtp_layers} is not supported: no "
                "multi-token-prediction module is built"
            )
        if self.gdn_value_heads % self.gdn_key_heads:
            raise ValueError(
                "gdn_value_heads must be a multiple of gdn_key_heads"
            )

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_dim(self) -> int:
        return self.gdn_key_heads * self.gdn_key_dim

    @property
    def value_dim(self) -> int:
        return self.gdn_value_heads * self.gdn_value_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim

    def is_attention(self, layer: int) -> bool:
        return (layer + 1) % self.full_attention_interval == 0


qwen3_next_configs = {
    # two periods of (3 Gated DeltaNet + 1 attention), 32 experts
    "tiny": dict(
        vocab_size=256, dim=64, n_layers=8, n_heads=4, n_kv_heads=2,
        head_dim=32, gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=16,
        gdn_value_dim=16, n_experts=32, top_k=4, moe_ffn_dim=32,
        shared_ffn_dim=32, max_seq_len=128, dtype=jnp.float32,
    ),
    # Qwen/Qwen3-Next-80B-A3B-Instruct (the defaults above)
    "qwen3_next_80b_a3b": dict(),
}


def _rope_head(x, rope, offset=0, positions=None):
    """Rotary on the first ``rotary_dim`` lanes of every head (the
    table's width says how many), the rest passed through."""
    r = 2 * rope.shape[1]
    head, tail = x[..., :r], x[..., r:]
    head = (apply_rope(head, rope, offset) if positions is None
            else apply_rope_at(head, rope, positions))
    return jnp.concatenate([head, tail], axis=-1)


class Qwen3NextAttention(nn.Module):
    """Softmax attention with per-head q/k norms, rotary on a quarter of
    the head and a sigmoid gate on its output (module docstring)."""

    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.cfg = cfg
        d, hd = cfg.dim, cfg.head_dim
        lin = lambda i, o: nn.Linear(  # noqa: E731
            i, o, bias=False, dtype=cfg.dtype, weight_init=_hf_normal
        )
        self.wq = lin(d, cfg.n_heads * 2 * hd)  # query and gate
        self.wk = lin(d, cfg.n_kv_heads * hd)
        self.wv = lin(d, cfg.n_kv_heads * hd)
        self.wo = lin(cfg.n_heads * hd, d)
        self.q_norm = nn.RMSNorm(hd, eps=cfg.norm_eps, dtype=cfg.dtype)
        self.k_norm = nn.RMSNorm(hd, eps=cfg.norm_eps, dtype=cfg.dtype)

    def _qkvg(self, x):
        cfg = self.cfg
        b, s, _ = x.shape
        qg = self.wq(x).reshape(b, s, cfg.n_heads, 2 * cfg.head_dim)
        q, gate = jnp.split(qg, 2, axis=-1)
        k = self.wk(x).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        v = self.wv(x).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        return self.q_norm(q), self.k_norm(k), v, gate

    def _out(self, o, gate):
        b, s = o.shape[:2]
        with jax.named_scope("attn/gate"):
            o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
        return self.wo(o.reshape(b, s, -1))

    def forward_cached(self, x, rope, cache, cache_pos):
        q, k, v, gate = self._qkvg(x)
        q, k = _rope_head(q, rope, cache_pos), _rope_head(k, rope, cache_pos)
        out, cache = cached_attention(
            q, k, v, cache, cache_pos, use_flash=self.cfg.use_flash
        )
        return self._out(out, gate), cache

    def forward_decode(self, x, rope, cache, positions):
        q, k, v, gate = self._qkvg(x)
        q = _rope_head(q, rope, positions=positions)
        k = _rope_head(k, rope, positions=positions)
        out, cache = slot_cached_attention(
            q, k, v, cache, positions, use_flash=self.cfg.use_flash
        )
        return self._out(out, gate), cache


class GatedDeltaNet(nn.Module):
    """The Gated-DeltaNet mixer (module docstring)."""

    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.cfg = cfg
        dt, hv = cfg.dtype, cfg.gdn_value_heads
        lin = lambda i, o: nn.Linear(  # noqa: E731
            i, o, bias=False, dtype=dt, weight_init=_hf_normal
        )
        self.in_proj_qkvz = lin(cfg.dim, cfg.conv_dim + cfg.value_dim)
        self.in_proj_ba = lin(cfg.dim, 2 * hv)
        self.conv_weight = nn.Parameter(
            _hf_normal((cfg.conv_dim, cfg.d_conv), dt)
        )
        self.dt_bias = nn.Parameter(_hf_normal((hv,), dt))
        self.A_log = nn.Parameter(_hf_normal((hv,), dt))
        self.norm = nn.RMSNorm(cfg.gdn_value_dim, eps=cfg.norm_eps, dtype=dt)
        self.out_proj = lin(cfg.value_dim, cfg.dim)

    def _conv(self, taps):
        """``silu(sum_j w[:, j] * taps[j])``: ``taps`` the ``K`` inputs
        of every output, oldest first, each (..., conv_dim)."""
        w = self.conv_weight.astype(jnp.float32)
        acc = w[:, 0] * taps[0].astype(jnp.float32)
        for j, tap in enumerate(taps[1:], 1):
            acc = acc + w[:, j] * tap.astype(jnp.float32)
        return jax.nn.silu(acc).astype(taps[-1].dtype)

    def _heads(self, qkv):
        """The convolved ``[q, k, v]`` (..., conv_dim) -> ``q``, ``k``
        (..., Hk, Dk) float32, normalised (``q`` scaled too), ``v`` (...,
        Hv, Dv)."""
        cfg = self.cfg
        lead = qkv.shape[:-1]
        q, k, v = jnp.split(qkv, [cfg.key_dim, 2 * cfg.key_dim], axis=-1)

        def l2norm(x):
            x = x.astype(jnp.float32).reshape(
                *lead, cfg.gdn_key_heads, cfg.gdn_key_dim
            )
            return x * lax.rsqrt(
                jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6
            )

        q = l2norm(q) * (1.0 / math.sqrt(cfg.gdn_key_dim))
        v = v.reshape(*lead, cfg.gdn_value_heads, cfg.gdn_value_dim)
        return q, l2norm(k), v

    def _gates(self, ba):
        """``[b, a]`` (..., 2 Hv) -> ``g`` (the log of the decay, <= 0)
        and ``beta``, float32 (..., Hv)."""
        b, a = jnp.split(ba.astype(jnp.float32), 2, axis=-1)
        g = -jnp.exp(self.A_log.astype(jnp.float32)) * jax.nn.softplus(
            a + self.dt_bias.astype(jnp.float32)
        )
        return g, jax.nn.sigmoid(b)

    def _out(self, o, z):
        """``o`` (..., Hv, Dv) under the gated norm, through ``W_out``."""
        z = z.reshape(o.shape)
        y = self.norm(o) * jax.nn.silu(z.astype(jnp.float32)).astype(o.dtype)
        return self.out_proj(y.reshape(*y.shape[:-2], -1))

    def forward_cached(self, x, state, true_len=None):
        """``x`` (B, S, dim) after the state's tokens; of its rows the
        first ``true_len`` are real (None: all).  Returns the mixer's
        output and the state after the real rows."""
        if x.shape[1] == 1 and true_len is None:
            return self.forward_decode(x, state)
        cfg = self.cfg
        b, s, _ = x.shape
        k1, c = cfg.d_conv - 1, cfg.conv_dim
        n_real = s if true_len is None else true_len
        qkv, z = jnp.split(self.in_proj_qkvz(x), [c], axis=-1)
        with jax.named_scope("gdn/conv"):
            ext = jnp.concatenate(
                [state.conv.reshape(b, k1, c).astype(qkv.dtype), qkv], axis=1
            )
            qkv = self._conv([ext[:, j:j + s] for j in range(cfg.d_conv)])
            conv = lax.dynamic_slice_in_dim(ext, n_real, k1, axis=1)
        q, k, v = self._heads(qkv)
        g, beta = self._gates(self.in_proj_ba(x))
        o, ssm = gated_delta_chunk(
            q, k, v, g, beta, state.ssm, n_real, use_kernel=cfg.use_flash
        )
        return self._out(o, z), RecurrentState(
            conv.reshape(b, k1 * c).astype(state.conv.dtype), ssm
        )

    def forward_decode(self, x, state):
        """One token a row (a serving slot): ``x`` (B, 1, dim)."""
        cfg = self.cfg
        c = cfg.conv_dim
        qkv, z = jnp.split(self.in_proj_qkvz(x[:, 0]), [c], axis=-1)
        with jax.named_scope("gdn/conv"):
            old = state.conv
            conv = jnp.concatenate([old[:, c:], qkv.astype(old.dtype)], axis=-1)
            qkv = self._conv(
                [old[:, j * c:(j + 1) * c] for j in range(cfg.d_conv - 1)]
                + [qkv]
            )
        q, k, v = self._heads(qkv)
        g, beta = self._gates(self.in_proj_ba(x[:, 0]))
        o, ssm = gated_delta_update(
            state.ssm, q, k, v, g, beta, use_kernel=cfg.use_flash
        )
        return self._out(o, z)[:, None], RecurrentState(conv, ssm)


class Qwen3NextBlock(nn.Module):
    def __init__(self, cfg: Qwen3NextConfig, layer: int):
        super().__init__()
        self.is_attention = cfg.is_attention(layer)
        norm = lambda: nn.RMSNorm(  # noqa: E731
            cfg.dim, eps=cfg.norm_eps, dtype=cfg.dtype
        )
        self.input_norm = norm()
        self.mixer = (
            Qwen3NextAttention(cfg) if self.is_attention else GatedDeltaNet(cfg)
        )
        self.post_norm = norm()
        self.mlp = MoE(
            cfg.dim, cfg.moe_ffn_dim, cfg.n_experts, top_k=cfg.top_k,
            dtype=cfg.dtype, dispatch_mode="grouped", scoring="softmax",
            shared_ffn_dim=cfg.shared_ffn_dim, shared_gate=True,
            held=cfg.experts_held, weight_init=_hf_normal,
            use_kernel=cfg.use_flash,
        )

    # scopes are metadata only: the compiled operations carry
    # ``attention`` (with ``attn/gate``) / ``gdn`` (with ``gdn/conv``,
    # ``gdn/chunk``, ``gdn/update``) / ``mlp`` (with ``moe/route``,
    # ``moe/experts``, ``moe/shared``) in their op_name

    @property
    def _scope(self) -> str:
        return "attention" if self.is_attention else "gdn"

    def _mlp_half(self, x):
        with jax.named_scope("mlp"):
            return x + self.mlp(self.post_norm(x))

    def forward_cached(self, x, rope, cache, cache_pos, true_len=None):
        with jax.named_scope(self._scope):
            u = self.input_norm(x)
            if self.is_attention:
                a, cache = self.mixer.forward_cached(u, rope, cache, cache_pos)
            else:
                a, cache = self.mixer.forward_cached(u, cache, true_len)
            x = x + a
        return self._mlp_half(x), cache

    def forward_decode(self, x, rope, cache, positions):
        with jax.named_scope(self._scope):
            u = self.input_norm(x)
            if self.is_attention:
                a, cache = self.mixer.forward_decode(u, rope, cache, positions)
            else:
                a, cache = self.mixer.forward_decode(u, cache)
            x = x + a
        return self._mlp_half(x), cache


class Qwen3Next(nn.Module):
    #: the serve engine reads these: ``forward_cached`` can apply the
    #: head to one position only, and that position says how many rows
    #: are real; the expert layers record under ``nn.moe.moe_count_tape``
    prefill_logits_at = True
    moe_counters = True

    def __init__(self, cfg: Qwen3NextConfig):
        super().__init__()
        self.cfg = cfg
        self.tok_emb = nn.Embedding(
            cfg.vocab_size, cfg.dim, dtype=cfg.dtype, weight_init=_hf_normal
        )
        self.blocks = nn.ModuleList(
            [Qwen3NextBlock(cfg, i) for i in range(cfg.n_layers)]
        )
        self.norm = nn.RMSNorm(cfg.dim, eps=cfg.norm_eps, dtype=cfg.dtype)
        self.lm_head = nn.Linear(
            cfg.dim, cfg.vocab_size, bias=False, dtype=cfg.dtype,
            weight_init=_hf_normal,
        )

    @classmethod
    def from_name(cls, name: str, **overrides) -> "Qwen3Next":
        kw = dict(qwen3_next_configs[name])
        kw.update(overrides)
        return cls(Qwen3NextConfig(**kw))

    def _rope(self):
        cfg = self.cfg
        return _rope_freqs(cfg.rotary_dim, cfg.max_seq_len, cfg.rope_theta)

    def _head(self, x):
        with jax.named_scope("vocab_projection"):
            return self.lm_head(self.norm(x))

    def forward(self, tokens, return_hidden: bool = False):
        """The whole sequence from empty state: ``forward_cached`` over
        a cache of its own length, which is dropped."""
        b, s = tokens.shape
        rope = self._rope()
        x = self.tok_emb(tokens)
        for blk, c in zip(self.blocks, self.init_cache(b, s)):
            x, _ = blk.forward_cached(x, rope, c, 0)
        if return_hidden:
            return self.norm(x)
        return self._head(x)

    # -- incremental decoding ---------------------------------------------

    def init_cache(self, batch_size: int, max_seq: Optional[int] = None):
        """Per layer what it keeps of the context: an attention layer
        the pair ``(k, v)`` of zeros (B, max_seq, Hkv, D), a
        Gated-DeltaNet layer a ``RecurrentState`` of zeros (module
        docstring), whatever ``max_seq``."""
        cfg = self.cfg
        rows = (batch_size, max_seq or cfg.max_seq_len, cfg.n_kv_heads,
                cfg.head_dim)
        return [
            (jnp.zeros(rows, cfg.dtype), jnp.zeros(rows, cfg.dtype))
            if cfg.is_attention(i)
            else RecurrentState(
                jnp.zeros(
                    (batch_size, (cfg.d_conv - 1) * cfg.conv_dim), cfg.dtype
                ),
                jnp.zeros(
                    (batch_size, cfg.gdn_value_heads, cfg.gdn_key_dim,
                     cfg.gdn_value_dim), jnp.float32,
                ),
            )
            for i in range(cfg.n_layers)
        ]

    def forward_cached(self, tokens, cache, cache_pos, logits_at=None):
        """``tokens`` (a prompt, a chunk of one, or one decode token)
        after what the cache holds: the attention layers write their rows
        at ``cache_pos``, the Gated-DeltaNet layers go on from their
        state.  Returns (logits, new_cache).  With ``logits_at`` (a
        traced position within ``tokens``) the head is applied to that
        one position, the logits are (B, 1, vocab), and the rows past it
        are padding: they leave the recurrent state untouched."""
        true_len = None if logits_at is None else logits_at + 1
        rope = self._rope()
        x = self.tok_emb(tokens)
        new_cache = []
        for blk, c in zip(self.blocks, cache):
            x, c = blk.forward_cached(x, rope, c, cache_pos, true_len)
            new_cache.append(c)
        if logits_at is not None:
            x = lax.dynamic_slice_in_dim(x, logits_at, 1, axis=1)
        return self._head(x), new_cache

    def forward_decode(self, tokens, cache, positions, page_tables=None):
        """One decode step for a batch of serving slots: ``tokens``
        (B, 1), ``positions`` (B,) int32; ``cache`` the engine's slab,
        per layer a stored pair (slots, max_len, Hkv * D) or a
        ``RecurrentState``.  Every slot's state is rewritten, an idle
        slot's too (``serve/kv_cache.py`` says why that is safe)."""
        if page_tables is not None:
            raise ValueError(
                "a paged cache is not supported over recurrent state"
            )
        rope = self._rope()
        x = self.tok_emb(tokens)
        new_cache = []
        for blk, c in zip(self.blocks, cache):
            x, c = blk.forward_decode(x, rope, c, positions)
            new_cache.append(c)
        return self._head(x), new_cache
