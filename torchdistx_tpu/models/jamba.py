"""Jamba-family hybrid decoder (``model_type: jamba``; AI21-Jamba2-3B):
Mamba-1 state-space layers with an attention layer every
``attn_layer_period`` layers, a dense SwiGLU after every mixer, no
positional encoding anywhere, tied embeddings.

The equations (sizes of Jamba2-3B: hidden ``H`` 2560, ``d_inner`` =
``mamba_expand`` x H = 5120, state ``N`` 16, ``dt_rank`` ``R`` 160, conv
width ``K`` 4, MLP 8192, 20 query heads on 1 KV head of 128)::

    x0 = E[tokens]                                         # no positional encoding
    layer l:  a = x + Mixer_l(RMSNorm(x; input_norm))
              x' = a + MLP(RMSNorm(a; pre_ff_norm)),  MLP(u) = W_down(silu(W_gate u) * W_up u)
    Mixer_l = Attention if l % attn_layer_period == attn_layer_offset else Mamba
    Attention(u): q = W_q u, k = W_k u, v = W_v u; causal softmax(q k^T / sqrt(head)) v; W_o.
                  No bias, no rope.
    Mamba(u):  [xs, z] = split(W_in u)                      # d_inner each, no bias
               xc_t = silu(b_conv + sum_{j<K} w_conv[:, j] * xs_{t-(K-1)+j})   # depthwise, causal, xs_{<0} = 0
               [dr, B, C] = split(W_x xc_t)                 # R, N, N, no bias
               dr = RMSNorm(dr; dt_norm); B = RMSNorm(B; b_norm); C = RMSNorm(C; c_norm)   # Jamba's addition
               D_t = softplus(W_dt dr + b_dt)               # (d_inner,)
               A = -exp(A_log)                              # (d_inner, N)
               h_t[c, n] = exp(D_t[c] * A[c, n]) * h_{t-1}[c, n] + D_t[c] * B_t[n] * xc_t[c],   h_{-1} = 0
               y_t[c] = sum_n C_t[n] * h_t[c, n] + Dskip[c] * xc_t[c]
               out = W_out(y_t * silu(z_t))                 # no bias
    logits = RMSNorm(x_L; norm) @ E^T                       # tied

**What a Mamba layer keeps of the context** is its recurrent state, a
``serve.kv_cache.RecurrentState``: ``conv``, the last ``K - 1`` rows of
``xs`` (stored flat, ``(B, (K - 1) * d_inner)``, oldest first: lane
slices of it are the convolution's taps), and ``ssm``, ``h`` as ``(B, N,
d_inner)`` **float32** -- channels on lanes, the layout of
``ops/selective_scan.py``'s kernels, and float32 because the recurrence
accumulates over thousands of steps.  It is constant in the context
length.  An attention layer keeps the ordinary ``(k, v)`` pair.
``init_cache`` returns the two kinds layer by layer, and the serve
engine's slab stores them side by side (``serve/kv_cache.py``).

**A prefill is told the true length.**  The serve engine right-pads a
prompt to a bucket; attention masks padding by position later, a
recurrence cannot.  ``forward_cached(..., logits_at=)`` therefore takes
``logits_at + 1`` as the number of REAL rows: rows at and past it leave
``h`` untouched (``D_t`` forced to 0 there: decay 1, update 0) and
``conv`` is taken at the true length, so the same prompt in two buckets
writes the same state.

Refused by name (``JambaConfig.__post_init__``): ``num_experts`` != 1
(the expert layers of larger Jambas), ``sliding_window``,
``mamba_proj_bias``, untied embeddings.  Training is not supported: the
selective scan has no backward here.

The model exposes what ``generation.generate`` and ``ServeEngine`` ask
of one (``init_cache``, ``forward_cached``, ``forward_decode``) and the
hint ``prefill_logits_at`` (``forward_cached``'s ``logits_at``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .. import nn
from ..nn import functional as F
from ..ops.attention import (
    cached_attention,
    multihead_attention,
    slot_cached_attention,
)
from ..ops.flash_attention import resolve_use_flash
from ..ops.selective_scan import selective_scan, selective_state_update
from ..serve.kv_cache import RecurrentState
from .llama import LlamaMLP, _hf_normal

__all__ = ["JambaConfig", "Jamba", "jamba_configs"]


@dataclasses.dataclass
class JambaConfig:
    vocab_size: int = 65536
    dim: int = 2560
    n_layers: int = 28
    n_heads: int = 20
    n_kv_heads: int = 1
    ffn_dim: int = 8192
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160
    conv_bias: bool = True
    proj_bias: bool = False
    num_experts: int = 1
    sliding_window: Optional[int] = None
    tie_word_embeddings: bool = True
    max_seq_len: int = 262144  # a limit only: there is no rope table
    norm_eps: float = 1e-6
    dtype: object = jnp.bfloat16
    use_flash: Optional[bool] = None  # None = auto: kernels on a TPU

    def __post_init__(self) -> None:
        if self.num_experts != 1:
            raise ValueError(
                f"num_experts={self.num_experts} is not supported: every "
                "layer's feed-forward is the dense MLP (num_experts: 1)"
            )
        if self.sliding_window is not None:
            raise ValueError(
                "sliding_window is not supported: the attention layers "
                "attend the whole context (sliding_window: null)"
            )
        if self.proj_bias:
            raise ValueError(
                "mamba_proj_bias is not supported: the mixer's in and out "
                "projections have no bias (mamba_proj_bias: false)"
            )
        if not self.conv_bias:
            raise ValueError(
                "mamba_conv_bias=false is not supported: the convolution "
                "has its bias (mamba_conv_bias: true)"
            )
        if not self.tie_word_embeddings:
            raise ValueError(
                "tie_word_embeddings=false is not supported: the head is "
                "the embedding (tie_word_embeddings: true)"
            )

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.dim

    def is_attention(self, layer: int) -> bool:
        return layer % self.attn_layer_period == self.attn_layer_offset


jamba_configs = {
    # two periods of three, one attention layer each
    "tiny": dict(
        vocab_size=256, dim=64, n_layers=6, n_heads=4, n_kv_heads=1,
        ffn_dim=128, attn_layer_period=3, attn_layer_offset=1, d_state=8,
        dt_rank=8, max_seq_len=128, dtype=jnp.float32,
    ),
    # ai21labs/AI21-Jamba2-3B (the defaults above)
    "jamba2_3b": dict(),
}


class JambaAttention(nn.Module):
    """Llama's attention without the rope: same cache calls."""

    def __init__(self, cfg: JambaConfig):
        super().__init__()
        self.cfg = cfg
        d, hd = cfg.dim, cfg.head_dim
        lin = lambda i, o: nn.Linear(  # noqa: E731
            i, o, bias=False, dtype=cfg.dtype, weight_init=_hf_normal
        )
        self.wq = lin(d, cfg.n_heads * hd)
        self.wk = lin(d, cfg.n_kv_heads * hd)
        self.wv = lin(d, cfg.n_kv_heads * hd)
        self.wo = lin(cfg.n_heads * hd, d)

    def _qkv(self, x):
        cfg = self.cfg
        b, s, _ = x.shape
        q = self.wq(x).reshape(b, s, cfg.n_heads, cfg.head_dim)
        k = self.wk(x).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        v = self.wv(x).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        return q, k, v

    def _out(self, o):
        b, s = o.shape[:2]
        return self.wo(o.reshape(b, s, -1))

    def forward(self, x):
        q, k, v = self._qkv(x)
        if resolve_use_flash(self.cfg.use_flash):
            from ..ops.flash_attention import flash_attention

            return self._out(flash_attention(q, k, v, causal=True))
        return self._out(multihead_attention(q, k, v, causal=True))

    def forward_cached(self, x, cache, cache_pos):
        q, k, v = self._qkv(x)
        out, cache = cached_attention(
            q, k, v, cache, cache_pos, use_flash=self.cfg.use_flash
        )
        return self._out(out), cache

    def forward_decode(self, x, cache, positions):
        q, k, v = self._qkv(x)
        out, cache = slot_cached_attention(
            q, k, v, cache, positions, use_flash=self.cfg.use_flash
        )
        return self._out(out), cache


class JambaMamba(nn.Module):
    """The Mamba-1 mixer with Jamba's norms on ``dr``, ``B`` and ``C``
    (module docstring)."""

    def __init__(self, cfg: JambaConfig):
        super().__init__()
        self.cfg = cfg
        c, n, r = cfg.d_inner, cfg.d_state, cfg.dt_rank
        dt = cfg.dtype
        lin = lambda i, o, bias=False: nn.Linear(  # noqa: E731
            i, o, bias=bias, dtype=dt, weight_init=_hf_normal,
            bias_init=_hf_normal,
        )
        norm = lambda f: nn.RMSNorm(f, eps=cfg.norm_eps, dtype=dt)  # noqa: E731
        self.in_proj = lin(cfg.dim, 2 * c)
        self.conv_weight = nn.Parameter(_hf_normal((c, cfg.d_conv), dt))
        self.conv_bias = nn.Parameter(_hf_normal((c,), dt))
        self.x_proj = lin(c, r + 2 * n)
        self.dt_norm, self.b_norm, self.c_norm = norm(r), norm(n), norm(n)
        self.dt_proj = lin(r, c, bias=True)
        self.A_log = nn.Parameter(_hf_normal((c, n), dt))
        self.D = nn.Parameter(nn.init.ones((c,), dtype=dt))
        self.out_proj = lin(c, cfg.dim)

    def _a(self):
        """``A`` as the kernels take it: (N, d_inner) float32."""
        return -jnp.exp(self.A_log.astype(jnp.float32)).T

    def _conv(self, taps):
        """``silu(b + sum_j w[:, j] * taps[j])``: ``taps`` the ``K``
        inputs of every output, oldest first, each (..., d_inner)."""
        w = self.conv_weight.astype(jnp.float32)
        acc = self.conv_bias.astype(jnp.float32)
        for j, tap in enumerate(taps):
            acc = acc + w[:, j] * tap.astype(jnp.float32)
        return jax.nn.silu(acc).astype(taps[-1].dtype)

    def _coefficients(self, xc):
        """``xc`` (..., d_inner) -> the token's step size (..., d_inner)
        and its ``B``, ``C`` (..., N), float32."""
        cfg = self.cfg
        dr, b, c = jnp.split(
            self.x_proj(xc), [cfg.dt_rank, cfg.dt_rank + cfg.d_state], axis=-1
        )
        dt = jax.nn.softplus(
            self.dt_proj(self.dt_norm(dr)).astype(jnp.float32)
        )
        f32 = lambda v: v.astype(jnp.float32)  # noqa: E731
        return dt, f32(self.b_norm(b)), f32(self.c_norm(c))

    def forward_cached(self, x, state, true_len=None):
        """``x`` (B, S, dim) after the state's tokens; of its rows the
        first ``true_len`` are real (None: all).  Returns the mixer's
        output and the state after the real rows."""
        if x.shape[1] == 1 and true_len is None:
            return self.forward_decode(x, state)
        cfg = self.cfg
        b, s, _ = x.shape
        k1, c = cfg.d_conv - 1, cfg.d_inner
        n_real = s if true_len is None else true_len
        xs, z = jnp.split(self.in_proj(x), 2, axis=-1)
        with jax.named_scope("mamba/conv"):
            ext = jnp.concatenate(
                [state.conv.reshape(b, k1, c).astype(xs.dtype), xs], axis=1
            )
            xc = self._conv([ext[:, j:j + s] for j in range(cfg.d_conv)])
            conv = lax.dynamic_slice_in_dim(ext, n_real, k1, axis=1)
        dt, bm, cm = self._coefficients(xc)
        y, h = selective_scan(
            xc, dt, self._a(), bm, cm, self.D, z, state.ssm, n_real,
            use_kernel=cfg.use_flash,
        )
        return self.out_proj(y), RecurrentState(
            conv.reshape(b, k1 * c).astype(state.conv.dtype), h
        )

    def forward_decode(self, x, state):
        """One token a row (a serving slot): ``x`` (B, 1, dim)."""
        cfg = self.cfg
        c = cfg.d_inner
        xs, z = jnp.split(self.in_proj(x[:, 0]), 2, axis=-1)
        with jax.named_scope("mamba/conv"):
            old = state.conv
            xc = self._conv(
                [old[:, j * c:(j + 1) * c] for j in range(cfg.d_conv - 1)]
                + [xs]
            )
            conv = jnp.concatenate([old[:, c:], xs.astype(old.dtype)], axis=-1)
        dt, bm, cm = self._coefficients(xc)
        y, h = selective_state_update(
            state.ssm, xc, dt, self._a(), bm, cm, self.D, z,
            use_kernel=cfg.use_flash,
        )
        return self.out_proj(y)[:, None], RecurrentState(conv, h)


class JambaBlock(nn.Module):
    def __init__(self, cfg: JambaConfig, layer: int):
        super().__init__()
        self.is_attention = cfg.is_attention(layer)
        self.input_norm = nn.RMSNorm(cfg.dim, eps=cfg.norm_eps, dtype=cfg.dtype)
        self.mixer = JambaAttention(cfg) if self.is_attention else JambaMamba(cfg)
        self.pre_ff_norm = nn.RMSNorm(cfg.dim, eps=cfg.norm_eps, dtype=cfg.dtype)
        self.mlp = LlamaMLP(cfg)

    # scopes are metadata only: the compiled operations carry
    # ``attention`` / ``mamba`` (with ``mamba/conv``, ``mamba/scan``,
    # ``mamba/update`` inside) / ``mlp`` in their op_name

    @property
    def _scope(self) -> str:
        return "attention" if self.is_attention else "mamba"

    def _mlp_half(self, x):
        with jax.named_scope("mlp"):
            return x + self.mlp(self.pre_ff_norm(x))

    def forward_cached(self, x, cache, cache_pos, true_len=None):
        with jax.named_scope(self._scope):
            u = self.input_norm(x)
            if self.is_attention:
                a, cache = self.mixer.forward_cached(u, cache, cache_pos)
            else:
                a, cache = self.mixer.forward_cached(u, cache, true_len)
            x = x + a
        return self._mlp_half(x), cache

    def forward_decode(self, x, cache, positions):
        with jax.named_scope(self._scope):
            u = self.input_norm(x)
            if self.is_attention:
                a, cache = self.mixer.forward_decode(u, cache, positions)
            else:
                a, cache = self.mixer.forward_decode(u, cache)
            x = x + a
        return self._mlp_half(x), cache


class Jamba(nn.Module):
    #: the serve engine reads this: ``forward_cached`` can apply the head
    #: to one position only, and that position says how many rows are real
    prefill_logits_at = True

    def __init__(self, cfg: JambaConfig):
        super().__init__()
        self.cfg = cfg
        self.tok_emb = nn.Embedding(
            cfg.vocab_size, cfg.dim, dtype=cfg.dtype, weight_init=_hf_normal
        )
        self.blocks = nn.ModuleList(
            [JambaBlock(cfg, i) for i in range(cfg.n_layers)]
        )
        self.norm = nn.RMSNorm(cfg.dim, eps=cfg.norm_eps, dtype=cfg.dtype)

    @classmethod
    def from_name(cls, name: str, **overrides) -> "Jamba":
        kw = dict(jamba_configs[name])
        kw.update(overrides)
        return cls(JambaConfig(**kw))

    def _head(self, x):
        with jax.named_scope("vocab_projection"):
            return F.linear(self.norm(x), self.tok_emb.weight)  # tied

    def forward(self, tokens, return_hidden: bool = False):
        """The whole sequence from empty state: ``forward_cached`` over
        a cache of its own length, which is dropped."""
        b, s = tokens.shape
        x = self.tok_emb(tokens)
        for blk, c in zip(self.blocks, self.init_cache(b, s)):
            x, _ = blk.forward_cached(x, c, 0)
        if return_hidden:
            return self.norm(x)
        return self._head(x)

    # -- incremental decoding ---------------------------------------------

    def init_cache(self, batch_size: int, max_seq: Optional[int] = None):
        """Per layer what it keeps of the context: an attention layer
        the pair ``(k, v)`` of zeros (B, max_seq, Hkv, D), a Mamba layer
        a ``RecurrentState`` of zeros (module docstring), whatever
        ``max_seq``."""
        cfg = self.cfg
        rows = (batch_size, max_seq or cfg.max_seq_len, cfg.n_kv_heads,
                cfg.head_dim)
        c = cfg.d_inner
        return [
            (jnp.zeros(rows, cfg.dtype), jnp.zeros(rows, cfg.dtype))
            if cfg.is_attention(i)
            else RecurrentState(
                jnp.zeros((batch_size, (cfg.d_conv - 1) * c), cfg.dtype),
                jnp.zeros((batch_size, cfg.d_state, c), jnp.float32),
            )
            for i in range(cfg.n_layers)
        ]

    def forward_cached(self, tokens, cache, cache_pos, logits_at=None):
        """``tokens`` (a prompt, a chunk of one, or one decode token)
        after what the cache holds: the attention layers write their rows
        at ``cache_pos``, the Mamba layers go on from their state.
        Returns (logits, new_cache).  With ``logits_at`` (a traced
        position within ``tokens``) the head is applied to that one
        position, the logits are (B, 1, vocab), and the rows past it are
        padding: they leave the recurrent state untouched."""
        true_len = None if logits_at is None else logits_at + 1
        x = self.tok_emb(tokens)
        new_cache = []
        for blk, c in zip(self.blocks, cache):
            x, c = blk.forward_cached(x, c, cache_pos, true_len)
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
        x = self.tok_emb(tokens)
        new_cache = []
        for blk, c in zip(self.blocks, cache):
            x, c = blk.forward_decode(x, c, positions)
            new_cache.append(c)
        return self._head(x), new_cache
