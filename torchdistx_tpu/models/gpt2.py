"""GPT-2 family (BASELINE.json config 3: deferred_init(GPT-2-large) →
materialize sharded across 8 chips).

Standard GPT-2: learned positional embeddings, pre-LayerNorm blocks, GELU
MLP, weight-tied LM head.
"""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..nn import init
from ..ops.attention import (
    cached_attention,
    multihead_attention,
    slot_cached_attention,
)
from ..obs.numerics import tap as _num_tap
from ..ops.flash_attention import resolve_use_flash

__all__ = ["GPT2Config", "GPT2", "gpt2_configs"]


@dataclasses.dataclass
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    dim: int = 768
    n_layers: int = 12
    n_heads: int = 12
    norm_eps: float = 1e-5
    dtype: object = jnp.float32
    # pallas flash-attention kernel.  None = auto: on for TPU (measured
    # 2-5x and the only path at 8k+, scripts/bench_flash_attention.py),
    # off elsewhere (interpret-mode pallas is exact but slow on CPU)
    use_flash: object = None
    # Sequence parallelism (mirrors Llama): shard the sequence over this
    # mesh axis and run the model inside shard_map (tokens P(None, sp));
    # learned positions offset by the shard index.  sp_mode: "ring"
    # (flash kernels when use_flash resolves on) or "ulysses".
    sp_axis: object = None
    sp_mode: str = "ring"

    def __post_init__(self) -> None:
        if self.sp_mode not in ("ring", "ulysses"):
            raise ValueError(
                f"sp_mode must be 'ring' or 'ulysses', got {self.sp_mode!r}"
            )


gpt2_configs = {
    "tiny": dict(vocab_size=256, n_positions=64, dim=64, n_layers=2, n_heads=4),
    "gpt2": dict(dim=768, n_layers=12, n_heads=12),
    "gpt2_medium": dict(dim=1024, n_layers=24, n_heads=16),
    "gpt2_large": dict(dim=1280, n_layers=36, n_heads=20),
    "gpt2_xl": dict(dim=1600, n_layers=48, n_heads=25),
}


def _normal_init(std):
    return lambda s, d: init.normal(s, std=std, dtype=d)


def _zeros_init(s, d):
    return init.zeros(s, d)


class GPT2Block(nn.Module):
    def __init__(self, cfg: GPT2Config):
        super().__init__()
        self.use_flash = cfg.use_flash
        self.sp_axis = cfg.sp_axis
        self.sp_mode = cfg.sp_mode
        d = cfg.dim
        # GPT-2 scheme: N(0, 0.02) weights, zero biases, residual output
        # projections scaled by 1/sqrt(2 * n_layers)
        w = _normal_init(0.02)
        w_res = _normal_init(0.02 / math.sqrt(2 * cfg.n_layers))
        self.ln1 = nn.LayerNorm(d, eps=cfg.norm_eps, dtype=cfg.dtype)
        self.attn_qkv = nn.Linear(d, 3 * d, dtype=cfg.dtype, weight_init=w, bias_init=_zeros_init)
        self.attn_out = nn.Linear(d, d, dtype=cfg.dtype, weight_init=w_res, bias_init=_zeros_init)
        self.ln2 = nn.LayerNorm(d, eps=cfg.norm_eps, dtype=cfg.dtype)
        self.mlp_up = nn.Linear(d, 4 * d, dtype=cfg.dtype, weight_init=w, bias_init=_zeros_init)
        self.mlp_down = nn.Linear(
            4 * d, d, dtype=cfg.dtype, weight_init=w_res, bias_init=_zeros_init
        )
        self.n_heads = cfg.n_heads

    def forward(self, x):
        b, s, d = x.shape
        h = self.ln1(x)
        qkv = self.attn_qkv(h).reshape(b, s, 3, self.n_heads, d // self.n_heads)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if self.sp_axis is not None:
            from ..ops.attention import sp_attention

            a = sp_attention(
                q, k, v, axis=self.sp_axis, mode=self.sp_mode,
                causal=True, use_flash=self.use_flash,
            ).reshape(b, s, d)
        elif resolve_use_flash(self.use_flash):
            from ..ops.flash_attention import flash_attention

            a = flash_attention(q, k, v, causal=True).reshape(b, s, d)
        else:
            a = multihead_attention(q, k, v, causal=True).reshape(b, s, d)
        x = x + self.attn_out(a)
        h = self.ln2(x)
        return x + self.mlp_down(F.gelu(self.mlp_up(h)))

    def forward_cached(self, x, cache, cache_pos):
        """Incremental attention against a static-shape KV cache — same
        contract as the Llama blocks (ops.attention.cached_attention)."""
        b, s, d = x.shape
        hd = d // self.n_heads
        h = self.ln1(x)
        qkv = self.attn_qkv(h).reshape(b, s, 3, self.n_heads, hd)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        a, cache = cached_attention(
            q, k, v, cache, cache_pos, use_flash=self.use_flash
        )
        x = x + self.attn_out(a.reshape(b, s, d))
        h = self.ln2(x)
        return x + self.mlp_down(F.gelu(self.mlp_up(h))), cache

    def forward_decode(self, x, cache, positions, page_tables=None):
        """One-token batched decode with PER-ROW cache positions (serving
        slots) — the ``slot_cached_attention`` sibling of
        ``forward_cached``.  ``page_tables`` selects the paged pool
        layout (``serve/kv_cache.py``)."""
        b, s, d = x.shape
        hd = d // self.n_heads
        h = self.ln1(x)
        qkv = self.attn_qkv(h).reshape(b, s, 3, self.n_heads, hd)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        a, cache = slot_cached_attention(
            q, k, v, cache, positions, use_flash=self.use_flash,
            page_tables=page_tables,
        )
        x = x + self.attn_out(a.reshape(b, s, d))
        h = self.ln2(x)
        return x + self.mlp_down(F.gelu(self.mlp_up(h))), cache


class GPT2(nn.Module):
    def __init__(self, cfg: GPT2Config):
        super().__init__()
        self.cfg = cfg
        emb = _normal_init(0.02)
        self.tok_emb = nn.Embedding(cfg.vocab_size, cfg.dim, dtype=cfg.dtype, weight_init=emb)
        self.pos_emb = nn.Embedding(cfg.n_positions, cfg.dim, dtype=cfg.dtype, weight_init=emb)
        self.blocks = nn.ModuleList([GPT2Block(cfg) for _ in range(cfg.n_layers)])
        self.ln_f = nn.LayerNorm(cfg.dim, eps=cfg.norm_eps, dtype=cfg.dtype)

    @classmethod
    def from_name(cls, name: str, **overrides) -> "GPT2":
        kw = dict(gpt2_configs[name])
        kw.update(overrides)
        return cls(GPT2Config(**kw))

    def forward(self, tokens, return_hidden: bool = False):
        """``return_hidden=True`` returns the post-ln_f hidden states for
        ``ops.fused_linear_cross_entropy`` (with the tied
        ``tok_emb.weight`` as the head) — no (B, S, vocab) logits in
        HBM."""
        s = tokens.shape[1]
        if self.cfg.sp_axis is not None:
            import jax

            # s is the LOCAL shard; positions are global (shard offset)
            n = jax.lax.axis_size(self.cfg.sp_axis)
            if s * n > self.cfg.n_positions:
                raise ValueError(
                    f"global sequence length {s * n} exceeds n_positions="
                    f"{self.cfg.n_positions}"
                )
            pos = jax.lax.axis_index(self.cfg.sp_axis) * s + jnp.arange(s)
        elif s > self.cfg.n_positions:
            # jnp.take clamps out-of-range indices silently; fail loudly
            raise ValueError(
                f"sequence length {s} exceeds n_positions="
                f"{self.cfg.n_positions}"
            )
        else:
            pos = jnp.arange(s)
        x = _num_tap("tok_emb", self.tok_emb(tokens) + self.pos_emb(pos)[None])
        for i, blk in enumerate(self.blocks):
            x = _num_tap(f"block{i}", blk(x))
        x = self.ln_f(x)
        if return_hidden:
            return x
        # weight-tied head (GPT-2 ties lm_head to tok_emb)
        return _num_tap("logits", x @ self.tok_emb.weight.T)

    # -- KV-cache decode (generation.generate contract, like Llama) -------

    def init_cache(self, batch_size: int, max_seq=None):
        """Per-layer (k, v) caches of static shape (B, max_seq, H, D)."""
        cfg = self.cfg
        max_seq = max_seq or cfg.n_positions
        shape = (
            batch_size, max_seq, cfg.n_heads, cfg.dim // cfg.n_heads,
        )
        return [
            (jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))
            for _ in range(cfg.n_layers)
        ]

    def forward_cached(self, tokens, cache, cache_pos):
        """Run ``tokens`` (prefill chunk or single decode token) against the
        cache starting at ``cache_pos``.  Returns (logits, new_cache)."""
        s = tokens.shape[1]
        pos = cache_pos + jnp.arange(s)
        x = self.tok_emb(tokens) + self.pos_emb(pos)[None]
        new_cache = []
        for blk, c in zip(self.blocks, cache):
            x, c = blk.forward_cached(x, c, cache_pos)
            new_cache.append(c)
        x = self.ln_f(x)
        return x @ self.tok_emb.weight.T, new_cache

    def forward_decode(self, tokens, cache, positions, page_tables=None):
        """One decode step for a batch of independent serving slots:
        ``tokens`` (B, S), ``positions`` (B,) int32 per-row cache depths
        (token ``(b, i)`` sits at depth ``positions[b] + i``; ``S > 1``
        is the speculative verify block).  ``cache`` is the serve
        engine's, in its stored layout (``serve/kv_cache.py``: head tail
        merged, (B, max_len, H * D)); with ``page_tables`` it is the
        per-layer page pools.  Returns (logits, new_cache); same cache
        pytree as it was given."""
        s = tokens.shape[1]
        if s == 1:
            x = self.tok_emb(tokens) + self.pos_emb(positions)[:, None]
        else:
            pos = jnp.clip(
                positions[:, None] + jnp.arange(s)[None, :],
                0,
                self.cfg.n_positions - 1,
            )
            x = self.tok_emb(tokens) + self.pos_emb(pos)
        new_cache = []
        for blk, c in zip(self.blocks, cache):
            x, c = blk.forward_decode(x, c, positions, page_tables)
            new_cache.append(c)
        x = self.ln_f(x)
        return x @ self.tok_emb.weight.T, new_cache
