"""Llama-family decoder (the framework's flagship model).

Standard Llama-2 architecture: RMSNorm pre-norm, rotary position embeddings,
grouped-query attention, SwiGLU MLP, tied-free LM head.  The north-star
config (``llama2_7b``) matches BASELINE.json config 5
(deferred_init(Llama-2-7B) → sharded materialize → train step).

TPU-first choices: bf16 parameters by default, f32 softmax/norm statistics,
optional ``jax.checkpoint`` over blocks (rematerialization trades FLOPs for
HBM), optional ring attention over an ``sp`` mesh axis for long context.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..nn import init as nn_init
from ..nn.remat import POLICIES as REMAT_POLICIES, checkpoint_policy
from ..ops.attention import (
    cached_attention,
    multihead_attention,
    slot_cached_attention,
    sp_attention,
)
from ..obs.numerics import tap as _num_tap
from ..ops.flash_attention import resolve_use_flash

__all__ = ["LlamaConfig", "Llama", "llama_configs", "pp_stage"]


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: Optional[int] = None
    ffn_dim: Optional[int] = None  # default: Llama SwiGLU sizing
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: object = jnp.bfloat16
    remat: bool = False  # jax.checkpoint each block
    # Rematerialization policy when remat=True (the memory/FLOPs dial,
    # nn/remat.py):
    #   "full"  — recompute everything (jax.checkpoint default); smallest
    #             footprint, costs ~23% of the bench step (BASELINE.md)
    #   "dots"  — jax.checkpoint_policies.dots_with_no_batch_dims_saveable:
    #             matmul outputs are SAVED, only elementwise/softmax work
    #             recomputes — recovers most of full-remat's overhead
    #             (recompute becomes VPU work overlapped with the MXU)
    #             while still dropping the attention-probs working set
    #   "auto"  — "dots" where the step that compiles the blocks finds
    #             the saving program fits the device (ShardedTrainStep),
    #             else "full"
    remat_policy: str = "auto"
    sp_axis: Optional[str] = None  # sequence parallelism over this mesh axis
    # "ring" (K/V rotate, works for any head count, O(S)-bias support) or
    # "ulysses" (two all-to-alls around local attention; needs head counts
    # divisible by the axis size)
    sp_mode: str = "ring"
    # pallas flash-attention kernel (single chip).  None = auto: on for TPU
    # (measured 2-5x over the jnp path at 2k-4k and the only path that runs
    # at 8k+, scripts/bench_flash_attention.py), off elsewhere (the CPU
    # fallback is interpret-mode pallas — exact but slow).
    use_flash: Optional[bool] = None
    # Sliding-window attention (Mistral/Mixtral scheme): query i attends
    # keys (i - window, i].  Applies to the single-device flash/jnp paths
    # and cached decode; not supported together with sp_axis (the ring
    # would need band-aware hop pruning).
    sliding_window: Optional[int] = None

    def __post_init__(self) -> None:
        if self.sp_mode not in ("ring", "ulysses"):
            raise ValueError(
                f"sp_mode must be 'ring' or 'ulysses', got {self.sp_mode!r}"
            )
        if self.remat_policy not in REMAT_POLICIES:
            # validated at construction like sp_mode (not lazily at the
            # first rematted forward, far from the typo)
            raise ValueError(
                f"remat_policy must be one of {REMAT_POLICIES}, "
                f"got {self.remat_policy!r}"
            )
        if self.sliding_window is not None and self.sliding_window < 1:
            raise ValueError(
                f"sliding_window must be >= 1, got {self.sliding_window}"
            )
        if self.sliding_window is not None and self.sp_axis is not None:
            raise ValueError(
                "sliding_window is not supported together with sp_axis"
            )
        if self.n_kv_heads is None:
            self.n_kv_heads = self.n_heads
        if self.ffn_dim is None:
            hidden = int(2 * (4 * self.dim) / 3)
            multiple = 256
            self.ffn_dim = multiple * ((hidden + multiple - 1) // multiple)

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads


def _remat_policy(name: str):
    """Resolve ``LlamaConfig.remat_policy`` to a jax.checkpoint policy
    (None = recompute everything, the jax.checkpoint default); ``"auto"``
    reads the enclosing ``nn.remat.remat_scope``."""
    return checkpoint_policy(name)


def _hf_normal(shape, dtype):
    """HF Llama init: N(0, initializer_range=0.02) for matmuls/embeddings."""
    return nn_init.normal(shape, std=0.02, dtype=dtype)


llama_configs = {
    "tiny": dict(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, max_seq_len=128,
        dtype=jnp.float32,
    ),
    # 1B-class config sized to train on ONE v5e chip (16 GB HBM) with
    # AnyPrecisionAdamW state + remat — the single-chip throughput bench
    "llama_1b": dict(
        vocab_size=32000, dim=2048, n_layers=16, n_heads=16,
        max_seq_len=2048, remat=True,
    ),
    "llama2_7b": dict(
        vocab_size=32000, dim=4096, n_layers=32, n_heads=32,
        max_seq_len=4096,
    ),
    "llama2_13b": dict(
        vocab_size=32000, dim=5120, n_layers=40, n_heads=40,
        max_seq_len=4096,
    ),
    # Mistral-7B: Llama architecture + GQA (8 KV heads) + 4096-token
    # sliding-window attention (the band the flash kernel block-prunes)
    "mistral_7b": dict(
        vocab_size=32000, dim=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, ffn_dim=14336, max_seq_len=8192,
        rope_theta=10000.0, sliding_window=4096,
    ),
    # Llama-3-8B: GQA (8 KV heads), 128k vocab, rope theta 5e5
    "llama3_8b": dict(
        vocab_size=128256, dim=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, ffn_dim=14336, max_seq_len=8192,
        rope_theta=500000.0,
    ),
}


def _rope_freqs(head_dim: int, max_seq: int, theta: float) -> jax.Array:
    inv = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)  # (seq, head_dim/2)
    return jnp.stack([jnp.cos(freqs), jnp.sin(freqs)], axis=-1)


def apply_rope(x: jax.Array, rope: jax.Array, offset=0) -> jax.Array:
    """x: (B, S, H, D); rope: (max_seq, D/2, 2).  ``offset`` may be traced
    (sequence-parallel shards pass ``axis_index * local_seq``)."""
    s = x.shape[1]
    window = jax.lax.dynamic_slice_in_dim(rope, offset, s, axis=0)
    cos = window[:, :, 0][None, :, None, :]
    sin = window[:, :, 1][None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope_at(x: jax.Array, rope: jax.Array, positions: jax.Array) -> jax.Array:
    """x: (B, S, H, D); ``positions``: (B,) int32 — PER-ROW rotary offsets
    (continuous-batching decode: each batch row is a serving slot at its
    own depth).  Token ``(b, i)`` gets the same rotation ``apply_rope``
    would apply at scalar offset ``positions[b] + i`` (``S == 1`` is the
    plain decode step; ``S > 1`` is the speculative verify block, whose
    per-row offsets clamp at the table end exactly like ``jnp.take``'s
    default clip mode on the single-token path — those rows are
    rejected-lane only)."""
    s = x.shape[1]
    if s == 1:
        window = jnp.take(rope, positions, axis=0)  # (B, D/2, 2)
        cos = window[:, None, None, :, 0]
        sin = window[:, None, None, :, 1]
    else:
        pos_grid = jnp.clip(
            positions[:, None] + jnp.arange(s)[None, :], 0, rope.shape[0] - 1
        )
        window = rope[pos_grid]  # (B, S, D/2, 2)
        cos = window[:, :, None, :, 0]
        sin = window[:, :, None, :, 1]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


class LlamaAttention(nn.Module):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        d, hd = cfg.dim, cfg.head_dim
        self.cfg = cfg
        lin = lambda i, o: nn.Linear(  # noqa: E731
            i, o, bias=False, dtype=cfg.dtype, weight_init=_hf_normal
        )
        self.wq = lin(d, cfg.n_heads * hd)
        self.wk = lin(d, cfg.n_kv_heads * hd)
        self.wv = lin(d, cfg.n_kv_heads * hd)
        self.wo = lin(cfg.n_heads * hd, d)

    def forward(self, x, rope, pos_offset=0):
        b, s, _ = x.shape
        cfg = self.cfg
        q = self.wq(x).reshape(b, s, cfg.n_heads, cfg.head_dim)
        k = self.wk(x).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        v = self.wv(x).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        if cfg.sp_axis is not None:
            # sequence-parallel: this shard holds positions
            # [axis_index * s, axis_index * s + s)
            pos_offset = jax.lax.axis_index(cfg.sp_axis) * s
        q = apply_rope(q, rope, pos_offset)
        k = apply_rope(k, rope, pos_offset)
        if cfg.sp_axis is not None:
            # ring: flash kernel per block (per-device memory flat as
            # shards grow, K/V travel at hkv heads) or the jnp ring;
            # ulysses: all-to-all — one shared dispatcher for all models
            out = sp_attention(
                q, k, v, axis=cfg.sp_axis, mode=cfg.sp_mode,
                causal=True, use_flash=cfg.use_flash,
            )
        elif resolve_use_flash(cfg.use_flash):
            from ..ops.flash_attention import flash_attention

            # flash_attention reduces block sizes to dividing values itself
            out = flash_attention(
                q, k, v, causal=True, window=cfg.sliding_window
            )
        else:
            out = multihead_attention(
                q, k, v, causal=True, window=cfg.sliding_window
            )
        return self.wo(out.reshape(b, s, cfg.n_heads * cfg.head_dim))

    def forward_cached(self, x, rope, cache, cache_pos):
        """Incremental attention against a static-shape KV cache.

        ``cache`` is ``(k, v)`` of shape (B, max_seq, Hkv, D); the new keys/
        values are written at ``cache_pos`` (traced) and attention masks out
        slots beyond ``cache_pos + s``.  Returns (out, new_cache).
        """
        b, s, _ = x.shape
        cfg = self.cfg
        q = self.wq(x).reshape(b, s, cfg.n_heads, cfg.head_dim)
        k = self.wk(x).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        v = self.wv(x).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        q = apply_rope(q, rope, cache_pos)
        k = apply_rope(k, rope, cache_pos)
        out, cache = cached_attention(
            q, k, v, cache, cache_pos, use_flash=cfg.use_flash,
            window=cfg.sliding_window,
        )
        return self.wo(out.reshape(b, s, cfg.n_heads * cfg.head_dim)), cache

    def forward_decode(self, x, rope, cache, positions, page_tables=None):
        """One-token batched decode with PER-ROW cache positions (serving
        slots): ``x`` is (B, 1, dim), ``positions`` (B,) int32.  Same math
        as ``forward_cached`` at ``s == 1``, row for row.  With
        ``page_tables`` (B, pages_per_slot) int32 the cache is the paged
        pool layout (``serve/kv_cache.py``) instead of a contiguous
        slab — same attention contract either way."""
        b, s, _ = x.shape
        cfg = self.cfg
        q = self.wq(x).reshape(b, s, cfg.n_heads, cfg.head_dim)
        k = self.wk(x).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        v = self.wv(x).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        q = apply_rope_at(q, rope, positions)
        k = apply_rope_at(k, rope, positions)
        out, cache = slot_cached_attention(
            q, k, v, cache, positions, window=cfg.sliding_window,
            use_flash=cfg.use_flash, page_tables=page_tables,
        )
        return self.wo(out.reshape(b, s, cfg.n_heads * cfg.head_dim)), cache


class LlamaMLP(nn.Module):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        lin = lambda i, o: nn.Linear(  # noqa: E731
            i, o, bias=False, dtype=cfg.dtype, weight_init=_hf_normal
        )
        self.w_gate = lin(cfg.dim, cfg.ffn_dim)
        self.w_up = lin(cfg.dim, cfg.ffn_dim)
        self.w_down = lin(cfg.ffn_dim, cfg.dim)

    def forward(self, x):
        return self.w_down(F.silu(self.w_gate(x)) * self.w_up(x))


class LlamaBlock(nn.Module):
    def __init__(self, cfg: LlamaConfig, mlp: Optional[nn.Module] = None):
        super().__init__()
        self.attn_norm = nn.RMSNorm(cfg.dim, eps=cfg.norm_eps, dtype=cfg.dtype)
        self.attn = LlamaAttention(cfg)
        self.mlp_norm = nn.RMSNorm(cfg.dim, eps=cfg.norm_eps, dtype=cfg.dtype)
        # the FFN half is pluggable: Mixtral's block passes an MoE here and
        # inherits the whole attention/cache scaffolding
        self.mlp = mlp if mlp is not None else LlamaMLP(cfg)

    # each half runs under a ``jax.named_scope`` (metadata only): the
    # compiled operations carry ``attention`` / ``mlp`` in their op_name,
    # so a profile's fusions can be put down to a half of the block

    def _mlp_half(self, x):
        with jax.named_scope("mlp"):
            return x + self.mlp(self.mlp_norm(x))

    def forward(self, x, rope):
        with jax.named_scope("attention"):
            x = x + self.attn(self.attn_norm(x), rope)
        return self._mlp_half(x)

    def forward_cached(self, x, rope, cache, cache_pos):
        with jax.named_scope("attention"):
            a, cache = self.attn.forward_cached(
                self.attn_norm(x), rope, cache, cache_pos
            )
            x = x + a
        return self._mlp_half(x), cache

    def forward_decode(self, x, rope, cache, positions, page_tables=None):
        with jax.named_scope("attention"):
            a, cache = self.attn.forward_decode(
                self.attn_norm(x), rope, cache, positions, page_tables
            )
            x = x + a
        return self._mlp_half(x), cache


class Llama(nn.Module):
    block_cls = LlamaBlock  # subclasses (Mixtral) swap the block type

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.tok_emb = nn.Embedding(
            cfg.vocab_size, cfg.dim, dtype=cfg.dtype, weight_init=_hf_normal
        )
        self.blocks = nn.ModuleList(
            [self.block_cls(cfg) for _ in range(cfg.n_layers)]
        )
        self.norm = nn.RMSNorm(cfg.dim, eps=cfg.norm_eps, dtype=cfg.dtype)
        self.lm_head = nn.Linear(
            cfg.dim, cfg.vocab_size, bias=False, dtype=cfg.dtype,
            weight_init=_hf_normal,
        )

    @classmethod
    def from_name(cls, name: str, **overrides) -> "Llama":
        kw = dict(llama_configs[name])
        kw.update(overrides)
        return cls(LlamaConfig(**kw))

    def forward(self, tokens, return_hidden: bool = False):
        """``return_hidden=True`` returns the pre-LM-head hidden states —
        the input losses like ``ops.fused_linear_cross_entropy`` consume
        together with ``lm_head.weight`` so the (B, S, vocab) logits never
        materialize."""
        cfg = self.cfg
        x = self.tok_emb(tokens)
        rope = _rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
        block_fn = (
            jax.checkpoint(
                lambda blk, h: blk(h, rope),
                static_argnums=(0,),
                policy=_remat_policy(cfg.remat_policy),
            )
            if cfg.remat
            else (lambda blk, h: blk(h, rope))
        )
        x = _num_tap("tok_emb", x)
        for i, blk in enumerate(self.blocks):
            # tapped on the block RESULT, outside the remat wrapper —
            # digests must not be recomputed (or dropped) by checkpoint
            x = _num_tap(f"block{i}", block_fn(blk, x))
        x = self.norm(x)
        if return_hidden:
            return x
        with jax.named_scope("vocab_projection"):
            return _num_tap("logits", self.lm_head(x))

    # -- incremental decoding (KV cache) ----------------------------------

    def init_cache(self, batch_size: int, max_seq: Optional[int] = None):
        """Per-layer (k, v) caches of static shape (B, max_seq, Hkv, D)."""
        cfg = self.cfg
        max_seq = max_seq or cfg.max_seq_len
        shape = (batch_size, max_seq, cfg.n_kv_heads, cfg.head_dim)
        return [
            (
                jnp.zeros(shape, cfg.dtype),
                jnp.zeros(shape, cfg.dtype),
            )
            for _ in range(cfg.n_layers)
        ]

    def forward_cached(self, tokens, cache, cache_pos):
        """Run ``tokens`` (prefill chunk or single decode token) against the
        cache starting at position ``cache_pos``.  Returns (logits,
        new_cache)."""
        cfg = self.cfg
        x = self.tok_emb(tokens)
        rope = _rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
        new_cache = []
        for blk, c in zip(self.blocks, cache):
            x, c = blk.forward_cached(x, rope, c, cache_pos)
            new_cache.append(c)
        x = self.norm(x)
        with jax.named_scope("vocab_projection"):
            return self.lm_head(x), new_cache

    def forward_decode(self, tokens, cache, positions, page_tables=None):
        """One decode step for a batch of independent serving slots:
        ``tokens`` (B, 1), ``positions`` (B,) int32 — row ``b``'s token
        is written at its own cache depth ``positions[b]``
        (``ops.attention.slot_cached_attention``).  ``cache`` is the
        serve engine's, in its STORED layout (``serve/kv_cache.py``: per
        layer ``(k, v)`` of shape (B, max_len, Hkv * D), not
        ``init_cache``'s (B, S, Hkv, D)).  With ``page_tables`` the
        cache pytree is the per-layer page pools and row ``b``'s depth
        indexes its page chain.  Returns (logits, new_cache); same
        cache-ins/cache-outs pytree as it was given."""
        cfg = self.cfg
        x = self.tok_emb(tokens)
        rope = _rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
        new_cache = []
        for blk, c in zip(self.blocks, cache):
            x, c = blk.forward_decode(x, rope, c, positions, page_tables)
            new_cache.append(c)
        x = self.norm(x)
        with jax.named_scope("vocab_projection"):
            return self.lm_head(x), new_cache


def pp_stage(cfg: LlamaConfig, n_blocks: int = 1):
    """Module class for one pipeline stage: ``n_blocks`` LlamaBlocks with a
    uniform ``forward(x) -> x`` signature (rope recomputed per call from the
    config — parameter-free), as ``parallel.pp`` stage functions require.
    Instantiate under ``deferred_init`` per stage, materialize, then
    ``stack_pipeline_stages``; bind params per call with ``functional_call``
    on one template instance.
    """

    class LlamaStage(nn.Module):
        def __init__(self):
            super().__init__()
            self.blocks = nn.ModuleList(
                [LlamaBlock(cfg) for _ in range(n_blocks)]
            )

        def forward(self, x):
            rope = _rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
            for blk in self.blocks:
                x = blk(x, rope)
            return x

    return LlamaStage
