"""T5 encoder-decoder family (BASELINE.json config 4: deferred_init(T5-3B) +
FSDP wrap → materialize → train step).

Standard T5 v1.0 architecture: RMS-style LayerNorm without bias or mean
subtraction, relative-position-bucket attention bias shared across layers
(per stack), ReLU MLP, tied embedding scaling.
"""

from __future__ import annotations

import dataclasses
import jax
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..ops.attention import cached_attention
from ..ops.flash_attention import rel_pos_bucket, resolve_use_flash

__all__ = ["T5Config", "T5", "t5_configs"]


@dataclasses.dataclass
class T5Config:
    vocab_size: int = 32128
    dim: int = 512
    d_ff: int = 2048
    d_kv: int = 64
    n_heads: int = 8
    n_layers: int = 6  # per stack
    rel_pos_buckets: int = 32
    rel_pos_max_dist: int = 128
    norm_eps: float = 1e-6
    dtype: object = jnp.float32
    # pallas flash attention for SELF-attention (bias streamed into the
    # kernel).  None = auto: on for TPU, off elsewhere (interpret-mode
    # pallas on CPU is exact but slow).  Cross-attention stays einsum.
    # NOTE: with flash_bucket_bias off, the (H, Sq, Skv) bias
    # materializes in HBM and caps single-chip context; turn it on (or
    # use sequence parallelism) for long contexts.
    use_flash: object = None
    # In-kernel bucket bias (single-chip long context): self-attention
    # passes the (H, buckets) table into the flash kernels, which compute
    # each tile's bias from bucket ids in VMEM — no (H, S, S) bias ever
    # materializes, restoring flash's O(S) memory for T5.  Requires
    # use_flash; off by default (compiled-kernel acceptance pending the
    # next on-chip run; CPU interpret-mode parity is pinned in tests).
    flash_bucket_bias: bool = False
    # Sequence parallelism: shard the sequence dim over this mesh axis
    # (run the model inside shard_map, tokens P(None, sp_axis)).  Self-
    # attention rides the RING (flash kernels when use_flash resolves on)
    # with the relative-position bias sliced per device (O(S) rows);
    # cross-attention rings over the encoder's key shards.  Training /
    # encoding only — cached generation runs unsharded.
    sp_axis: object = None

    def __post_init__(self) -> None:
        if self.flash_bucket_bias and self.sp_axis is not None:
            # the SP ring materializes each device's (H, sq_local,
            # S_global) bias slice; silently dropping to that path would
            # re-introduce the HBM footprint the flag exists to remove
            raise ValueError(
                "flash_bucket_bias is not supported together with "
                "sp_axis: the ring paths slice a materialized per-device "
                "bias (O(S) rows) — drop one of the two"
            )


t5_configs = {
    "tiny": dict(vocab_size=256, dim=64, d_ff=128, d_kv=16, n_heads=4, n_layers=2),
    "t5_small": dict(dim=512, d_ff=2048, d_kv=64, n_heads=8, n_layers=6),
    "t5_base": dict(dim=768, d_ff=3072, d_kv=64, n_heads=12, n_layers=12),
    "t5_large": dict(dim=1024, d_ff=4096, d_kv=64, n_heads=16, n_layers=24),
    "t5_3b": dict(dim=1024, d_ff=16384, d_kv=128, n_heads=32, n_layers=24),
    "t5_11b": dict(dim=1024, d_ff=65536, d_kv=128, n_heads=128, n_layers=24),
}


class T5Attention(nn.Module):
    def __init__(self, cfg: T5Config, *, has_rel_bias: bool, bidirectional: bool):
        super().__init__()
        inner = cfg.n_heads * cfg.d_kv
        self.cfg = cfg
        self.bidirectional = bidirectional
        self.q = nn.Linear(cfg.dim, inner, bias=False, dtype=cfg.dtype)
        self.k = nn.Linear(cfg.dim, inner, bias=False, dtype=cfg.dtype)
        self.v = nn.Linear(cfg.dim, inner, bias=False, dtype=cfg.dtype)
        self.o = nn.Linear(inner, cfg.dim, bias=False, dtype=cfg.dtype)
        if has_rel_bias:
            self.rel_bias = nn.Embedding(cfg.rel_pos_buckets, cfg.n_heads, dtype=cfg.dtype)
        else:
            self.rel_bias = None

    def _bias(self, sq: int, skv: int, q_offset=0):
        """(H, sq, skv) relative-position bias for query rows starting at
        global position ``q_offset`` (0 for the unsharded path)."""
        if self.rel_bias is None:
            return None
        cfg = self.cfg
        ctx = q_offset + jnp.arange(sq)[:, None]
        mem = jnp.arange(skv)[None, :]
        bucket = rel_pos_bucket(
            mem - ctx,
            bidirectional=self.bidirectional,
            buckets=cfg.rel_pos_buckets,
            max_dist=cfg.rel_pos_max_dist,
        )
        return jnp.transpose(self.rel_bias(bucket), (2, 0, 1))  # (H, Sq, Skv)

    def _bias_sp(self, sq: int):
        """Sequence-parallel bias slice: THIS device's global query rows
        (shard ``axis_index``) against ALL key positions — the ring
        paths' (H, sq_local, S_global) layout, O(S) per device."""
        if self.rel_bias is None:
            return None
        axis = self.cfg.sp_axis
        n = jax.lax.axis_size(axis)
        return self._bias(
            sq, n * sq, q_offset=jax.lax.axis_index(axis) * sq
        )

    def forward_cached_self(self, x, cache, cache_pos, bias):
        """Incremental causal self-attention against a (k, v) cache.

        ``bias`` is the (H, sq, max_seq) slice of the relative-position
        bias for the rows being decoded (computed once per step at the
        stack level and shared by every layer, like ``forward``).
        """
        cfg = self.cfg
        b, sq, _ = x.shape
        q = self.q(x).reshape(b, sq, cfg.n_heads, cfg.d_kv)
        k = self.k(x).reshape(b, sq, cfg.n_heads, cfg.d_kv)
        v = self.v(x).reshape(b, sq, cfg.n_heads, cfg.d_kv)
        # T5 uses unscaled dot products (scale folded into init)
        out, cache = cached_attention(
            q, k, v, cache, cache_pos, scale=1.0, bias=bias
        )
        return self.o(out.reshape(b, sq, cfg.n_heads * cfg.d_kv)), cache

    def forward_cross_cached(self, x, ke, ve):
        """Cross-attention with the encoder K/V projected once up front."""
        cfg = self.cfg
        b, sq, _ = x.shape
        q = self.q(x).reshape(b, sq, cfg.n_heads, cfg.d_kv)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, ke).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, ve)
        return self.o(out.reshape(b, sq, cfg.n_heads * cfg.d_kv))

    def forward(self, x, kv=None, causal=False, bias=None):
        cfg = self.cfg
        b, sq, _ = x.shape
        is_self = kv is None
        kv = x if kv is None else kv
        skv = kv.shape[1]
        q = self.q(x).reshape(b, sq, cfg.n_heads, cfg.d_kv)
        k = self.k(kv).reshape(b, skv, cfg.n_heads, cfg.d_kv)
        v = self.v(kv).reshape(b, skv, cfg.n_heads, cfg.d_kv)
        if cfg.sp_axis is not None:
            # sequence-parallel ring (config docstring): the shared-bias
            # plumbing carries each device's (H, sq_local, S_global)
            # slice; cross-attention rings over encoder key shards
            from ..ops.attention import sp_attention

            if is_self and bias is None and self.rel_bias is not None:
                bias = self._bias_sp(sq)
            out = sp_attention(
                q, k, v, axis=cfg.sp_axis, causal=causal,
                scale=1.0, bias=bias if is_self else None,
                use_flash=cfg.use_flash,
            )
            return (
                self.o(out.reshape(b, sq, cfg.n_heads * cfg.d_kv)),
                bias,
            )
        use_bucket = (
            is_self
            and cfg.flash_bucket_bias
            and resolve_use_flash(cfg.use_flash)
        )
        if use_bucket:
            # the shared "bias" object is the (H, buckets) TABLE in this
            # mode — layer 0 extracts it, later layers reuse it
            from ..ops.flash_attention import flash_attention

            table = bias
            if table is None and self.rel_bias is not None:
                table = jnp.transpose(self.rel_bias.weight)
            out = flash_attention(
                q, k, v, causal=causal, scale=1.0,
                rel_bias_table=table,
                rel_bias_buckets=cfg.rel_pos_buckets,
                rel_bias_max_dist=cfg.rel_pos_max_dist,
                rel_bias_bidirectional=self.bidirectional,
            )
            return (
                self.o(out.reshape(b, sq, cfg.n_heads * cfg.d_kv)),
                table,
            )
        if bias is None and self.rel_bias is not None:
            bias = self._bias(sq, skv)
        # T5 uses unscaled dot products (scale folded into init)
        if is_self and resolve_use_flash(cfg.use_flash):
            from ..ops.flash_attention import flash_attention

            out = flash_attention(
                q, k, v, bias=bias, causal=causal, scale=1.0
            )
        else:
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
            if bias is not None:
                logits = logits + bias[None].astype(jnp.float32)
            if causal:
                mask = jnp.tril(jnp.ones((sq, skv), bool), k=skv - sq)
                logits = jnp.where(mask, logits, -jnp.inf)
            probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        return self.o(out.reshape(b, sq, cfg.n_heads * cfg.d_kv)), bias


class T5Block(nn.Module):
    def __init__(self, cfg: T5Config, *, is_decoder: bool, has_rel_bias: bool):
        super().__init__()
        self.is_decoder = is_decoder
        self.ln1 = nn.RMSNorm(cfg.dim, eps=cfg.norm_eps, dtype=cfg.dtype)
        self.self_attn = T5Attention(
            cfg, has_rel_bias=has_rel_bias, bidirectional=not is_decoder
        )
        if is_decoder:
            self.ln_cross = nn.RMSNorm(cfg.dim, eps=cfg.norm_eps, dtype=cfg.dtype)
            self.cross_attn = T5Attention(cfg, has_rel_bias=False, bidirectional=True)
        self.ln2 = nn.RMSNorm(cfg.dim, eps=cfg.norm_eps, dtype=cfg.dtype)
        self.wi = nn.Linear(cfg.dim, cfg.d_ff, bias=False, dtype=cfg.dtype)
        self.wo = nn.Linear(cfg.d_ff, cfg.dim, bias=False, dtype=cfg.dtype)

    def forward(self, x, enc=None, bias=None):
        a, bias = self.self_attn(self.ln1(x), causal=self.is_decoder, bias=bias)
        x = x + a
        if self.is_decoder and enc is not None:
            c, _ = self.cross_attn(self.ln_cross(x), kv=enc)
            x = x + c
        return x + self.wo(F.relu(self.wi(self.ln2(x)))), bias

    def decode_step(self, x, cache, cache_pos, bias):
        """Incremental decoder block: cached causal self-attention +
        cross-attention over pre-projected encoder K/V."""
        ck, cv, ke, ve = cache
        a, (ck, cv) = self.self_attn.forward_cached_self(
            self.ln1(x), (ck, cv), cache_pos, bias
        )
        x = x + a
        x = x + self.cross_attn.forward_cross_cached(self.ln_cross(x), ke, ve)
        return x + self.wo(F.relu(self.wi(self.ln2(x)))), (ck, cv, ke, ve)


class T5(nn.Module):
    def __init__(self, cfg: T5Config):
        super().__init__()
        self.cfg = cfg
        self.shared_emb = nn.Embedding(cfg.vocab_size, cfg.dim, dtype=cfg.dtype)
        self.enc_blocks = nn.ModuleList(
            [
                T5Block(cfg, is_decoder=False, has_rel_bias=(i == 0))
                for i in range(cfg.n_layers)
            ]
        )
        self.enc_norm = nn.RMSNorm(cfg.dim, eps=cfg.norm_eps, dtype=cfg.dtype)
        self.dec_blocks = nn.ModuleList(
            [
                T5Block(cfg, is_decoder=True, has_rel_bias=(i == 0))
                for i in range(cfg.n_layers)
            ]
        )
        self.dec_norm = nn.RMSNorm(cfg.dim, eps=cfg.norm_eps, dtype=cfg.dtype)

    @classmethod
    def from_name(cls, name: str, **overrides) -> "T5":
        kw = dict(t5_configs[name])
        kw.update(overrides)
        return cls(T5Config(**kw))

    def encode(self, tokens):
        x = self.shared_emb(tokens)
        bias = None
        for i, blk in enumerate(self.enc_blocks):
            x, b = blk(x, bias=bias)
            if i == 0:
                bias = b  # first layer's rel bias shared by the stack
        return self.enc_norm(x)

    def forward(self, enc_tokens, dec_tokens, return_hidden: bool = False):
        """``return_hidden=True`` returns the decoder hidden states with
        T5's 1/sqrt(dim) head scaling already applied, so
        ``ops.fused_linear_cross_entropy(h, shared_emb.weight, labels)``
        reproduces the tied-head logits without materializing them."""
        enc = self.encode(enc_tokens)
        x = self.shared_emb(dec_tokens)
        bias = None
        for i, blk in enumerate(self.dec_blocks):
            x, b = blk(x, enc=enc, bias=bias)
            if i == 0:
                bias = b
        x = self.dec_norm(x)
        # tied output head with T5's 1/sqrt(dim) scaling
        x = x * (self.cfg.dim**-0.5)
        if return_hidden:
            return x
        return x @ self.shared_emb.weight.T

    # -- incremental encoder-decoder decode (generation.generate_encdec) --

    def init_decoder_cache(self, enc, max_seq: int):
        """Per-decoder-layer cache: causal self-attn (k, v) of static shape
        (B, max_seq, H, d_kv) plus the encoder K/V projected ONCE per layer
        (cross-attention reuses them every step)."""
        cfg = self.cfg
        b, s_enc, _ = enc.shape
        shape = (b, max_seq, cfg.n_heads, cfg.d_kv)
        caches = []
        for blk in self.dec_blocks:
            ke = blk.cross_attn.k(enc).reshape(b, s_enc, cfg.n_heads, cfg.d_kv)
            ve = blk.cross_attn.v(enc).reshape(b, s_enc, cfg.n_heads, cfg.d_kv)
            caches.append(
                (
                    jnp.zeros(shape, cfg.dtype),
                    jnp.zeros(shape, cfg.dtype),
                    ke,
                    ve,
                )
            )
        return caches

    def _decoder_bias_slice(self, sq: int, max_seq: int, cache_pos):
        """Relative-position bias rows for decode positions
        ``cache_pos + [0, sq)`` against all ``max_seq`` cache slots —
        the incremental slice of the first decoder layer's shared bias."""
        layer0 = self.dec_blocks[0].self_attn
        ctx = (cache_pos + jnp.arange(sq))[:, None]
        mem = jnp.arange(max_seq)[None, :]
        bucket = rel_pos_bucket(
            mem - ctx,
            bidirectional=False,
            buckets=self.cfg.rel_pos_buckets,
            max_dist=self.cfg.rel_pos_max_dist,
        )
        return jnp.transpose(layer0.rel_bias(bucket), (2, 0, 1))

    def decode_step(self, dec_tokens, cache, cache_pos):
        """Run a prefill chunk or single decode token against the cache.
        Returns (logits, new_cache)."""
        sq = dec_tokens.shape[1]
        max_seq = cache[0][0].shape[1]
        x = self.shared_emb(dec_tokens)
        bias = self._decoder_bias_slice(sq, max_seq, cache_pos)
        new_cache = []
        for blk, c in zip(self.dec_blocks, cache):
            x, c = blk.decode_step(x, c, cache_pos, bias)
            new_cache.append(c)
        x = self.dec_norm(x)
        return (x * (self.cfg.dim**-0.5)) @ self.shared_emb.weight.T, new_cache
