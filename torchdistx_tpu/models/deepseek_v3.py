"""DeepSeek-V3-family decoder: multi-head latent attention (MLA) over a
latent cache, and a sigmoid-routed mixture of experts with shared
experts that drops no token.

The architecture of ``model_type: deepseek_v3`` checkpoints (DeepSeek-V3,
Kanana-2-30B-A3B, ...), per layer, ``x`` the RMS-normed input:

- ``q = W_q x`` -> per head ``[q_nope ; q_rope]`` (no query low-rank:
  ``q_lora_rank`` is refused);
- ``[c ; k_r] = W_kv_a x``; ``c <- RMSNorm(c)`` with its own scale;
  ``k_r`` is ONE rope key shared by all heads;
- rope on ``q_rope`` (per head) and ``k_r``, on **interleaved pairs**
  ``(2i, 2i+1)`` (``rope_interleave: true``).  The published code first
  permutes the pairs into the half-split layout and rotates there; the
  permutation is the same for ``q_rope`` and ``k_r`` and cancels in every
  dot product, so the rotation is applied in place here (program and
  reference alike);
- **expanded form** (``forward``, ``forward_cached``): ``[k_nope ; v] =
  W_kv_b c`` per head, ``k = [k_nope ; k_r]``, causal softmax of ``q.k /
  sqrt(qk width)``, ``o = P v``, ``y = W_o o``;
- **absorbed form** (``forward_decode``, the serve engine's step): with
  ``W_kv_b`` split per head into ``W_uk`` and ``W_uv``, ``q~ = q_nope
  W_uk``, scores ``(q~ . c_j + q_rope . k_r,j) / sqrt(qk width)`` over
  the cache rows, ``o~ = sum_j P_j c_j``, ``o = o~ W_uv`` — the same
  numbers as the expanded form up to rounding (tests pin it), with each
  cache row read once (``ops/latent_decode_attention.py``);
- the cache row is ``[c (after its norm) ; k_r (after rope)]``, ``kv_lora_rank
  + qk_rope_head_dim`` wide (576), ONE per token and layer, **stored
  zero-padded to a whole number of 128-lane tiles** (640): ``init_cache``
  returns per layer the typed entry ``LatentEntry(latent (B, S,
  cache_width))`` (``serve/kv_cache.py``), which
  is also how ``serve/kv_cache.py`` stores it.  The padding costs no
  memory the chip would not spend anyway (a bf16 array tiled (8, 128)
  with 576 lanes minor is laid out on 640) and it is what keeps the
  compiler from storing the array rows-minor, 576 being no multiple of
  128: the decode kernel would then be handed a relayouted COPY of the
  whole slab in every layer of every step (PR 28's fault, found again
  in this PR's first described compile);
- FFN: the first ``first_k_dense`` layers a dense SwiGLU; the others
  ``nn.MoE`` with float32 sigmoid scores, the selection-only bias
  ``e_score_correction_bias``, renormalised top-k weights times
  ``routed_scale``, and a shared SwiGLU expert (``n_shared_experts x
  moe_ffn_dim`` wide) every token takes.  ``n_group = topk_group = 1``
  only: group-limited choice is then the identity, and anything else is
  refused.

Departures, written down: ``e_score_correction_bias`` is a parameter
drawn by the same rule as every other leaf (published checkpoints start
it at zero and carry trained values; zero would leave the
selection-only path unexercised).  Training is not supported: the MLA
flash forward has no backward at qk width != v width and refuses by
name.

The model exposes what ``generation.generate`` and ``ServeEngine`` ask
of one (``init_cache``, ``forward_cached``, ``forward_decode``), with
a typed cache entry (``LatentEntry``: the engine refuses paging, int8,
speculation, persistent decode, chunked prefill and a TP mesh over it)
and one hint the engine reads, ``prefill_logits_at``
(``forward_cached``'s ``logits_at``: the head applied to the one
position that is sampled).
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
from ..ops.attention import latent_slot_cached_attention, multihead_attention
from ..ops.flash_attention import resolve_use_flash
from ..serve.kv_cache import LatentEntry
from .llama import LlamaMLP, _hf_normal, _rope_freqs

__all__ = ["DeepseekV3Config", "DeepseekV3", "deepseek_v3_configs"]


@dataclasses.dataclass
class DeepseekV3Config:
    vocab_size: int = 128256
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    ffn_dim: int = 6144  # the leading dense layers' SwiGLU
    moe_ffn_dim: int = 768  # one routed expert's
    n_routed_experts: int = 128
    n_shared_experts: int = 2
    top_k: int = 6
    first_k_dense: int = 1
    routed_scale: float = 2.448
    n_group: int = 1
    topk_group: int = 1
    q_lora_rank: Optional[int] = None
    max_seq_len: int = 32768
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    dtype: object = jnp.bfloat16
    use_flash: Optional[bool] = None  # None = auto: kernels on a TPU

    def __post_init__(self) -> None:
        if self.q_lora_rank is not None:
            raise ValueError(
                "q_lora_rank is not supported: this model projects the "
                "query in one matrix (q_lora_rank: null)"
            )
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError(
                f"n_group={self.n_group} / topk_group={self.topk_group} are "
                "not supported: group-limited expert choice is implemented "
                "only as the identity (n_group = topk_group = 1)"
            )
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (rope pairs)")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """A cache row: the compressed key/value and the shared rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """A cache row as stored: padded to whole 128-lane tiles."""
        return -(-self.latent_width // 128) * 128


deepseek_v3_configs = {
    "tiny": dict(
        vocab_size=256, dim=64, n_layers=3, n_heads=4, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, ffn_dim=128,
        moe_ffn_dim=32, n_routed_experts=8, n_shared_experts=2, top_k=3,
        max_seq_len=128, dtype=jnp.float32,
    ),
    # kakaocorp/kanana-2-30b-a3b-instruct-2601 (the defaults above)
    "kanana_2_30b_a3b": dict(),
}


def rope_interleaved(x: jax.Array, table: jax.Array) -> jax.Array:
    """Rotate the pairs ``(2i, 2i+1)`` of ``x`` (..., D); ``table``
    (..., D/2, 2) holds cos and sin, broadcast against ``x``'s leading
    axes."""
    cos, sin = table[..., 0], table[..., 1]
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    x1, x2 = xf[..., 0], xf[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class MLAttention(nn.Module):
    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        self.cfg = cfg
        h = cfg.n_heads
        lin = lambda i, o: nn.Linear(  # noqa: E731
            i, o, bias=False, dtype=cfg.dtype, weight_init=_hf_normal
        )
        self.wq = lin(cfg.dim, h * cfg.qk_head_dim)
        self.wkv_a = lin(cfg.dim, cfg.latent_width)
        self.kv_norm = nn.RMSNorm(
            cfg.kv_lora_rank, eps=cfg.norm_eps, dtype=cfg.dtype
        )
        self.wkv_b = lin(
            cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)
        )
        self.wo = lin(h * cfg.v_head_dim, cfg.dim)

    @property
    def scale(self) -> float:
        return 1.0 / math.sqrt(self.cfg.qk_head_dim)

    def _project(self, x, table):
        """``x`` (B, S, dim), ``table`` the rope rows of its positions,
        (S, r/2, 2) or per slot (B, S, r/2, 2).  Returns ``q_nope`` (B, S,
        H, nope), ``q_rope`` (B, S, H, r) and the cache rows ``[c ;
        k_r ; 0]`` (B, S, cache_width), norm and rope applied."""
        cfg = self.cfg
        b, s, _ = x.shape
        q = self.wq(x).reshape(b, s, cfg.n_heads, cfg.qk_head_dim)
        q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
        c, k_r = jnp.split(self.wkv_a(x), [cfg.kv_lora_rank], axis=-1)
        per_head = table[..., None, :, :]  # broadcast over the head axis
        q_rope = rope_interleaved(q_rope, per_head)
        pad = jnp.zeros((b, s, cfg.cache_width - cfg.latent_width), x.dtype)
        rows = jnp.concatenate(
            [self.kv_norm(c), rope_interleaved(k_r, table), pad], axis=-1
        )
        return q_nope, q_rope, rows

    def _expand(self, rows):
        """Cache rows (B, S, cache_width) -> per-head keys (B, S, H, qk)
        and values (B, S, H, v): the expanded form's ``W_kv_b c``, the
        shared rope key repeated for every head."""
        cfg = self.cfg
        b, s, _ = rows.shape
        c, k_r, _ = jnp.split(
            rows, [cfg.kv_lora_rank, cfg.latent_width], axis=-1
        )
        kv = self.wkv_b(c).reshape(
            b, s, cfg.n_heads, cfg.qk_nope_head_dim + cfg.v_head_dim
        )
        k_nope, v = jnp.split(kv, [cfg.qk_nope_head_dim], axis=-1)
        k_r = jnp.broadcast_to(
            k_r[:, :, None, :], (b, s, cfg.n_heads, cfg.qk_rope_head_dim)
        )
        return jnp.concatenate([k_nope, k_r], axis=-1), v

    def _causal(self, q, k, v):
        """Ordinary causal attention of new tokens over themselves, qk
        width != v width: the flash kernel on a TPU, else jnp."""
        if resolve_use_flash(self.cfg.use_flash):
            from ..ops.flash_attention import flash_attention

            s = q.shape[1]
            pad = (-s) % 128  # lane-multiple blocks for odd lengths
            if pad:
                widen = lambda a: jnp.pad(  # noqa: E731
                    a, ((0, 0), (0, pad), (0, 0), (0, 0))
                )
                q, k, v = widen(q), widen(k), widen(v)
            return flash_attention(
                q, k, v, causal=True, scale=self.scale
            )[:, :s]
        return multihead_attention(q, k, v, causal=True, scale=self.scale)

    def _out(self, o):
        b, s = o.shape[:2]
        return self.wo(o.reshape(b, s, -1))

    def forward(self, x, rope):
        s = x.shape[1]
        q_nope, q_rope, rows = self._project(x, rope[:s])
        k, v = self._expand(rows)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        return self._out(self._causal(q, k, v))

    def forward_cached(self, x, rope, cache, cache_pos):
        """Expanded-form attention against the latent cache ``(latent
        (B, max_seq, W),)``: the new rows are written at ``cache_pos``;
        a from-empty prefill (``cache_pos == 0`` static) attends the new
        rows alone, anything else expands the whole cache through
        ``W_kv_b`` (the plain path ``generate()`` decodes on; the serve
        engine decodes through ``forward_decode``)."""
        b, s, _ = x.shape
        (latent,) = cache
        table = lax.dynamic_slice_in_dim(rope, cache_pos, s, axis=0)
        q_nope, q_rope, rows = self._project(x, table)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        latent = lax.dynamic_update_slice(
            latent, rows.astype(latent.dtype), (0, cache_pos, 0)
        )
        if isinstance(cache_pos, int) and cache_pos == 0:
            k, v = self._expand(rows)
            return self._out(self._causal(q, k, v)), LatentEntry(latent)
        k, v = self._expand(latent.astype(x.dtype))
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        visible = (
            jnp.arange(latent.shape[1])[None, :]
            <= cache_pos + jnp.arange(s)[:, None]
        )
        logits = jnp.where(visible[None, None], logits * self.scale, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        return self._out(
            jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        ), LatentEntry(latent)

    def forward_decode(self, x, rope, cache, positions):
        """One token a serving slot, each at its own depth, in the
        absorbed form: the latent cache is the kernel's operand as it is
        stored, and no row of it goes through ``W_kv_b``."""
        cfg = self.cfg
        b = x.shape[0]
        table = jnp.take(rope, positions, axis=0)[:, None]  # (B, 1, r/2, 2)
        q_nope, q_rope, row = self._project(x, table)
        w = self.wkv_b.weight.reshape(
            cfg.n_heads, cfg.qk_nope_head_dim + cfg.v_head_dim,
            cfg.kv_lora_rank,
        )
        w_uk, w_uv = jnp.split(w, [cfg.qk_nope_head_dim], axis=1)
        q_abs = jnp.einsum("bhn,hnc->bhc", q_nope[:, 0], w_uk)
        pad = jnp.zeros(
            (b, cfg.n_heads, cfg.cache_width - cfg.latent_width), x.dtype
        )
        q_full = jnp.concatenate([q_abs, q_rope[:, 0], pad], axis=-1)
        o_lat, cache = latent_slot_cached_attention(
            q_full, row, cache, positions, value_width=cfg.kv_lora_rank,
            scale=self.scale, use_flash=cfg.use_flash,
        )
        o = jnp.einsum("bhc,hvc->bhv", o_lat, w_uv)
        return self.wo(o.reshape(b, 1, -1)), cache


class DeepseekV3Block(nn.Module):
    def __init__(self, cfg: DeepseekV3Config, layer: int):
        super().__init__()
        self.attn_norm = nn.RMSNorm(cfg.dim, eps=cfg.norm_eps, dtype=cfg.dtype)
        self.attn = MLAttention(cfg)
        self.mlp_norm = nn.RMSNorm(cfg.dim, eps=cfg.norm_eps, dtype=cfg.dtype)
        if layer < cfg.first_k_dense:
            self.mlp = LlamaMLP(cfg)
        else:
            self.mlp = MoE(
                cfg.dim, cfg.moe_ffn_dim, cfg.n_routed_experts,
                top_k=cfg.top_k, dtype=cfg.dtype,
                dispatch_mode="grouped",
                scoring="sigmoid", selection_bias=True,
                routed_scale=cfg.routed_scale,
                shared_ffn_dim=cfg.n_shared_experts * cfg.moe_ffn_dim,
                weight_init=_hf_normal,
                use_kernel=cfg.use_flash,
            )

    # scopes are metadata only: the compiled operations carry
    # ``latent_attention`` / ``mlp`` (and the MoE's ``moe/route``,
    # ``moe/experts``, ``moe/shared``) in their op_name

    def _mlp_half(self, x):
        with jax.named_scope("mlp"):
            return x + self.mlp(self.mlp_norm(x))

    def forward(self, x, rope):
        with jax.named_scope("latent_attention"):
            x = x + self.attn(self.attn_norm(x), rope)
        return self._mlp_half(x)

    def forward_cached(self, x, rope, cache, cache_pos):
        with jax.named_scope("latent_attention"):
            a, cache = self.attn.forward_cached(
                self.attn_norm(x), rope, cache, cache_pos
            )
            x = x + a
        return self._mlp_half(x), cache

    def forward_decode(self, x, rope, cache, positions):
        with jax.named_scope("latent_attention"):
            a, cache = self.attn.forward_decode(
                self.attn_norm(x), rope, cache, positions
            )
            x = x + a
        return self._mlp_half(x), cache


class DeepseekV3(nn.Module):
    #: the serve engine reads this: ``forward_cached`` can apply the head
    #: to one position only
    prefill_logits_at = True
    #: the expert layers record rows and groups under
    #: ``nn.moe.moe_count_tape`` (the grouped path does)
    moe_counters = True

    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        self.cfg = cfg
        self.tok_emb = nn.Embedding(
            cfg.vocab_size, cfg.dim, dtype=cfg.dtype, weight_init=_hf_normal
        )
        self.blocks = nn.ModuleList(
            [DeepseekV3Block(cfg, i) for i in range(cfg.n_layers)]
        )
        self.norm = nn.RMSNorm(cfg.dim, eps=cfg.norm_eps, dtype=cfg.dtype)
        self.lm_head = nn.Linear(
            cfg.dim, cfg.vocab_size, bias=False, dtype=cfg.dtype,
            weight_init=_hf_normal,
        )

    @classmethod
    def from_name(cls, name: str, **overrides) -> "DeepseekV3":
        kw = dict(deepseek_v3_configs[name])
        kw.update(overrides)
        return cls(DeepseekV3Config(**kw))

    def _rope(self):
        cfg = self.cfg
        return _rope_freqs(cfg.qk_rope_head_dim, cfg.max_seq_len, cfg.rope_theta)

    def _head(self, x):
        with jax.named_scope("vocab_projection"):
            return self.lm_head(self.norm(x))

    def forward(self, tokens, return_hidden: bool = False):
        x = self.tok_emb(tokens)
        rope = self._rope()
        for blk in self.blocks:
            x = blk(x, rope)
        if return_hidden:
            return self.norm(x)
        return self._head(x)

    def init_cache(self, batch_size: int, max_seq: Optional[int] = None):
        """Per layer a ``LatentEntry(latent)``: zeros (B, max_seq,
        cache_width)."""
        cfg = self.cfg
        shape = (batch_size, max_seq or cfg.max_seq_len, cfg.cache_width)
        return [
            LatentEntry(jnp.zeros(shape, cfg.dtype)) for _ in range(cfg.n_layers)
        ]

    def forward_cached(self, tokens, cache, cache_pos, logits_at=None):
        """``tokens`` (prefill chunk or one decode token) against the
        cache from ``cache_pos``.  Returns (logits, new_cache).  With
        ``logits_at`` (a traced position within ``tokens``) the head is
        applied to that one position and the logits are (B, 1, vocab):
        a prefill samples one token, and the (bucket, vocab) array is
        never made."""
        x = self.tok_emb(tokens)
        rope = self._rope()
        new_cache = []
        for blk, c in zip(self.blocks, cache):
            x, c = blk.forward_cached(x, rope, c, cache_pos)
            new_cache.append(c)
        if logits_at is not None:
            x = lax.dynamic_slice_in_dim(x, logits_at, 1, axis=1)
        return self._head(x), new_cache

    def forward_decode(self, tokens, cache, positions, page_tables=None):
        """One decode step for a batch of serving slots: ``tokens``
        (B, 1), ``positions`` (B,) int32; ``cache`` the engine's latent
        slab, per layer ``(latent (slots, max_len, cache_width),)``."""
        if page_tables is not None:
            raise ValueError(
                "a paged cache is not supported over a latent cache"
            )
        x = self.tok_emb(tokens)
        rope = self._rope()
        new_cache = []
        for blk, c in zip(self.blocks, cache):
            x, c = blk.forward_decode(x, rope, c, positions)
            new_cache.append(c)
        return self._head(x), new_cache
