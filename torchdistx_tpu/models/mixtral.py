"""Mixtral-family sparse-MoE decoder.

The Mixtral architecture (Jiang et al., arXiv:2401.04088): a Llama-style
decoder whose FFN is a top-k-routed mixture of SwiGLU experts.  Built by
subclassing the flagship :class:`Llama` — :class:`MixtralBlock` plugs an
:class:`nn.MoE` (dense or GShard capacity dispatch, expert parallelism as
a sharding annotation) into :class:`LlamaBlock`'s FFN slot, inheriting
the whole attention (RoPE/GQA/flash/SP), remat, KV-cache, and decode
scaffolding, so everything deferred-inits, shard-materializes, trains,
and generates like the flagship.  No reference counterpart (the
reference has no models; SURVEY §2.4 marks EP absent).

Training uses ``forward_with_aux`` to get the router load-balancing loss
from the same routing pass (Switch-style; weight it with a 1e-2-class
coefficient as in the paper).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ..nn.moe import MoE, moe_shard_rule
from .llama import Llama, LlamaBlock, LlamaConfig, _rope_freqs

__all__ = ["MixtralConfig", "Mixtral", "mixtral_configs"]


@dataclasses.dataclass
class MixtralConfig(LlamaConfig):
    n_experts: int = 8
    top_k: int = 2
    # None = dense compute (every expert, masked combine — exact);
    # a float enables GShard capacity dispatch (see nn.moe)
    capacity_factor: Optional[float] = None
    # "einsum" (GSPMD-partitionable), "gather" (no bookkeeping MACs —
    # the single-chip fast path) or "grouped" (no capacity: no token
    # dropped, work ~ tokens x top_k); see nn.moe's module docstring
    moe_dispatch: str = "einsum"


mixtral_configs = {
    "tiny": dict(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, max_seq_len=128,
        n_experts=4, top_k=2, dtype=jnp.float32,
    ),
    # 8x7B-class spec config (paper table 1); ffn_dim is per-expert
    "mixtral_8x7b": dict(
        vocab_size=32000, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        ffn_dim=14336, max_seq_len=4096, n_experts=8, top_k=2,
    ),
}


class MixtralBlock(LlamaBlock):
    """LlamaBlock with the FFN slot holding a routed MoE; the attention
    half, cache path (``forward_cached``), and residual wiring are
    inherited."""

    def __init__(self, cfg: MixtralConfig):
        super().__init__(
            cfg,
            mlp=MoE(
                cfg.dim,
                cfg.ffn_dim,
                cfg.n_experts,
                top_k=cfg.top_k,
                dtype=cfg.dtype,
                capacity_factor=cfg.capacity_factor,
                dispatch_mode=cfg.moe_dispatch,
            ),
        )

    def forward(self, x, rope, return_aux: bool = False):
        x = x + self.attn(self.attn_norm(x), rope)
        if return_aux:
            y, aux = self.mlp(self.mlp_norm(x), return_aux=True)
            return x + y, aux
        return x + self.mlp(self.mlp_norm(x))


class Mixtral(Llama):
    """``forward``/``forward_cached``/``init_cache``/``generate`` (and the
    remat policy) are the inherited Llama paths over MoE blocks; only the
    aux-loss forward is Mixtral-specific."""

    block_cls = MixtralBlock

    @classmethod
    def from_name(cls, name: str, **overrides) -> "Mixtral":
        kw = dict(mixtral_configs[name])
        kw.update(overrides)
        return cls(MixtralConfig(**kw))

    def forward_with_aux(self, tokens):
        """(logits, aux) where ``aux`` is the mean over layers of the
        Switch load-balancing loss, computed from the same routing pass as
        the forward.  Add ``weight * aux`` to the training loss."""
        cfg = self.cfg
        x = self.tok_emb(tokens)
        rope = _rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
        block_fn = lambda blk, h: blk(h, rope, return_aux=True)  # noqa: E731
        if cfg.remat:
            from .llama import _remat_policy

            block_fn = jax.checkpoint(
                block_fn, static_argnums=(0,),
                policy=_remat_policy(cfg.remat_policy),
            )
        aux_total = jnp.zeros((), jnp.float32)
        for blk in self.blocks:
            x, aux = block_fn(blk, x)
            aux_total = aux_total + aux
        x = self.norm(x)
        return self.lm_head(x), aux_total / len(self.blocks)

    def shard_rule(self, mesh, ep_axis: str = "ep", base_rule=None):
        """Expert-parallel sharding rule for ``materialize_module`` /
        checkpoint restore: expert-stacked weights over ``ep_axis``, rest
        via ``base_rule`` (see :func:`nn.moe.moe_shard_rule`)."""
        return moe_shard_rule(mesh, ep_axis=ep_axis, base_rule=base_rule)
