"""The DeepSeek-V3 family (``torchdistx_tpu.models.DeepseekV3``:
``model_type: deepseek_v3`` -- multi-head latent attention over a latent
cache, sigmoid-routed experts with shared experts), as the harness's
protocol asks of every family:

``constructor(config)``  configuration file -> the program's model; the
                         only place that turns the published key names
                         into the program's, refusing what it does not
                         pass on
``reference``            the family's plain reference
                         (``deepseek_v3_reference.py``): ``Arch``,
                         ``leaf_plan``, ``ServeReference``,
                         ``PRECISIONS``; no ``TrainReference`` (the
                         family has no training cell)
``counts``               the family's model FLOPs and its kernels' needs
                         (``deepseek_v3_counts.py``): ``serve_flops``,
                         ``latent_decode_need``, ``grouped_matmul_need``,
                         ``mla_prefill_need``
"""

from __future__ import annotations

from families import deepseek_v3_counts as counts  # noqa: F401
from families import deepseek_v3_reference as reference  # noqa: F401

#: what the program implements one way only: key -> the value it takes
ONLY = {
    "rope_scaling": None, "q_lora_rank": None, "n_group": 1, "topk_group": 1,
    "tie_word_embeddings": False, "scoring_func": "sigmoid",
    "norm_topk_prob": True, "topk_method": "noaux_tc", "moe_layer_freq": 1,
    "attention_bias": False, "hidden_act": "silu", "rope_interleave": True,
}


def constructor(config: dict):
    """A zero-argument constructor for ``tdx.deferred_init``."""
    import jax.numpy as jnp

    from torchdistx_tpu.models import DeepseekV3, DeepseekV3Config

    for key, only in ONLY.items():
        if config.get(key, only) != only:
            raise ValueError(
                f"{key}={config[key]!r}: this adapter passes on only "
                f"{key}={only!r}")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention has one head count")
    if config["qk_head_dim"] != (config["qk_nope_head_dim"]
                                 + config["qk_rope_head_dim"]):
        raise ValueError("qk_head_dim is not qk_nope_head_dim + qk_rope_head_dim")
    if config.get("initializer_range", 0.02) != 0.02:
        raise ValueError("the program draws every leaf with std 0.02")
    extra = dict(config.get("program", {}))
    cfg = DeepseekV3Config(
        vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        ffn_dim=config["intermediate_size"],
        moe_ffn_dim=config["moe_intermediate_size"],
        n_routed_experts=config["n_routed_experts"],
        n_shared_experts=config["n_shared_experts"],
        top_k=config["num_experts_per_tok"],
        first_k_dense=config["first_k_dense_replace"],
        routed_scale=float(config["routed_scaling_factor"]),
        n_group=config["n_group"],
        topk_group=config["topk_group"],
        q_lora_rank=config["q_lora_rank"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(config.get("torch_dtype", "bfloat16")),
        **extra,
    )
    return lambda: DeepseekV3(cfg)
