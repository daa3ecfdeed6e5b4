"""The Qwen3-Next family (``torchdistx_tpu.models.Qwen3Next``:
``model_type: qwen3_next`` -- Gated-DeltaNet layers with a gated softmax
attention layer every ``full_attention_interval``, every feed-forward an
expert layer with a gated shared expert, untied head), as the harness's
protocol asks of every family:

``constructor(config)``  configuration file -> the program's model; the
                         only place that turns the published key names
                         into the program's, refusing what it does not
                         pass on
``reference``            the family's plain reference
                         (``qwen3_next_reference.py``): ``Arch``,
                         ``leaf_plan``, ``ServeReference`` (and its
                         planted fault, ``drop_state_at``),
                         ``PRECISIONS``; no ``TrainReference`` (the
                         family has no training cell: neither the
                         chunked delta rule nor the grouped matmul has a
                         backward in the program)
``counts``               the family's model FLOPs and its kernels' needs
                         (``qwen3_next_counts.py``): ``serve_flops``,
                         ``gdn_update_need``, ``gdn_chunk_need``,
                         ``grouped_matmul_need``

A configuration may hold a SHARE of the routed experts: ``num_experts``
is then what is held (a ``reduced`` key), ``router_width`` the published
count the router scores, ``experts_held`` the range ``[lo, hi)``.
"""

from __future__ import annotations

from families import qwen3_next_counts as counts  # noqa: F401
from families import qwen3_next_reference as reference  # noqa: F401


def constructor(config: dict):
    """A zero-argument constructor for ``tdx.deferred_init``."""
    import jax.numpy as jnp

    from torchdistx_tpu.models import Qwen3Next, Qwen3NextConfig

    if config.get("hidden_act", "silu") != "silu":
        raise ValueError(
            f"hidden_act={config['hidden_act']!r}: this adapter passes on "
            "only hidden_act='silu'")
    if config.get("initializer_range", 0.02) != 0.02:
        raise ValueError(
            "initializer_range: the program draws every leaf with std 0.02")
    for key, only in (("gdn_state_dtype", "float32"),
                      ("conv_state_dtype", config.get("torch_dtype", "bfloat16"))):
        if config.get(key, only) != only:
            raise ValueError(
                f"{key}={config[key]!r}: the program keeps it in {only}")
    width = config.get("router_width", config["num_experts"])
    lo, hi = config.get("experts_held", (0, config["num_experts"]))
    if hi - lo != config["num_experts"]:
        raise ValueError(
            f"experts_held={[lo, hi]} does not hold num_experts="
            f"{config['num_experts']} experts")
    # intermediate_size is the width of a dense layer's MLP: with
    # mlp_only_layers [] and decoder_sparse_step 1 no layer is dense and
    # it passes on nothing (carried, unused)
    extra = dict(config.get("program", {}))
    cfg = Qwen3NextConfig(
        vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        full_attention_interval=config["full_attention_interval"],
        partial_rotary_factor=float(config["partial_rotary_factor"]),
        rope_theta=float(config["rope_theta"]),
        gdn_key_heads=config["linear_num_key_heads"],
        gdn_value_heads=config["linear_num_value_heads"],
        gdn_key_dim=config["linear_key_head_dim"],
        gdn_value_dim=config["linear_value_head_dim"],
        d_conv=config["linear_conv_kernel_dim"],
        n_experts=width,
        top_k=config["num_experts_per_tok"],
        moe_ffn_dim=config["moe_intermediate_size"],
        shared_ffn_dim=config["shared_expert_intermediate_size"],
        experts_held=(lo, hi),
        max_seq_len=config["max_position_embeddings"],
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(config.get("torch_dtype", "bfloat16")),
        mlp_only_layers=tuple(config["mlp_only_layers"]),
        decoder_sparse_step=config["decoder_sparse_step"],
        use_sliding_window=config["use_sliding_window"],
        rope_scaling=config["rope_scaling"],
        tie_word_embeddings=config["tie_word_embeddings"],
        norm_topk_prob=config["norm_topk_prob"],
        mtp_layers=config.get("num_nextn_predict_layers", 0),
        **extra,
    )
    return lambda: Qwen3Next(cfg)
