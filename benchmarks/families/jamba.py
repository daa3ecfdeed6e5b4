"""The Jamba family (``torchdistx_tpu.models.Jamba``: ``model_type:
jamba`` -- Mamba-1 state-space layers with an attention layer every
``attn_layer_period``, a dense SwiGLU after every mixer, no positional
encoding, tied embeddings), as the harness's protocol asks of every
family:

``constructor(config)``  configuration file -> the program's model; the
                         only place that turns the published key names
                         into the program's, refusing what it does not
                         pass on
``reference``            the family's plain reference
                         (``jamba_reference.py``): ``Arch``,
                         ``leaf_plan``, ``ServeReference`` (and its
                         planted fault, ``drop_state_at``),
                         ``PRECISIONS``; no ``TrainReference`` (the
                         family has no training cell: the selective scan
                         has no backward in the program)
``counts``               the family's model FLOPs and its kernels' needs
                         (``jamba_counts.py``): ``serve_flops``,
                         ``selective_scan_need``, ``state_update_need``
"""

from __future__ import annotations

from families import jamba_counts as counts  # noqa: F401
from families import jamba_reference as reference  # noqa: F401

#: what the program implements one way only: key -> the value it takes
ONLY = {
    "num_experts": 1, "num_experts_per_tok": 1, "sliding_window": None,
    "mamba_proj_bias": False, "mamba_conv_bias": True,
    "tie_word_embeddings": True, "hidden_act": "silu",
}


def constructor(config: dict):
    """A zero-argument constructor for ``tdx.deferred_init``."""
    import jax.numpy as jnp

    from torchdistx_tpu.models import Jamba, JambaConfig

    for key, only in ONLY.items():
        if config.get(key, only) != only:
            raise ValueError(
                f"{key}={config[key]!r}: this adapter passes on only "
                f"{key}={only!r}")
    if config.get("initializer_range", 0.02) != 0.02:
        raise ValueError(
            "initializer_range: the program draws every leaf with std 0.02")
    head = config["hidden_size"] // config["num_attention_heads"]
    if config.get("head_dim", head) != head:
        raise ValueError("head_dim is not hidden_size / num_attention_heads")
    # expert_layer_period / expert_layer_offset say which layers WOULD
    # hold experts: with num_experts 1 every layer's FFN is the dense MLP
    # and they pass on nothing; use_mamba_kernels and num_logits_to_keep
    # steer the published code's own paths and are carried, unused
    extra = dict(config.get("program", {}))
    cfg = JambaConfig(
        vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        ffn_dim=config["intermediate_size"],
        attn_layer_period=config["attn_layer_period"],
        attn_layer_offset=config["attn_layer_offset"],
        d_state=config["mamba_d_state"],
        d_conv=config["mamba_d_conv"],
        expand=config["mamba_expand"],
        dt_rank=config["mamba_dt_rank"],
        conv_bias=config["mamba_conv_bias"],
        proj_bias=config["mamba_proj_bias"],
        num_experts=config["num_experts"],
        sliding_window=config["sliding_window"],
        tie_word_embeddings=config["tie_word_embeddings"],
        max_seq_len=config["max_position_embeddings"],
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(config.get("torch_dtype", "bfloat16")),
        **extra,
    )
    return lambda: Jamba(cfg)
