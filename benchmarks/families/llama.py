"""The Llama family (``torchdistx_tpu.models.Llama``: dense MHA/GQA
decoders), as the harness's protocol asks of every family:

``constructor(config)``  configuration file -> the program's model; the
                         only place that turns the published key names
                         into the program's
``reference``            the family's plain reference
                         (``llama_reference.py``): ``Arch``, ``leaf_plan``,
                         ``ServeReference``, ``TrainReference``,
                         ``sample_leaves``, ``PRECISIONS``
``counts``               the family's model FLOPs (``llama_counts.py``):
                         ``train_flops_per_token``, ``serve_flops``
"""

from __future__ import annotations

from families import llama_counts as counts  # noqa: F401
from families import llama_reference as reference  # noqa: F401


def constructor(config: dict):
    """A zero-argument constructor for ``tdx.deferred_init``."""
    import jax.numpy as jnp

    from torchdistx_tpu.models import Llama
    from torchdistx_tpu.models.llama import LlamaConfig

    if config["hidden_size"] // config["num_attention_heads"] != config["head_dim"]:
        raise ValueError("the program derives head_dim as hidden/heads")
    if config.get("sliding_window") is not None or config.get("rope_scaling"):
        raise ValueError("configuration asks for what this adapter does not pass on")
    extra = dict(config.get("program", {}))
    cfg = LlamaConfig(
        vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        ffn_dim=config["intermediate_size"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(config.get("torch_dtype", "bfloat16")),
        sliding_window=None,
        **extra,
    )
    return lambda: Llama(cfg)
