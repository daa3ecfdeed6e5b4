"""End-to-end example: deferred-init GPT-2, FSDP-shard it across all local
devices, and train on a synthetic token stream with AnyPrecisionAdamW.

Run on a TPU host:          python examples/train_gpt2.py
Run on CPU (8 virtual):     XLA_FLAGS=--xla_force_host_platform_device_count=8 \
                            JAX_PLATFORMS=cpu python examples/train_gpt2.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

import torchdistx_tpu as tdx
from torchdistx_tpu import nn
from torchdistx_tpu.data import DataLoader, TokenDataset
from torchdistx_tpu.models import GPT2
from torchdistx_tpu.nn import functional_call
from torchdistx_tpu.optimizers import (
    anyprecision_adamw,
    decay_labels,
    with_param_groups,
)
from torchdistx_tpu.parallel import ShardedTrainStep, create_mesh, fsdp_shard_rule
from torchdistx_tpu.trainer import Trainer


def main() -> None:
    mesh = create_mesh({"fsdp": -1})  # all local devices

    # 1. construct with zero storage, materialize directly into FSDP shards
    tdx.manual_seed(0)
    model = tdx.deferred_init(GPT2.from_name, "tiny")
    tdx.materialize_module(model, sharding_rule=fsdp_shard_rule(mesh))
    print(f"model: {model.num_params()/1e6:.2f}M params, sharded over "
          f"{mesh.devices.size} devices")

    def loss_fn(params, batch):
        tokens, labels = batch
        logits = functional_call(model, params, (tokens,))
        return nn.functional.cross_entropy(logits, labels)

    # the standard torch two-group recipe (weight decay on matrices only),
    # expressed as labeled leaves: decay_labels routes biases/norm scales
    # to the no_decay group, everything else decays
    optimizer = with_param_groups(
        anyprecision_adamw,
        groups={
            "decay": {"weight_decay": 0.01},
            "no_decay": {"weight_decay": 0.0},
        },
        labels=decay_labels,
        learning_rate=3e-4,
        use_kahan_summation=True,
    )
    step = ShardedTrainStep(
        loss_fn,
        optimizer,
        mesh,
        shard_axis="fsdp",
    )
    params = dict(model.named_parameters())
    opt_state = step.init_optimizer(params)

    # 2. synthetic data, prefetched to device
    stream = np.random.RandomState(0).randint(0, 256, 500_000)
    loader = DataLoader(
        TokenDataset(stream, seq_len=64),
        batch_size=8 * max(1, mesh.devices.size // 8),
        shuffle=True,
        seed=0,
    )

    # 3. train
    trainer = Trainer(step, params, opt_state,
                      tokens_per_batch=loader.batch_size * 64, log_every=20)
    trainer.fit(iter(loader), num_steps=100)


if __name__ == "__main__":
    main()
