"""ViT image classification: deferred init at real scale, then a sharded
fine-tuning loop on synthetic data.

Run on a TPU host:          python examples/vit_train.py
Run on CPU (8 virtual):     XLA_FLAGS=--xla_force_host_platform_device_count=8 \
                            JAX_PLATFORMS=cpu python examples/vit_train.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np
import optax

import torchdistx_tpu as tdx
from torchdistx_tpu.models import ViT
from torchdistx_tpu.nn import functional, functional_call
from torchdistx_tpu.parallel import ShardedTrainStep, create_mesh, fsdp_shard_rule


def main() -> None:
    # 1. inspect the real thing without allocating it: ViT-L/16 in fake mode
    with tdx.fake_mode():
        big = ViT.from_name("vit_l16")
    print(f"ViT-L/16: {big.num_params()/1e6:.1f}M params (zero bytes held)")

    # 2. train a small one, FSDP-sharded, on synthetic labels
    mesh = create_mesh({"fsdp": -1})
    name = os.environ.get("TDX_VIT_MODEL", "tiny")
    tdx.manual_seed(0)
    model = tdx.deferred_init(ViT.from_name, name)
    tdx.materialize_module(model, sharding_rule=fsdp_shard_rule(mesh))
    print(f"model: {model.num_params()/1e6:.2f}M params over "
          f"{mesh.devices.size} devices")

    params = dict(model.named_parameters())
    size = model.cfg.image_size

    def loss_fn(p, batch):
        images, labels = batch
        return functional.cross_entropy(
            functional_call(model, p, (images,)), labels
        )

    step = ShardedTrainStep(
        loss_fn, optax.adamw(3e-4, weight_decay=0.05), mesh,
        shard_axis="fsdp",
    )
    # params were born sharded (materialize_module's sharding_rule);
    # only the optimizer state needs explicit placement
    opt_state = step.init_optimizer(params)

    rs = np.random.RandomState(0)
    for i in range(30):
        images = rs.randn(8, 3, size, size).astype(np.float32)
        labels = (rs.rand(8) * model.cfg.num_classes).astype(np.int32)
        params, opt_state, loss = step(params, opt_state, (images, labels))
        if (i + 1) % 10 == 0:
            print(f"step {i + 1}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
