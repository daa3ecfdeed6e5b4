"""End-to-end example: elastic training with failure detection.

Composes the three elasticity layers (utils/failure.py):
  - guard_nonfinite_updates: non-finite gradients apply no update,
  - FailureDetector + on_failure="restore": a run whose loss diverges
    rolls back to the latest health-gated checkpoint and continues,
  - Heartbeat: an external supervisor can watch the stamp file.

A gradient-poisoning fault is injected mid-run to show the recovery.

Run on a TPU host:          python examples/elastic_training.py
Run on CPU (8 virtual):     XLA_FLAGS=--xla_force_host_platform_device_count=8 \
                            JAX_PLATFORMS=cpu python examples/elastic_training.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import torchdistx_tpu as tdx
from torchdistx_tpu import nn
from torchdistx_tpu.nn import functional_call
from torchdistx_tpu.trainer import Trainer
from torchdistx_tpu.utils.failure import (
    FailureDetector,
    Heartbeat,
    guard_nonfinite_updates,
)


class MLP(nn.Module):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(32, 128)
        self.fc2 = nn.Linear(128, 1)

    def forward(self, x):
        return self.fc2(nn.functional.relu(self.fc1(x)))


def main() -> None:
    tdx.manual_seed(0)
    model = tdx.deferred_init(MLP)
    tdx.materialize_module(model)
    params = dict(model.named_parameters())

    # in-step protection: a poisoned gradient applies NO update
    tx = guard_nonfinite_updates(optax.adam(1e-3))

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((functional_call(model, p, (x,)) - y) ** 2)

    @jax.jit
    def step(p, s, batch):
        loss, grads = jax.value_and_grad(loss_fn)(p, batch)
        updates, s = tx.update(grads, s, p)
        return optax.apply_updates(p, updates), s, loss

    workdir = tempfile.mkdtemp(prefix="elastic_")

    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(64, 32), jnp.float32)
    y = jnp.sum(x[:, :4], axis=1, keepdims=True)

    def batches():
        n = 0
        while True:
            n += 1
            if n == 30:  # injected fault: corrupted batch / bad shard read
                yield x, y * jnp.float32(float("nan"))
            else:
                yield x, y

    with Heartbeat(os.path.join(workdir, "heartbeat"), interval_s=5.0) as hb:

        def log(metrics):
            hb.step = metrics.get("step", hb.step)  # step-resolution liveness
            print(__import__("json").dumps(metrics), flush=True)

        trainer = Trainer(
            step,
            params,
            tx.init(params),
            log_every=10,
            log_fn=log,
            checkpoint_dir=workdir,
            checkpoint_every=10,
            failure_detector=FailureDetector(nan_tolerance=0, step_deadline_s=120),
            on_failure="restore",
        )
        trainer.fit(batches(), num_steps=60)

    print(f"done at step {trainer.global_step}; checkpoints in {workdir}")
    for leaf in jax.tree_util.tree_leaves(trainer.params):
        assert bool(jnp.all(jnp.isfinite(leaf))), "params must stay finite"


if __name__ == "__main__":
    main()
