"""T5 train-step throughput: the biased-flash-backward delta on real TPU.

Measures one encoder-decoder T5 train step (relative-position bias
streamed into the flash kernels, AnyPrecisionAdamW) with the pallas
biased backward vs the round-3 chunked-recompute backward
(``--chunked-bwd``), using the same multi-second lax.scan window +
layout-fixpoint warmup as bench.py's train phase.

Usage (TPU):  python scripts/bench_t5_train.py [--chunked-bwd]
Smoke (CPU):  JAX_PLATFORMS=cpu TDX_T5_MODEL=tiny TDX_BENCH_SEQ=64 \
                  python scripts/bench_t5_train.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--chunked-bwd", action="store_true",
        help="force the round-3 chunked-recompute biased backward (A/B)",
    )
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()

    import jax

    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    import torchdistx_tpu as tdx
    from torchdistx_tpu.nn import functional
    from torchdistx_tpu.nn.module import functional_call
    from torchdistx_tpu.models import T5
    from torchdistx_tpu.models.t5 import t5_configs
    from torchdistx_tpu.optimizers import anyprecision_adamw
    from torchdistx_tpu.ops import flash_attention as fa
    from torchdistx_tpu.utils.benchmarks import (
        peak_bf16_flops,
        warm_to_steady_state,
    )

    # raises on a device kind with no peak on record: approx_mfu is
    # never computed against a default
    peak = peak_bf16_flops(jax.devices()[0].device_kind)

    fa._FORCE_CHUNKED_BWD = args.chunked_bwd

    name = os.environ.get("TDX_T5_MODEL", "t5_large")
    batch = int(os.environ.get("TDX_BENCH_BATCH", "4"))
    seq = int(os.environ.get("TDX_BENCH_SEQ", "512"))
    dtype = jnp.bfloat16 if plat != "cpu" else jnp.float32

    tdx.manual_seed(0)
    model = tdx.deferred_init(
        T5.from_name, name, dtype=dtype, use_flash=True
    )
    tdx.materialize_module(model)
    params = dict(model.named_parameters())
    n_params = model.num_params()

    tx = anyprecision_adamw(1e-4)
    opt_state = tx.init(params)

    cfg = t5_configs[name]
    vocab = cfg.get("vocab_size", 32128)
    rs = np.random.RandomState(0)
    src = jnp.asarray(rs.randint(0, vocab, (batch, seq)), jnp.int32)
    tgt = jnp.asarray(rs.randint(0, vocab, (batch, seq)), jnp.int32)

    def loss_fn(p):
        logits = functional_call(model, p, (src, tgt))
        return functional.cross_entropy(logits, tgt)

    def one_step(carry, _):
        p, s = carry
        loss, g = jax.value_and_grad(loss_fn)(p)
        u, s = tx.update(g, s, p)
        import optax

        return (optax.apply_updates(p, u), s), loss

    n_steps = args.steps

    @jax.jit
    def run(carry):
        return lax.scan(one_step, carry, None, length=n_steps)

    carry = (params, opt_state)
    carry, warm_times, converged = warm_to_steady_state(
        run, carry, sync=lambda losses: float(np.asarray(losses[-1]))
    )
    t0 = time.perf_counter()
    carry, losses = run(carry)
    final = float(np.asarray(losses[-1]))
    dt = time.perf_counter() - t0

    # model FLOPs: 6 * params * tokens (enc+dec both seq-length) + attention
    toks = n_steps * batch * seq
    tokens_per_sec = toks / dt
    flops_per_token = 6 * n_params
    from torchdistx_tpu.obs.ledger import record_stamp

    print(json.dumps({
        **record_stamp(),
        "model": name,
        "params": int(n_params),
        "batch": batch,
        "seq": seq,
        "backward": "chunked" if args.chunked_bwd else "kernel",
        "steps": n_steps,
        "window_s": round(dt, 3),
        "warm_calls_s": [round(t, 2) for t in warm_times],
        "warm_converged": converged,
        "tokens_per_sec": round(tokens_per_sec, 1),
        "approx_mfu": round(
            tokens_per_sec * flops_per_token / peak, 4
        ),
        "final_loss": round(final, 4),
    }))


if __name__ == "__main__":
    main()
