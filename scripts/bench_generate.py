"""Decode throughput: tokens/sec for KV-cache generation, bf16 vs
weight-only int8 (``--quantize``).

Decode is weight-read-bound — each generated token streams the full
parameter set from HBM — so int8 weights should approach 2x bf16 decode
throughput on large models.  Timed over a multi-token window.

Usage (TPU):  python scripts/bench_generate.py [--quantize]
Smoke (CPU):  JAX_PLATFORMS=cpu TDX_GEN_MODEL=tiny \
                  python scripts/bench_generate.py --new-tokens 8
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
    ap.add_argument("--quantize", action="store_true",
                    help="weight-only int8 (nn.quantize_module)")
    ap.add_argument("--new-tokens", type=int, default=256)
    ap.add_argument("--batch", type=int, default=1)
    args = ap.parse_args()

    import jax

    plat = jax.devices()[0].platform

    import jax.numpy as jnp
    import numpy as np

    import torchdistx_tpu as tdx
    from torchdistx_tpu.generation import generate
    from torchdistx_tpu.models import Llama
    from torchdistx_tpu.nn import quantize_module

    name = os.environ.get("TDX_GEN_MODEL", "llama_1b")
    dtype = jnp.bfloat16 if plat != "cpu" else jnp.float32

    tdx.manual_seed(0)
    model = tdx.deferred_init(Llama.from_name, name, dtype=dtype)
    tdx.materialize_module(model)
    if args.quantize:
        quantize_module(model)
    n_bytes = sum(
        p.size * p.dtype.itemsize for _, p in model.named_parameters()
    )

    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, 256, (args.batch, 32)),
        jnp.int32,
    )
    # warm: first call compiles prefill + decode scan
    out = generate(model, prompt, max_new_tokens=args.new_tokens)
    np.asarray(out)
    t0 = time.perf_counter()
    out = generate(model, prompt, max_new_tokens=args.new_tokens)
    np.asarray(out)
    dt = time.perf_counter() - t0

    toks = args.batch * args.new_tokens
    from torchdistx_tpu.obs.ledger import record_stamp

    print(json.dumps({
        **record_stamp(),
        "model": name,
        "quantized": args.quantize,
        "param_bytes_gb": round(n_bytes / 1e9, 3),
        "batch": args.batch,
        "new_tokens": args.new_tokens,
        "window_s": round(dt, 3),
        "decode_tokens_per_sec": round(toks / dt, 1),
        # weight-streaming bound: bytes * tokens / window
        "effective_weight_bw_gbps": round(n_bytes * toks / dt / 1e9, 1),
    }))


if __name__ == "__main__":
    main()
