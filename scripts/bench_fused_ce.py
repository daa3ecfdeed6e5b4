"""Fused LM-head cross-entropy vs the unfused logits path, on-chip.

Kernel-level companion to the end-to-end ``TDX_BENCH_FUSED_CE=1 bench.py``
A/B: times value_and_grad of the loss alone (matmul + CE fwd + dX + dW)
at LM-head shapes, fused (``ops.fused_ce``: logits never in HBM) vs
unfused (XLA einsum + f32 log-softmax).  Each measurement jits a
lax.scan of ``iters`` applications, so one timed call amortizes dispatch
over many kernel runs.

Usage:
    python scripts/bench_fused_ce.py            # TPU
    JAX_PLATFORMS=cpu python scripts/bench_fused_ce.py \
        --shapes 256x128x1000 --iters 2         # rehearsal, times mean nothing
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
        "--shapes",
        # NxDxV: bench shape (2x2048 tokens, llama_1b head) plus a 7B-ish
        # head and a small control
        default="4096x2048x32000,4096x4096x32000,1024x1024x32000",
    )
    ap.add_argument("--iters", type=int, default=30)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from torchdistx_tpu.nn import functional
    from torchdistx_tpu.ops.fused_ce import fused_linear_cross_entropy

    def unfused(x, w, y):
        return functional.cross_entropy(jnp.einsum("nd,vd->nv", x, w), y)

    def fused(x, w, y):
        return fused_linear_cross_entropy(x, w, y)

    def timed(fn, x, w, y, iters):
        import numpy as np

        grad = jax.value_and_grad(fn, argnums=(0, 1))

        @jax.jit
        def many(x, w, y):
            def body(c, _):
                # perturb x by the carry so iterations chain — otherwise
                # XLA hoists the loop-invariant loss out of the scan
                l, (dx, dw) = grad(
                    x * (1.0 + c * 1e-30).astype(x.dtype), w, y
                )
                # consume EVERY gradient: an unused dx/dw is dead code XLA
                # eliminates, and the timed region would be forward-only
                # (the round-3 flash-bench lesson, BASELINE.md)
                c = (
                    l.astype(jnp.float32)
                    + dx.sum().astype(jnp.float32) * 1e-30
                    + dw.sum().astype(jnp.float32) * 1e-30
                )
                return c, None
            out, _ = lax.scan(body, jnp.float32(0), None, length=iters)
            return out

        r = many(x, w, y)  # compile + warm
        jax.block_until_ready(r)
        t0 = time.perf_counter()
        r = many(x, w, y)
        jax.block_until_ready(r)
        dt = time.perf_counter() - t0
        assert np.isfinite(float(r))
        return dt / iters

    for spec in args.shapes.split(","):
        n, d, v = (int(s) for s in spec.split("x"))
        ks = jax.random.split(jax.random.PRNGKey(0), 3)  # tdx-lint: disable=TDX102 -- fixed-seed bench input data, not parameter init
        x = jax.random.normal(ks[0], (n, d), jnp.bfloat16)
        w = jax.random.normal(ks[1], (v, d), jnp.bfloat16) * 0.1
        y = jax.random.randint(ks[2], (n,), 0, v)
        t_un = timed(unfused, x, w, y, args.iters)
        t_fu = timed(fused, x, w, y, args.iters)
        from torchdistx_tpu.obs.ledger import record_stamp

        print(json.dumps({
            **record_stamp(),
            "shape": spec,
            "unfused_ms": round(t_un * 1e3, 3),
            "fused_ms": round(t_fu * 1e3, 3),
            "speedup": round(t_un / t_fu, 3),
            "device": str(jax.devices()[0]),
        }), flush=True)


if __name__ == "__main__":
    main()
