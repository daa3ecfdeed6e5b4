"""Flash-attention vs reference attention on the TPU chip.

Times fwd and fwd+bwd at Llama-7B attention shapes (H=32, D=128, bf16)
across sequence lengths.  Each measurement jits a lax.scan of ``iters``
applications, so one timed call amortizes dispatch over many kernel runs.
Rehearse on CPU with ``JAX_PLATFORMS=cpu`` (interpret-mode kernels: it
proves the script runs, its times mean nothing).

Usage: python scripts/bench_flash_attention.py [--seqs 2048,4096,8192,16384]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

from torchdistx_tpu.obs.ledger import record_stamp as _stamp
from torchdistx_tpu.ops.attention import multihead_attention
from torchdistx_tpu.ops.flash_attention import flash_attention

B, H, D = 1, 32, 128


def _inputs(seq, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)  # tdx-lint: disable=TDX102 -- fixed-seed bench input data, not parameter init
    shape = (B, seq, H, D)
    return tuple(
        jax.random.normal(k, shape, jnp.bfloat16) / math.sqrt(D) for k in ks
    )


def _time(fn, *args, iters):
    import numpy as np

    @jax.jit
    def many(q, k, v):
        def body(c, _):
            # the carry perturbs q so each iteration depends on the last —
            # without this XLA hoists the loop-invariant attention out of
            # the scan and the "benchmark" measures one application
            out = fn(q * (1.0 + c * 1e-30).astype(q.dtype), k, v)
            return out, None

        c, _ = lax.scan(
            body, jnp.zeros((), jnp.float32), None, length=iters
        )
        return c

    # the host fetch of the scalar result ends the timed region
    float(np.asarray(many(*args)))  # compile + warm
    t0 = time.perf_counter()
    float(np.asarray(many(*args)))
    dt = time.perf_counter() - t0
    return dt / iters


def attention_flops(seq, fwd_only):
    # 2 matmuls (QK^T, PV): 4*B*H*S^2*D fwd; bwd ~2x fwd (recompute ~+1x)
    f = 4 * B * H * seq * seq * D
    return f if fwd_only else 3 * f


def bias_rows(seqs):
    """Biased (T5 relative-position) fwd+bwd: pallas kernel backward vs
    the round-3 chunked-recompute backward.  Bias is O(H*S^2) memory, so
    realistic seqs stop well short of the bias-free 64k rows."""
    from torchdistx_tpu.ops import flash_attention as fa

    results = []
    for seq in seqs:
        q, k, v = _inputs(seq)
        bias = (
            jax.random.normal(jax.random.PRNGKey(7), (H, seq, seq), jnp.bfloat16)  # tdx-lint: disable=TDX102 -- fixed-seed bench bias data, not parameter init
            * 0.02
        )
        per_iter = attention_flops(seq, False)
        iters = int(os.environ.get(
            "TDX_BENCH_ITERS",
            max(4, min(1024, int(3.0 * 100e12 / per_iter))),
        ))

        def biased_loss(q, k, v, b):
            return (
                fa.flash_attention(q, k, v, bias=b, causal=True)
                .mean()
                .astype(jnp.float32)
            )

        def step(q, k, v):
            # consume EVERY gradient: an unused dk/dv/dbias is dead code
            # XLA eliminates, and the leg would time only the dq kernel
            grads = jax.grad(biased_loss, (0, 1, 2, 3))(q, k, v, bias)
            return sum(g.mean().astype(jnp.float32) for g in grads)

        row = {"seq": seq, "bias": True, **_stamp()}
        for name, forced in (("kernel_bwd", False), ("chunked_bwd", True)):
            fa._FORCE_CHUNKED_BWD = forced
            try:
                dt = _time(step, q, k, v, iters=iters)
                row[name] = dt
                row[name + "_tflops"] = (
                    attention_flops(seq, False) / dt / 1e12
                )
            except Exception as e:  # noqa: BLE001 — OOM at long seq is data
                row[name] = None
                row[name + "_err"] = f"{type(e).__name__}"
            finally:
                fa._FORCE_CHUNKED_BWD = False
        if row.get("kernel_bwd") and row.get("chunked_bwd"):
            row["kernel_speedup"] = row["chunked_bwd"] / row["kernel_bwd"]
        results.append(row)
        print(json.dumps(row))
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="2048,4096,8192,16384")
    ap.add_argument(
        "--bias", action="store_true",
        help="measure the biased (T5) fwd+bwd kernel-vs-chunked A/B instead",
    )
    args = ap.parse_args()
    seqs = [int(s) for s in args.seqs.split(",")]
    if args.bias:
        print(f"platform={jax.devices()[0].platform} B={B} H={H} D={D} "
              f"bf16 biased")
        return bias_rows(seqs)
    print(f"platform={jax.devices()[0].platform} B={B} H={H} D={D} bf16")
    results = []
    for seq in seqs:
        q, k, v = _inputs(seq)
        # size the scan so the timed region is multi-second at ~100 TFLOP/s
        # effective
        per_iter = attention_flops(seq, True)
        iters = int(os.environ.get(
            "TDX_BENCH_ITERS",
            max(8, min(4096, int(4.0 * 100e12 / per_iter))),
        ))

        def ref_fwd(q, k, v):
            return multihead_attention(q, k, v, causal=True).mean().astype(
                jnp.float32
            )

        def flash_fwd(q, k, v):
            return flash_attention(q, k, v, causal=True).mean().astype(
                jnp.float32
            )

        def ref_step(q, k, v):
            # sum over ALL grads — keeping only dq lets XLA dead-code the
            # dK/dV work out of the timed region (round-3 rows used [0];
            # re-measured rows supersede them)
            grads = jax.grad(
                lambda a, b, c: ref_fwd(a, b, c).sum(), (0, 1, 2)
            )(q, k, v)
            return sum(g.mean().astype(jnp.float32) for g in grads)

        def flash_step(q, k, v):
            grads = jax.grad(
                lambda a, b, c: flash_fwd(a, b, c).sum(), (0, 1, 2)
            )(q, k, v)
            return sum(g.mean().astype(jnp.float32) for g in grads)

        row = {"seq": seq, **_stamp()}
        for name, fn, fwd_only in (
            ("ref_fwd", ref_fwd, True),
            ("flash_fwd", flash_fwd, True),
            ("ref_fwdbwd", ref_step, False),
            ("flash_fwdbwd", flash_step, False),
        ):
            try:
                dt = _time(fn, q, k, v, iters=iters)
                row[name] = dt
                row[name + "_tflops"] = attention_flops(seq, fwd_only) / dt / 1e12
            except Exception as e:  # noqa: BLE001 — OOM at long seq is data
                row[name] = None
                row[name + "_err"] = f"{type(e).__name__}"
        if row.get("ref_fwd") and row.get("flash_fwd"):
            row["fwd_speedup"] = row["ref_fwd"] / row["flash_fwd"]
        if row.get("ref_fwdbwd") and row.get("flash_fwdbwd"):
            row["fwdbwd_speedup"] = row["ref_fwdbwd"] / row["flash_fwdbwd"]
        results.append(row)
        print(json.dumps(row))
    return results


if __name__ == "__main__":
    main()
