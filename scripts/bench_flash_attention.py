"""Flash-attention vs reference attention on the TPU chip.

Times fwd and fwd+bwd at Llama-7B attention shapes (H=32, D=128, bf16)
across sequence lengths.  Each measurement jits a lax.scan of ``iters``
applications, so one timed call amortizes dispatch over many kernel runs.
Rehearse on CPU with ``JAX_PLATFORMS=cpu`` (interpret-mode kernels: it
proves the script runs, its times mean nothing).

``--cells`` times each flash KERNEL alone at the benchmark's five cells'
shapes (``CELLS``: the train cell's step and every prefill bucket of the
four serve cells, rows in ``--dtype``): the forward, and where the
widths are equal the two backward kernels, called as the program calls
them inside ONE jitted loop over ``--sets`` operand sets of their own
(a call's operand depends on the call before, so none is hoisted or
merged; a set that took every turn would stay in the chip's 128 MiB of
VMEM from call to call and the kernel would read no HBM: PR 34).  One
profiler trace of the loop gives each kernel's device microseconds a
call by its name, beside the time its own matmuls take at the bf16 peak
(forward 2, dK/dV 4, dQ 3, over the causal triangle).  ``--block-q`` /
``--block-k`` pass the kernels' block bounds through, a line a value.
To time another checkout's kernels, copy this file into its
``scripts/`` and run it there.

Usage: python scripts/bench_flash_attention.py [--seqs 2048,4096,8192,16384]
       python scripts/bench_flash_attention.py --cells [CELL ...] [--dtype float32] [--block-q 256 512]
"""

from __future__ import annotations

import argparse
import itertools
import json
import math
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
from jax import lax

from torchdistx_tpu.obs.ledger import record_stamp as _stamp
from torchdistx_tpu.ops.attention import multihead_attention
from torchdistx_tpu.ops.flash_attention import flash_attention

B, H, D = 1, 32, 128

#: cell -> the shapes its flash calls have: the batch of one call, the
#: sequence lengths (a step's, or the prefill buckets), query and KV
#: heads, the qk width and, where it differs, the values' width
CELLS = {
    "dscoder-1.3b.train": dict(b=4, seqs=[2048], hq=16, hkv=16, d=128),
    "mistral-7b.batch16": dict(
        b=1, seqs=[128, 256, 512, 1024], hq=32, hkv=8, d=128),
    "kanana-2-30b.batch32-8k": dict(
        b=1, seqs=[1024, 2048, 4096, 6144], hq=32, hkv=32, d=192, dv=128),
    "jamba2-3b.batch256": dict(
        b=1, seqs=[256, 512, 1024], hq=20, hkv=1, d=128),
    "qwen3-next-80b.batch128-4k": dict(
        b=1, seqs=[512, 1024, 2048, 3072], hq=16, hkv=2, d=256),
}
#: kernel -> the matmuls one call does over the causal triangle
KERNEL_MATMULS = {
    "tdx_flash_forward": 2,
    "tdx_flash_backward_dkv": 4,
    "tdx_flash_backward_dq": 3,
}


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


def cell_rows(args):
    """``--cells``: one line a (cell, sequence length, block bounds)."""
    import numpy as np

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    from harness import peaks, tracered

    from torchdistx_tpu.ops import flash_attention as fa

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    peak = peaks.peaks(dev.device_kind)["bf16_flops_per_s"] if on_chip else None
    dtype = jnp.dtype(args.dtype)
    rs = np.random.RandomState(args.seed)
    # the module's own bounds for such rows (a checkout from before it
    # named them had 256 x 512 for all)
    bound_q, bound_k = getattr(
        fa, "_BLOCKS" if dtype.itemsize <= 2 else "_BLOCKS_SMALL", (256, 512))
    todo = [
        (cell, seq, bq, bk)
        for cell in (args.cells or list(CELLS))
        for seq in CELLS[cell]["seqs"]
        for bq, bk in itertools.product(
            args.block_q or [bound_q], args.block_k or [bound_k])
    ]
    results = []
    for cell, seq, bq, bk in todo:
        shape = CELLS[cell]
        b, hq, hkv, d = (shape[n] for n in ("b", "hq", "hkv", "d"))
        dv = shape.get("dv", d)
        backward = dv == d  # the backward kernels take one width
        kw = dict(causal=True, block_q=bq, block_k=bk, interpret=not on_chip)

        def rows(heads, width):
            return jnp.asarray(rs.standard_normal((b, seq, heads, width)), dtype)

        sets = tuple(
            (rows(hq, d), rows(hkv, d), rows(hkv, dv), rows(hq, dv))
            for _ in range(args.sets)
        )

        @jax.jit
        def loop(sets):
            def body(_, c):
                for q, k, v, g in sets:
                    q = q * (1.0 + c * 1e-30).astype(q.dtype)
                    if not backward:
                        c = fa._flash_forward(q, k, v, **kw)[0, 0, 0, 0]
                        continue
                    out, lse = fa._flash_forward(q, k, v, return_lse=True, **kw)
                    grads = fa._flash_backward(
                        q, k, v, out, lse, g, scale=None, **kw)
                    c = sum(x[0, 0, 0, 0] for x in grads)
                return c.astype(jnp.float32)

            return lax.fori_loop(
                0, args.rounds, body, jnp.zeros((), jnp.float32))

        row = {"cell": cell, "seq": seq, "dtype": dtype.name, "block_q": bq,
               "block_k": bk, "device": dev.device_kind,
               "platform": dev.platform}
        try:
            jax.block_until_ready(loop(sets))  # compile, and once warm
            with tempfile.TemporaryDirectory() as tmp:
                with jax.profiler.trace(tmp):
                    jax.block_until_ready(loop(sets))
                events = tracered.load_events(tracered.find_xplane(tmp))
        except Exception as e:  # noqa: BLE001 — blocks past VMEM are data
            row["error"] = f"{type(e).__name__}: {e}"[:200]
            events = {"devices": {}}
        planes = events["devices"]  # none off the chip
        ops = planes[min(planes)] if planes else []
        pairs = seq * (seq + 1) // 2
        for kernel, matmuls in KERNEL_MATMULS.items():
            secs, n = tracered.kernel_seconds(
                ops, lambda name, _meta: tracered.base_name(name) == kernel)
            if n:
                us = 1e6 * secs / n
                floor = 1e6 * matmuls * b * hq * (d + dv) * pairs / peak
                row[kernel] = {
                    "us": round(us, 1), "calls": n,
                    "matmuls_at_peak_us": round(floor, 1),
                    "pct_of_peak": round(100.0 * floor / us, 1)}
        results.append(row)
        print(json.dumps(row), flush=True)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="2048,4096,8192,16384")
    ap.add_argument(
        "--bias", action="store_true",
        help="measure the biased (T5) fwd+bwd kernel-vs-chunked A/B instead",
    )
    ap.add_argument(
        "--cells", nargs="*", choices=list(CELLS), default=None,
        help="each kernel alone at these cells' shapes (none named: all)",
    )
    ap.add_argument("--dtype", default="bfloat16", help="--cells: the rows'")
    ap.add_argument("--block-q", type=int, nargs="+", default=None,
                    help="--cells: tile bounds (default: the module's own)")
    ap.add_argument("--block-k", type=int, nargs="+", default=None)
    ap.add_argument("--sets", type=int, default=4,
                    help="--cells: operand sets that take turns in the loop")
    ap.add_argument("--rounds", type=int, default=3,
                    help="--cells: rounds of the sets in the traced loop")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.cells is not None:
        return cell_rows(args)
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
