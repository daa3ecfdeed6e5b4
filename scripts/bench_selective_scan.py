#!/usr/bin/env python
"""What one ``tdx_selective_scan`` and one ``tdx_selective_state_update``
call cost on the chip, alone.

The serve engine's programs call them once a Mamba layer: the scan a
prefill (one request's bucket of rows), the update a decode step (one
token for every slot).  This script times each call by itself at a
serving cell's shapes (the defaults are ``jamba2-3b.batch256``'s:
``d_inner`` 5120, ``d_state`` 16, buckets 256 / 512 / 1024, 256 slots,
bf16 rows and a float32 state), in the manner of
``scripts/bench_decode_attention.py``: ``--calls`` calls chained inside
ONE jitted loop (each call's state is the next call's, so none can be
hoisted or merged; the update goes round ``--layers`` states so that
none stays in VMEM between its calls), timed to ``block_until_ready``, best of
``--rounds``: microseconds a call hold the kernel and its wrapper's few
small reshapes, and no dispatch.  Beside each, the least the chip
allows for what the call needs (``benchmarks/families/jamba_counts.py``:
the bytes over 819 GB/s or the operations over the peak, whichever is
larger) and the call's grid.  A scan is timed with every row real and
with a prompt of ``bucket * 5 / 8`` rows (what is past ``true_len`` is
masked or skipped).  ``--block-c`` / ``--block-t`` / ``--block-s`` pass
the kernels' block bounds through, one line of output for each value.  A
number from a CPU run is the Pallas interpreter's and never a chip time;
each line names the device.

    python scripts/bench_selective_scan.py
    python scripts/bench_selective_scan.py --block-c 512 1024 --block-t 64 128
"""

from __future__ import annotations

import argparse
import itertools
import json
import math
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import jax
import jax.numpy as jnp

from bench_decode_attention import grid_of  # noqa: E402  (beside this script)
from families import jamba_counts  # noqa: E402
from harness import counts, peaks  # noqa: E402
from torchdistx_tpu.ops import selective_scan as ss  # noqa: E402


def best_of(loop, args, calls: int, rounds: int) -> float:
    """Best of ``rounds``: microseconds a call of a chained loop."""
    jax.block_until_ready(loop(*args))  # compile, then once warm
    jax.block_until_ready(loop(*args))
    best = math.inf
    for _ in range(rounds):
        t0 = time.perf_counter()
        jax.block_until_ready(loop(*args))
        best = min(best, time.perf_counter() - t0)
    return best / calls * 1e6


def operands(rs, lead, c, n):
    """Rows ``lead`` (a tuple) of x, dt, b, c, z and the coefficients."""
    normal = lambda *s: rs.standard_normal(s).astype(np.float32)  # noqa: E731
    x, z = (jnp.asarray(normal(*lead, c), jnp.bfloat16) for _ in range(2))
    dt = jnp.asarray(np.log1p(np.exp(normal(*lead, c) - 2.0)))
    a = -jnp.exp(jnp.asarray(0.02 * normal(n, c)))
    bm, cm = (jnp.asarray(normal(*lead, n)) for _ in range(2))
    return x, dt, a, bm, cm, jnp.ones((c,), jnp.float32), z


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--d-inner", type=int, default=5120)
    ap.add_argument("--d-state", type=int, default=16)
    ap.add_argument("--buckets", type=int, nargs="+", default=[256, 512, 1024])
    ap.add_argument("--slots", type=int, default=256)
    ap.add_argument("--block-c", type=int, nargs="+", default=[1024])
    ap.add_argument("--block-t", type=int, nargs="+", default=[128])
    ap.add_argument("--block-s", type=int, nargs="+", default=[16])
    ap.add_argument("--update-block-c", type=int, nargs="+", default=[1280])
    ap.add_argument("--layers", type=int, default=8,
                    help="states that take turns in the update's loop")
    ap.add_argument("--calls", type=int, default=104)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    c, n = args.d_inner, args.d_state
    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    peak = peaks.peaks(dev.device_kind) if on_chip else None
    cfg = {"hidden_size": c // 2, "mamba_expand": 2, "mamba_d_state": n,
           "mamba_d_conv": 4}
    rs = np.random.RandomState(args.seed)
    where = {"device": dev.device_kind, "platform": dev.platform}

    def floor_us(need):
        return None if peak is None else round(
            1e6 * counts.roofline_seconds(*need, peak)[0], 2)

    for bucket in args.buckets:
        ops = operands(rs, (1, bucket), c, n)
        h0 = jnp.zeros((1, n, c), jnp.float32)
        for bc, bt in itertools.product(args.block_c, args.block_t):
            kw = dict(use_kernel=True, block_c=bc, block_t=bt)
            row = {"kernel": ss.SCAN_KERNEL_NAME, "bucket": bucket,
                   "block_c": bc, "block_t": bt,
                   "grid": grid_of(lambda h: ss.selective_scan(
                       *ops, h, bucket, **kw), h0)}
            for name, true_len in (("all_real", bucket),
                                   ("five_eighths", bucket * 5 // 8)):
                @jax.jit
                def loop(h, true_len=true_len):
                    def body(_, h):  # a call's state is the next call's
                        return ss.selective_scan(*ops, h, true_len, **kw)[1]

                    return jax.lax.fori_loop(0, args.calls, body, h)

                try:
                    row[name + "_us"] = round(
                        best_of(loop, (h0,), args.calls, args.rounds), 2)
                except Exception as e:  # e.g. blocks past the kernel's VMEM
                    row[name + "_error"] = f"{type(e).__name__}: {e}"[:160]
                row[name + "_floor_us"] = floor_us(
                    jamba_counts.selective_scan_need(cfg, true_len))
            print(json.dumps({**row, **where}), flush=True)

    # the decode step: ``--layers`` states take turns, as a stack's
    # layers do.  ONE state carried through the loop (84 MB at the
    # defaults) is kept in the chip's 128 MiB of VMEM from call to call
    # and the kernel then reads no HBM at all (94-100 µs a call against
    # 218 µs of bytes, my chip run, PR 34: not what a model's step does)
    ops = operands(rs, (args.slots,), c, n)
    states = tuple(
        jnp.asarray(rs.standard_normal((args.slots, n, c)), jnp.float32)
        for _ in range(args.layers))
    rounds = max(1, args.calls // args.layers)
    for bs, bc in itertools.product(args.block_s, args.update_block_c):
        kw = dict(use_kernel=True, block_s=bs, block_c=bc)

        @jax.jit
        def loop(states):
            def body(_, states):
                return tuple(ss.selective_state_update(h, *ops, **kw)[1]
                             for h in states)

            return jax.lax.fori_loop(0, rounds, body, states)

        row = {"kernel": ss.UPDATE_KERNEL_NAME, "slots": args.slots,
               "layers": args.layers, "block_s": bs, "block_c": bc,
               "grid": grid_of(lambda h: ss.selective_state_update(
                   h, *ops, **kw), states[0]),
               "us": round(best_of(loop, (states,), rounds * args.layers,
                                   args.rounds), 2),
               "floor_us": floor_us(
                   jamba_counts.state_update_need(cfg, args.slots))}
        print(json.dumps({**row, **where}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
