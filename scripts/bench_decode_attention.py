#!/usr/bin/env python
"""What one ``tdx_decode_attention`` call costs on the chip, alone.

The serve engine's decode program calls the kernel once a layer and step
over the slab as it is stored, ``(slots, max_len, Hkv * D)``.  This
script times that call by itself at a serving cell's shapes (the
defaults are ``mistral-7b.batch16``'s: 16 slots of 2048 rows, 32 / 8
heads of 128, bf16) at three sets of per-slot depths:

(a) ``cell``: as a closed loop at full occupancy leaves its slots — a
    request of ``benchmarks/traffic/batch16.json``'s 8 x 8 grid of
    (prompt, output) sizes is met in a slot as often as its output is
    long, somewhere along that output; ``--draws`` sets of depths take
    turns call by call;
(b) ``zero``: every slot at depth 0 (one visible row: every K block but
    the first is pruned, so what is left is the grid);
(c) ``full``: every slot at ``max_len - 1`` (no block pruned).

``--calls`` calls are chained inside ONE jitted loop (each call's output
is the next call's query, so none can be hoisted or merged) and the loop
is timed to ``block_until_ready``: microseconds a call hold the kernel
and the few small reshapes of its wrapper, and no dispatch.  Beside them
one line gives the least the HBM allows for the rows a call has to read
(every visible K and V row once, over 819 GB/s, as
``benchmarks/harness/counts.py`` counts them) and the call's grid, read
from the traced ``pallas_call``.  ``--block-k`` passes the kernel's
upper bound through, one line of output for each value.  A number from a
CPU run is the Pallas interpreter's and never a chip time; each line
names the device.

    python scripts/bench_decode_attention.py
    python scripts/bench_decode_attention.py --block-k 512 256 128
"""

from __future__ import annotations

import argparse
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

from harness.traffic import length_grid  # noqa: E402
from torchdistx_tpu.ops.decode_attention import decode_attention  # noqa: E402

HBM_BYTES_PER_S = 819e9  # TPU v5e, benchmarks/harness/peaks.py


def cell_depths(traffic: str, slots: int, draws: int, max_len: int, seed: int):
    """``(draws, slots)`` depths of a closed loop at full occupancy over
    the traffic file's grid of sizes."""
    with open(os.path.join(REPO, "benchmarks", "traffic", traffic + ".json")) as f:
        mix = json.load(f)
    sizes = np.array([
        (p, o) for p in length_grid(mix["prompt_len"])
        for o in length_grid(mix["output_len"])
    ])
    rs = np.random.RandomState(seed)
    weight = sizes[:, 1] / sizes[:, 1].sum()
    met = sizes[rs.choice(len(sizes), size=(draws, slots), p=weight)]
    depth = met[..., 0] + (rs.random_sample((draws, slots)) * met[..., 1]).astype(int)
    return np.minimum(depth, max_len - 1).astype(np.int32)


def grid_of(fn, *args):
    """The grid of the one ``pallas_call`` in ``fn``'s jaxpr."""
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    (grid,) = grids
    return grid


def time_calls(q, ck, cv, depths, block_k: int, calls: int, rounds: int):
    """Best of ``rounds``: microseconds a call of a chained loop."""
    depths = jnp.asarray(depths)

    @jax.jit
    def loop(q, ck, cv):
        def body(i, q):
            return decode_attention(
                q, ck, cv, depths[i % depths.shape[0]], block_k=block_k
            )

        return jax.lax.fori_loop(0, calls, body, q)

    loop(q, ck, cv).block_until_ready()  # compile, then once warm
    loop(q, ck, cv).block_until_ready()
    best = math.inf
    for _ in range(rounds):
        t0 = time.perf_counter()
        loop(q, ck, cv).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / calls * 1e6


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--max-len", type=int, default=2048)
    ap.add_argument("--traffic", default="batch16")
    ap.add_argument("--block-k", type=int, nargs="+", default=[512])
    ap.add_argument("--draws", type=int, default=8)
    ap.add_argument("--calls", type=int, default=480)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    b, hq, hkv, d, rows = (
        args.slots, args.heads, args.kv_heads, args.head_dim, args.max_len
    )
    dev = jax.devices()[0]
    rs = np.random.RandomState(args.seed)
    q, ck, cv = (
        jnp.asarray(rs.standard_normal(shape), jnp.bfloat16)
        for shape in ((b, 1, hq, d), (b, rows, hkv * d), (b, rows, hkv * d))
    )
    sets = {
        "cell": cell_depths(args.traffic, b, args.draws, rows, args.seed),
        "zero": np.zeros((1, b), np.int32),
        "full": np.full((1, b), rows - 1, np.int32),
    }
    for block_k in args.block_k:
        grid = grid_of(
            lambda q, ck, cv: decode_attention(
                q, ck, cv, jnp.zeros((b,), jnp.int32), block_k=block_k
            ),
            q, ck, cv,
        )
        row = {
            "slots": b, "heads": hq, "kv_heads": hkv, "head_dim": d,
            "max_len": rows, "block_k_bound": block_k, "grid": list(grid),
            "grid_steps": math.prod(grid), "calls": args.calls,
            "unit": "us/call",
        }
        for name, depths in sets.items():
            visible = float((depths + 1).sum(axis=1).mean())
            row[name] = {
                "us": round(time_calls(
                    q, ck, cv, depths, block_k, args.calls, args.rounds), 2),
                "mean_depth": round(float(depths.mean()), 1),
                "hbm_floor_us": round(
                    2.0 * visible * hkv * d * ck.dtype.itemsize
                    / HBM_BYTES_PER_S * 1e6, 2),
            }
        row["device"] = {"platform": dev.platform, "device_kind": dev.device_kind}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
