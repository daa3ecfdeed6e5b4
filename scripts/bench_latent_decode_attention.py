#!/usr/bin/env python
"""What one ``tdx_latent_decode_attention`` call costs on the chip, alone.

The DeepSeek-V3 family's decode program calls the kernel once a layer
and step over the latent slab as it is stored, ``(slots, max_len, W)``.
This script times that call by itself at ``kanana-2-30b.batch32-8k``'s
shapes (32 slots of 8,192 rows, a 512 + 64-lane row stored on 640, 32
heads, ``value_width`` 512, bf16) at three sets of per-slot depths:

(a) ``cell``: as a closed loop at full occupancy leaves its slots — a
    request of ``benchmarks/traffic/batch32-8k.json``'s 8 x 8 grid of
    (prompt, output) sizes is met in a slot as often as its output is
    long, somewhere along that output; ``--draws`` sets of depths take
    turns call by call;
(b) ``zero``: every slot at depth 0 (one visible row a slot);
(c) ``full``: every slot at ``max_len - 1``.

``--caches`` slabs take turns inside ONE jitted loop of ``--calls``
calls (each call's output is the next call's query, so none can be
hoisted or merged, and no slab stays in fast memory from one call to
the next), timed to ``block_until_ready``, best of ``--rounds``:
microseconds a call hold the kernel, the small work of its wrapper, and
no dispatch.  One profiler trace of the ``cell`` loop gives the kernel's
own device microseconds a call.  Beside them: the call's grid steps and
the share of them that read nothing (``1 - visible blocks / steps``),
and the share of the need the call reaches (every visible row's 512 +
64 lanes read once over 819 GB/s, as
``benchmarks/metrics/deepseek_v3_readers.py`` counts it; the stored row
is 640 lanes, so a call that reads exactly the visible rows tops out
near 90 %).

``--parent DIR`` (a ``git archive`` of the parent commit, unpacked in a
directory ``.gitignore`` lists) runs DIR's ``torchdistx_tpu`` and this
checkout's, each in a child process of its own (one process a chip: this
one stays off jax), on the same depths, and prints a table of the two.
``--block-k`` passes the kernel's block size through, one line a value.
A number from a CPU run is the Pallas interpreter's and never a chip
time; each line names the device (``--slots 2 --max-len 256 --calls 2``
is a CPU rehearsal's size).

    python scripts/bench_latent_decode_attention.py --parent _parent
    python scripts/bench_latent_decode_attention.py --block-k 256 512 1024
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from harness.traffic import length_grid  # noqa: E402

HBM_BYTES_PER_S = 819e9  # TPU v5e, benchmarks/harness/peaks.py
KERNEL = "tdx_latent_decode_attention"
ROPE = 64  # the rope key's lanes beside the 512 of the latent


def cell_depths(traffic: str, slots: int, draws: int, max_len: int, seed: int):
    """``(draws, slots)`` depths of a closed loop at full occupancy over
    the traffic file's grid of sizes (``bench_decode_attention.py``'s
    draw)."""
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


def child(args, depth_sets: dict) -> int:
    """Time ``args.tree``'s kernel; one JSON line a block size."""
    sys.path.insert(0, args.tree)
    import jax
    import jax.numpy as jnp

    from harness import tracered
    from torchdistx_tpu.ops import latent_decode_attention as lda
    from torchdistx_tpu.utils.rng import manual_seed, next_rng_key

    if not lda.__file__.startswith(args.tree):
        raise RuntimeError(f"{lda.__file__} is not {args.tree}'s kernel")
    latent_decode_attention = lda.latent_decode_attention

    b, h, w, r, rows = (
        args.slots, args.heads, args.width, args.value_width, args.max_len)
    dev = jax.devices()[0]
    manual_seed(args.seed)
    q = jax.random.normal(next_rng_key(), (b, h, w), jnp.bfloat16)
    caches = [  # made on the device: 335 MB a slab at the cell's shapes
        jax.random.normal(next_rng_key(), (b, rows, w), jnp.bfloat16)
        for _ in range(args.caches)
    ]
    per_round = max(1, args.calls // args.caches)
    calls = per_round * args.caches

    for block_k in args.block_k:
        def attend(q, cache, depths):
            out = latent_decode_attention(
                q, cache, depths, value_width=r, scale=args.scale,
                block_k=block_k)
            return q.at[..., :r].set(out)

        @jax.jit
        def loop(q, caches, depths):
            def body(i, q):
                for j, cache in enumerate(caches):
                    q = attend(q, cache, depths[(i * len(caches) + j)
                                                % depths.shape[0]])
                return q

            return jax.lax.fori_loop(0, per_round, body, q)

        def best(depths):
            depths = jnp.asarray(depths)
            jax.block_until_ready(loop(q, caches, depths))  # compile, warm
            jax.block_until_ready(loop(q, caches, depths))
            t = math.inf
            for _ in range(args.rounds):
                t0 = time.perf_counter()
                jax.block_until_ready(loop(q, caches, depths))
                t = min(t, time.perf_counter() - t0)
            return t / calls * 1e6

        bk = min(block_k, rows)
        while rows % bk:
            bk //= 2
        row = {"block_k": bk, "slots": b, "max_len": rows, "width": w,
               "calls": calls, "caches": args.caches, "unit": "us/call"}
        for name, depths in depth_sets.items():
            depths = np.asarray(depths, np.int32)
            visible_rows = float((depths + 1).sum(axis=1).mean())
            blocks = float((depths // bk + 1).sum(axis=1).mean())
            need_us = visible_rows * (r + ROPE) * 2 / HBM_BYTES_PER_S * 1e6
            us = best(depths)
            row[name] = {
                "us": round(us, 2),
                "mean_depth": round(float(depths.mean()), 1),
                "visible_blocks": round(blocks, 1),
                "need_us": round(need_us, 2),
                "need_share_pct": round(100.0 * need_us / us, 1),
            }
        with tempfile.TemporaryDirectory() as d:
            cell = jnp.asarray(depth_sets["cell"])
            with jax.profiler.trace(d):
                jax.block_until_ready(loop(q, caches, cell))
            events = tracered.load_events(tracered.find_xplane(d))
        if events["devices"]:
            ops = events["devices"][sorted(events["devices"])[0]]
            own = tracered.self_times(ops)
            row["cell"]["kernel_us"] = round(
                sum(t for n, t in own.items() if KERNEL in n) * 1e-3 / calls, 2)
        row["device"] = {"platform": dev.platform, "device_kind": dev.device_kind}
        print(json.dumps(row), flush=True)
    return 0


def table(rows_by_tree: dict) -> str:
    """A line a (tree, block size): µs a call at each set of depths, the
    kernel's own µs at the cell's, the pruned share of the parent's
    fixed grid and the cell's share of the need."""
    lines = [
        "| tree | block_k | cell us (kernel us) | zero us | full us "
        "| visible blocks / fixed grid | cell need share % |",
        "| --- " * 7 + "|",
    ]
    for tree, rows in rows_by_tree.items():
        for r in rows:
            fixed = r["slots"] * (r["max_len"] // r["block_k"])
            c = r["cell"]
            lines.append(
                f"| {tree} | {r['block_k']} | {c['us']} ({c.get('kernel_us')})"
                f" | {r['zero']['us']} | {r['full']['us']}"
                f" | {c['visible_blocks']} / {fixed}"
                f" ({100 * (1 - c['visible_blocks'] / fixed):.1f} % pruned)"
                f" | {c['need_share_pct']} |")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--width", type=int, default=640,
                    help="stored lanes a row (512 + 64 padded to 640)")
    ap.add_argument("--value-width", type=int, default=512)
    ap.add_argument("--scale", type=float, default=192**-0.5)
    ap.add_argument("--max-len", type=int, default=8192)
    ap.add_argument("--traffic", default="batch32-8k")
    ap.add_argument("--block-k", type=int, nargs="+", default=[512])
    ap.add_argument("--draws", type=int, default=8)
    ap.add_argument("--caches", type=int, default=4)
    ap.add_argument("--calls", type=int, default=240)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--parent", default=None,
                    help="a checkout of the parent commit to run beside this one")
    ap.add_argument("--out", default=None,
                    help="also write every line, by tree, to this JSON file")
    ap.add_argument("--tree", default=REPO, help=argparse.SUPPRESS)
    ap.add_argument("--depths", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.depths:
        with open(args.depths) as f:
            return child(args, json.load(f))

    # this process stays off jax: a chip belongs to one process at a time
    b, rows = args.slots, args.max_len
    depth_sets = {
        "cell": cell_depths(args.traffic, b, args.draws, rows, args.seed),
        "zero": np.zeros((1, b), np.int32),
        "full": np.full((1, b), rows - 1, np.int32),
    }
    argv = list(sys.argv[1:] if argv is None else argv)
    rows_by_tree = {}
    trees = ([("parent", args.parent)] if args.parent else []) + [
        ("change" if args.parent else "this tree", REPO)]
    with tempfile.NamedTemporaryFile("w", suffix=".json") as f:
        json.dump({k: v.tolist() for k, v in depth_sets.items()}, f)
        f.flush()
        for label, tree in trees:
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--tree",
                 os.path.abspath(tree), "--depths", f.name, *argv],
                stdout=subprocess.PIPE, text=True, check=False)
            rows_ = [json.loads(line) for line in out.stdout.splitlines()
                     if line.startswith("{")]
            for r in rows_:
                print(json.dumps({"tree": label, **r}), flush=True)
            if out.returncode:
                print(f"{label}: exit {out.returncode}", file=sys.stderr)
                return out.returncode
            rows_by_tree[label] = rows_
    print(table(rows_by_tree), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows_by_tree, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
