#!/usr/bin/env python
"""What one grouped expert layer costs on the chip, alone: the kernel and
everything beside it (the layout's bookkeeping, the rows in and out).

One ``MoE(dispatch_mode="grouped")`` layer under ``jit`` at a serving
cell's shapes, in the manner of ``scripts/bench_gated_delta.py``:
``--layers`` layers with weights of their own take turns inside ONE
jitted loop (``x = x + layer(x)``: a call's result is the next call's
input, so none can be hoisted or merged, and no expert's weights stay in
VMEM between calls), timed to ``block_until_ready``, best of
``--rounds``: microseconds a layer, and no dispatch.  Then one profiler
trace of the same loop: the device seconds of ``tdx_grouped_matmul`` and
of every other operation (``benchmarks/harness/tracered.py``'s own
times), a layer, and the largest of the others by name.

Two configurations:

- ``share``: Qwen3-Next's (hidden 2048, 512 experts of 512, top 10,
  softmax, ``held=(0, 128)``, no shared expert) at 128 / 512 / 1,024 /
  2,048 / 3,072 tokens (a decode step of ``qwen3-next-80b.batch128-4k``
  and its four prefill buckets);
- ``whole``: kanana-2-30b's (hidden 2048, 128 experts of 768, top 6,
  sigmoid scores with the selection bias, every expert held) at 32 /
  1,024 / 2,048 / 4,096 / 6,144 (``kanana-2-30b.batch32-8k``).

(``--configs tiny`` is the CPU rehearsal's: the interpreter runs the
kernel, and the lines carry no device time.)

``--parent DIR`` (a ``git archive`` of the parent commit, unpacked in a
directory ``.gitignore`` lists) runs DIR's ``torchdistx_tpu`` and this
checkout's, each in a child process of its own (one process a chip: this
one stays off jax), and prints the two columns side by side.
``--row-tile`` overrides the layout's row tile (one line a value): the
sweep that set ``ops/grouped_matmul.row_tile``'s rule.

A number from a CPU run is the Pallas interpreter's and never a chip
time; each line names the device.

    python scripts/bench_expert_layer.py
    python scripts/bench_expert_layer.py --parent _parent
    python scripts/bench_expert_layer.py --configs share --tokens 2048 --row-tile 32 64 128
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

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: name -> (MoE keyword arguments, token counts)
CONFIGS = {
    "share": (
        dict(dim=2048, ffn_dim=512, n_experts=512, top_k=10, held=(0, 128)),
        (128, 512, 1024, 2048, 3072),
    ),
    "whole": (
        dict(dim=2048, ffn_dim=768, n_experts=128, top_k=6, scoring="sigmoid",
             selection_bias=True, routed_scale=2.448),
        (32, 1024, 2048, 4096, 6144),
    ),
    # the CPU rehearsal's (the interpreter runs the kernel)
    "tiny": (
        dict(dim=128, ffn_dim=128, n_experts=16, top_k=4, held=(0, 4)),
        (8, 64),
    ),
}
KERNEL = "tdx_grouped_matmul"


def child(args) -> int:
    """Every line of one tree: a JSON object a (configuration, tokens,
    row tile)."""
    tree = os.path.abspath(args.tree)
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    sys.path.insert(0, tree)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import tracered
    import torchdistx_tpu as tdx

    if not os.path.abspath(tdx.__file__).startswith(tree + os.sep):
        raise SystemExit(f"torchdistx_tpu came from {tdx.__file__}, not {tree}")
    from torchdistx_tpu.nn import functional_call
    from torchdistx_tpu.nn.moe import MoE
    from torchdistx_tpu.ops import grouped_matmul as gm

    dev = jax.devices()[0]
    where = {"device": dev.device_kind, "platform": dev.platform,
             "tree": args.tree}
    rule = gm.row_tile
    for name in args.configs:
        kwargs, tokens = CONFIGS[name]
        tdx.manual_seed(args.seed)
        layers = [
            MoE(dtype=jnp.bfloat16, dispatch_mode="grouped", use_kernel=True,
                weight_init=lambda s, d: tdx.nn.init.normal(
                    s, std=0.02, dtype=d),
                **kwargs)
            for _ in range(args.layers)
        ]
        # the weights as arguments: closed over, gigabytes of them would
        # be constants of the program
        params = [dict(m.named_parameters()) for m in layers]
        rs = np.random.RandomState(args.seed)
        for n in args.tokens or tokens:
            x = jnp.asarray(
                rs.standard_normal((1, n, kwargs["dim"])), jnp.bfloat16)
            for tm in args.row_tile or [None]:
                gm.row_tile = rule if tm is None else (lambda *_a, tm=tm: tm)

                @jax.jit
                def loop(x, params):
                    def body(_, x):
                        for m, p in zip(layers, params):
                            x = x + functional_call(m, p, (x,))
                        return x

                    return jax.lax.fori_loop(0, args.calls, body, x)

                calls = args.calls * args.layers
                row = {"config": name, "tokens": n, "row_tile": tm,
                       "layers": args.layers}
                try:
                    row["us_a_layer"] = round(
                        best_of(jax, loop, (x, params), calls, args.rounds), 2)
                    row.update(traced(jax, tracered, loop, (x, params), calls))
                except Exception as e:  # e.g. a tile Mosaic refuses
                    row["error"] = f"{type(e).__name__}: {e}"[:200]
                print(json.dumps({**row, **where}), flush=True)
        del layers, params
    return 0


def best_of(jax, loop, operands, calls: int, rounds: int) -> float:
    """Best of ``rounds``: microseconds a call of a chained loop
    (``bench_selective_scan.best_of``, not imported: that module puts
    THIS checkout first on ``sys.path``, and the parent's column would
    time the change)."""
    jax.block_until_ready(loop(*operands))  # compile, then once warm
    jax.block_until_ready(loop(*operands))
    best = math.inf
    for _ in range(rounds):
        t0 = time.perf_counter()
        jax.block_until_ready(loop(*operands))
        best = min(best, time.perf_counter() - t0)
    return best / calls * 1e6


def traced(jax, tracered, loop, operands, calls) -> dict:
    """Device microseconds a layer from one trace of the loop: the
    kernel's, everything else's, and the five largest of the others."""
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            jax.block_until_ready(loop(*operands))
        events = tracered.load_events(tracered.find_xplane(d))
    if not events["devices"]:
        return {}
    ops = events["devices"][sorted(events["devices"])[0]]
    own = tracered.self_times(ops)
    kernel = sum(t for n, t in own.items() if KERNEL in n)
    others = sorted(
        ((n, t) for n, t in own.items() if KERNEL not in n),
        key=lambda kv: -kv[1])
    per = 1e-3 / calls  # ns in all -> us a layer
    return {
        "kernel_us": round(kernel * per, 2),
        "other_us": round(sum(t for _, t in others) * per, 2),
        "largest_others_us": [[n, round(t * per, 2)] for n, t in others[:5]],
    }


def table(rows_by_tree: dict) -> str:
    """Parent and change side by side, a line a (configuration, tokens,
    row tile)."""
    trees = list(rows_by_tree)
    keys = []
    for rows in rows_by_tree.values():
        for r in rows:
            key = (r["config"], r["tokens"], r["row_tile"])
            if key not in keys:
                keys.append(key)
    head = "| layer | tokens | tile | " + " | ".join(
        f"{t}: us a layer (kernel + others)" for t in trees) + " |"
    lines = [head, "| --- " * (3 + len(trees)) + "|"]
    for key in keys:
        cells = []
        for t in trees:
            r = next((r for r in rows_by_tree[t] if (
                r["config"], r["tokens"], r["row_tile"]) == key), None)
            if r is None or "error" in r:
                cells.append("—" if r is None else r["error"][:40])
            else:
                cells.append("{} ({} + {})".format(
                    r["us_a_layer"], r.get("kernel_us"), r.get("other_us")))
        lines.append("| {} | {} | {} | ".format(
            key[0], key[1], key[2] or "rule") + " | ".join(cells) + " |")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", nargs="+", default=["share", "whole"],
                    choices=list(CONFIGS))
    ap.add_argument("--tokens", type=int, nargs="*", default=[],
                    help="instead of the configuration's own counts")
    ap.add_argument("--row-tile", type=int, nargs="*", default=[],
                    help="override ops.grouped_matmul.row_tile (the sweep)")
    ap.add_argument("--layers", type=int, default=4,
                    help="expert layers that take turns in the loop")
    ap.add_argument("--calls", type=int, default=4,
                    help="rounds of the layers a timed loop")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--parent", default=None,
                    help="a checkout of the parent commit to run beside this one")
    ap.add_argument("--tree", default=REPO, help=argparse.SUPPRESS)
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        return child(args)

    # this process stays off jax: a chip belongs to one process at a time
    argv = list(sys.argv[1:] if argv is None else argv)
    rows_by_tree = {}
    trees = ([("parent", args.parent)] if args.parent else []) + [
        ("change" if args.parent else "this tree", REPO)]
    for label, tree in trees:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child", "--tree",
             tree, *argv],
            stdout=subprocess.PIPE, text=True, check=False)
        rows = [json.loads(line) for line in out.stdout.splitlines()
                if line.startswith("{")]
        for r in rows:
            print(json.dumps(r), flush=True)
        if out.returncode:
            print(f"{label}: exit {out.returncode}", file=sys.stderr)
            return out.returncode
        rows_by_tree[label] = rows
    print(table(rows_by_tree), flush=True)
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "bench_expert_layer.json"), "w") as f:
        json.dump(rows_by_tree, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
