#!/usr/bin/env python
"""What one ``tdx_gated_delta_chunk`` and one ``tdx_gated_delta_update``
call cost on the chip, alone -- and one expert layer that holds a share
of its experts.

The serve engine's programs call the kernels once a Gated-DeltaNet
layer: the chunked rule a prefill (one request's bucket of rows), the
update a decode step (one token for every slot).  This script times each
call by itself at a serving cell's shapes (the defaults are
``qwen3-next-80b.batch128-4k``'s: 16 key and 32 value heads of 128,
buckets 512 / 1024 / 2048 / 3072, 128 slots, bf16 ``v`` and a float32
state), in the manner of ``scripts/bench_selective_scan.py``: ``--calls``
calls chained inside ONE jitted loop (each call's state is the next
call's, so none can be hoisted or merged; the update goes round
``--layers`` states so that none stays in VMEM between its calls),
timed to ``block_until_ready``, best of ``--rounds``: microseconds a
call hold the kernel and its wrapper's few small transposes, and no
dispatch.  Beside each, the least the chip allows for what the call
needs (``benchmarks/families/qwen3_next_counts.py``: the bytes over 819
GB/s or the operations over the peak, whichever is larger) and the
call's grid.  A prefill is timed with every row real and with a prompt
of ``bucket * 5 / 8`` rows (what is past ``true_len`` is masked or
skipped).  ``--block-t`` / ``--block-h`` / ``--block-s`` pass the
kernels' block bounds through, one line of output for each value.

``--share-block-n`` times the expert layer of the same cell (2048 ->
512, 128 of 512 experts held, top 10) over a decode step's 128 tokens
and a prefill's ``--share-tokens``, once for each column block of its
fused gate-and-up matmul (``MoE.up_block_n``): layers taking turns, so
that no expert's weights stay in VMEM.

A number from a CPU run is the Pallas interpreter's and never a chip
time; each line names the device.

    python scripts/bench_gated_delta.py
    python scripts/bench_gated_delta.py --block-t 64 128 --block-h 8 16 32 --share-block-n 256 512
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import jax
import jax.numpy as jnp

from bench_decode_attention import grid_of  # noqa: E402  (beside this script)
from bench_selective_scan import best_of  # noqa: E402
from families import qwen3_next_counts  # noqa: E402
from harness import counts, peaks  # noqa: E402
from torchdistx_tpu.ops import gated_delta as gd  # noqa: E402


def operands(rs, lead, hk, hv, dk, dv):
    """Rows ``lead`` (a tuple) of q, k, v, g, beta as a mixer makes them:
    ``k`` of unit length, ``q`` of length ``1 / sqrt(dk)``."""
    normal = lambda *s: rs.standard_normal(s).astype(np.float32)  # noqa: E731

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = jnp.asarray(unit(normal(*lead, hk, dk)) / np.sqrt(dk))
    k = jnp.asarray(unit(normal(*lead, hk, dk)))
    v = jnp.asarray(normal(*lead, hv, dv), jnp.bfloat16)
    g = jnp.asarray(-np.log1p(np.exp(normal(*lead, hv))))
    beta = jnp.asarray(1.0 / (1.0 + np.exp(-normal(*lead, hv))))
    return q, k, v, g, beta


def share_layers(args, block_n):
    """``--layers`` expert layers of the cell, each with weights of its
    own, and the jitted loop that runs them one after another."""
    import torchdistx_tpu as tdx
    from torchdistx_tpu.nn import functional_call
    from torchdistx_tpu.nn.moe import MoE

    dim, ffn, width, held, top_k = args.share_dims
    tdx.manual_seed(args.seed)
    layers = [
        MoE(dim, ffn, width, top_k=top_k, dtype=jnp.bfloat16,
            dispatch_mode="grouped", shared_ffn_dim=ffn, shared_gate=True,
            held=(0, held),
            weight_init=lambda s, d: tdx.nn.init.normal(s, std=0.02, dtype=d),
            use_kernel=True)
        for _ in range(args.layers)
    ]
    for m in layers:
        m.up_block_n = block_n
    # the weights as arguments: closed over, 4.8 GB of them would be
    # constants of the program
    params = [dict(m.named_parameters()) for m in layers]

    @jax.jit
    def loop(x, params):
        def body(_, x):
            for m, p in zip(layers, params):
                x = x + functional_call(m, p, (x,))
            return x

        return jax.lax.fori_loop(0, args.share_rounds, body, x)

    return loop, params


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--key-heads", type=int, default=16)
    ap.add_argument("--value-heads", type=int, default=32)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--buckets", type=int, nargs="+",
                    default=[512, 1024, 2048, 3072])
    ap.add_argument("--slots", type=int, default=128)
    ap.add_argument("--block-t", type=int, nargs="+", default=[64])
    ap.add_argument("--block-s", type=int, nargs="+", default=[1])
    ap.add_argument("--block-h", type=int, nargs="+", default=[16])
    ap.add_argument("--layers", type=int, default=6,
                    help="states (or expert layers) that take turns in a loop")
    ap.add_argument("--calls", type=int, default=48)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--share-block-n", type=int, nargs="*", default=[])
    ap.add_argument("--share-tokens", type=int, nargs="+", default=[128, 2048])
    ap.add_argument("--share-rounds", type=int, default=4)
    ap.add_argument("--share-dims", type=int, nargs=5,
                    default=[2048, 512, 512, 128, 10],
                    help="hidden, expert width, router width, held, top k")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    hk, hv, d = args.key_heads, args.value_heads, args.head_dim
    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    peak = peaks.peaks(dev.device_kind) if on_chip else None
    cfg = {"linear_num_key_heads": hk, "linear_num_value_heads": hv,
           "linear_key_head_dim": d, "linear_value_head_dim": d,
           "linear_conv_kernel_dim": 4}
    rs = np.random.RandomState(args.seed)
    where = {"device": dev.device_kind, "platform": dev.platform}

    def floor_us(need):
        return None if peak is None else round(
            1e6 * counts.roofline_seconds(*need, peak)[0], 2)

    for bucket in args.buckets:
        ops = operands(rs, (1, bucket), hk, hv, d, d)
        s0 = jnp.zeros((1, hv, d, d), jnp.float32)
        for bt in args.block_t:
            kw = dict(use_kernel=True, block_t=bt)
            row = {"kernel": gd.CHUNK_KERNEL_NAME, "bucket": bucket,
                   "block_t": bt,
                   "grid": grid_of(lambda s: gd.gated_delta_chunk(
                       *ops, s, bucket, **kw), s0)}
            for name, true_len in (("all_real", bucket),
                                   ("five_eighths", bucket * 5 // 8)):
                @jax.jit
                def loop(s, true_len=true_len):
                    def body(_, s):  # a call's state is the next call's
                        return gd.gated_delta_chunk(*ops, s, true_len, **kw)[1]

                    return jax.lax.fori_loop(0, args.calls, body, s)

                try:
                    row[name + "_us"] = round(
                        best_of(loop, (s0,), args.calls, args.rounds), 2)
                except Exception as e:  # e.g. blocks past the kernel's VMEM
                    row[name + "_error"] = f"{type(e).__name__}: {e}"[:160]
                row[name + "_floor_us"] = floor_us(
                    qwen3_next_counts.gdn_chunk_need(cfg, true_len))
            print(json.dumps({**row, **where}), flush=True)

    # the decode step: ``--layers`` states take turns, as a stack's
    # layers do, so that none stays in VMEM from call to call
    ops = operands(rs, (args.slots,), hk, hv, d, d)
    states = tuple(
        jnp.asarray(0.1 * rs.standard_normal((args.slots, hv, d, d)),
                    jnp.float32)
        for _ in range(args.layers))
    rounds = max(1, args.calls // args.layers)
    for bs, bh in itertools.product(args.block_s, args.block_h):
        kw = dict(use_kernel=True, block_s=bs, block_h=bh)

        @jax.jit
        def loop(states):
            def body(_, states):
                return tuple(gd.gated_delta_update(s, *ops, **kw)[1]
                             for s in states)

            return jax.lax.fori_loop(0, rounds, body, states)

        row = {"kernel": gd.UPDATE_KERNEL_NAME, "slots": args.slots,
               "layers": args.layers, "block_s": bs, "block_h": bh,
               "grid": grid_of(lambda s: gd.gated_delta_update(
                   s, *ops, **kw), states[0]),
               "floor_us": floor_us(
                   qwen3_next_counts.gdn_update_need(cfg, args.slots))}
        try:
            row["us"] = round(best_of(
                loop, (states,), rounds * args.layers, args.rounds), 2)
        except Exception as e:
            row["error"] = f"{type(e).__name__}: {e}"[:160]
        print(json.dumps({**row, **where}), flush=True)
    del states

    for block_n, tokens in itertools.product(
            args.share_block_n, args.share_tokens):
        loop, params = share_layers(args, block_n)
        x = jnp.asarray(
            rs.standard_normal((1, tokens, args.share_dims[0])), jnp.bfloat16)
        row = {"layer": "MoE hidden {}, experts of {}, {} wide, {} held, "
                        "top {}".format(*args.share_dims),
               "tokens": tokens, "up_block_n": block_n, "layers": args.layers}
        try:
            row["us_a_layer"] = round(best_of(
                loop, (x, params), args.share_rounds * args.layers,
                args.rounds), 2)
        except Exception as e:
            row["error"] = f"{type(e).__name__}: {e}"[:160]
        print(json.dumps({**row, **where}), flush=True)
        del loop, params  # 4.8 GB of stacks: gone before the next are made
    return 0


if __name__ == "__main__":
    sys.exit(main())
