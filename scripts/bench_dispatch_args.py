#!/usr/bin/env python
"""What it costs the host to hand a compiled program its small arguments.

``ServeEngine``'s decode dispatch takes seven per-slot arrays of
``num_slots`` elements beside the donated cache.  This script times three
ways of getting them to the device, with a jitted function that takes
them beside a donated dummy and the engine's own dtypes (``int32, int32,
float32, int32, int32, int32, bool``):

(i)   seven ``jnp.asarray`` calls, then the call (the engine before PR 31);
(ii)  the seven NumPy arrays handed to the call (the dispatch's own
      argument path makes the transfers);
(iii) one packed ``(7, B)`` int32 array handed to the call and unpacked on
      the device (``generation.pack_slot_state`` / ``_unpack_slot_state``,
      the engine's own since PR 31: the temperatures' bits, the mask as
      0/1).

Each form is run ``--iters`` times at B = 16 and 32 and its microseconds
per iteration printed as one JSON line.  Without ``--sync`` the loop does
not wait for the device between iterations: it is host time to enqueue,
and one ``block_until_ready`` ends each timing.  With ``--sync`` every
iteration ends in a fetch of the small output, as an engine step ends in
the token block's: the device is idle while the next arguments are built.
A number from a CPU run is Python overhead only and never a chip time; the
line names the device.

    python scripts/bench_dispatch_args.py --iters 2000
    python scripts/bench_dispatch_args.py --iters 2000 --sync
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from torchdistx_tpu.generation import (  # noqa: E402
    _unpack_slot_state,
    pack_slot_state,
)

DTYPES = (np.int32, np.int32, np.float32, np.int32, np.int32, np.int32, np.bool_)


def host_arrays(b: int, rng: np.random.Generator) -> list:
    out = []
    for dt in DTYPES:
        if dt is np.bool_:
            out.append(rng.integers(0, 2, b).astype(np.bool_))
        elif dt is np.float32:
            out.append(rng.random(b, dtype=np.float32))
        else:
            out.append(rng.integers(0, 1 << 20, b).astype(np.int32))
    return out


def _use(dummy, tok, pos, temp, seed, ntok, budget, fin):
    # touch every argument so none is pruned from the executable
    acc = tok + pos + seed + ntok + budget + fin.astype(jnp.int32)
    acc = acc + temp.astype(jnp.int32)
    return dummy + acc.sum().astype(dummy.dtype), acc


def _unpack_use(dummy, packed):
    return _use(dummy, *_unpack_slot_state(packed))


_separate = jax.jit(_use, donate_argnums=0)  # tdx-lint: disable=TDX101 -- one device, a dummy carry: no layout to keep
_packed = jax.jit(_unpack_use, donate_argnums=0)  # tdx-lint: disable=TDX101 -- one device, a dummy carry: no layout to keep


def time_form(form: str, b: int, iters: int, sync: bool) -> float:
    rng = np.random.default_rng(b)
    arrs = host_arrays(b, rng)
    dummy = jnp.zeros((8, 128), jnp.float32)

    def once(dummy):
        if form == "asarray_each":
            dummy, acc = _separate(dummy, *[jnp.asarray(a) for a in arrs])
        elif form == "numpy_each":
            dummy, acc = _separate(dummy, *[a.copy() for a in arrs])
        elif form == "packed":
            dummy, acc = _packed(dummy, pack_slot_state(*arrs))
        else:
            raise ValueError(form)
        if sync:
            np.asarray(acc)
        return dummy

    for _ in range(50):  # compile, then warm the argument path
        dummy = once(dummy)
    dummy.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        dummy = once(dummy)
    dt = time.perf_counter() - t0
    dummy.block_until_ready()
    return dt / iters * 1e6


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=2000)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--sync", action="store_true")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    for b in (16, 32):
        row = {"B": b, "iters": args.iters, "sync": args.sync, "unit": "us/iteration"}
        # the forms take turns, so a slow stretch of a shared host does
        # not fall on one of them alone; the best round is reported
        rounds = {f: [] for f in ("asarray_each", "numpy_each", "packed")}
        for _ in range(args.rounds):
            for form in rounds:
                rounds[form].append(time_form(form, b, args.iters, args.sync))
        for form, vals in rounds.items():
            row[form] = round(min(vals), 2)
            row[form + "_rounds"] = [round(v, 2) for v in vals]
        row["device"] = {"platform": dev.platform, "device_kind": dev.device_kind}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
