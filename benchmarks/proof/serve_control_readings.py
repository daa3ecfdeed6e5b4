"""``serve_readings.py`` for a cell whose logits do not fit twice: one
row of this cell's comparison is (check_width, vocab) float32 = 3.4 GB,
and ``harness.reference.served_gaps`` holds the reference's row while it
asks the control for its own (both, with the comparison's temporaries,
ran out of the chip's memory in this cell).  Here the control goes
first, row by row, and only the token it puts first at each position is
kept; then the float32 reference, row by row, gives the gap of the
served tokens (the lower readings) and of the control's tokens (the
upper readings).  Same numbers, same order of arithmetic, one row alive.

    python benchmarks/proof/serve_control_readings.py <cell> --seeds 3 --seconds 20 --out chiprun_out/x.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402
from harness import loader, reference  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2000003)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--control", default="int8", help="the control's precision")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    if not args.rehearsal and jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    run.configure_cache()
    cell = loader.load_cell(args.cell, rehearsal=args.rehearsal)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as f:
        for i in range(args.seeds):
            seed = args.first_seed + 104729 * i * i + (2**31 if i % 2 else 0)
            t0 = time.time()
            ctx, driver = run.make_driver(cell, seed, args.seconds)
            driver.setup()
            out = driver.window(args.seconds)
            driver.after_window()
            driver.free()
            t1 = time.time()
            seqs, lens = driver.sample()
            seqs = np.asarray(seqs, np.int32)
            family_ref = driver.family.reference
            control = family_ref.ServeReference(driver.arch, seed, args.control)
            first = [np.asarray(jnp.argmax(logits, axis=-1), np.int32)
                     for _, logits in control.logits_rows(seqs)]
            del control
            ref = family_ref.ServeReference(driver.arch, seed, "f32")
            served, ctl = [], []
            for r, logits in ref.logits_rows(seqs):
                p, total = int(lens[r][0]), int(lens[r][1])
                nxt = np.zeros((seqs.shape[1],), np.int32)
                nxt[:-1] = seqs[r, 1:]  # position j predicts token j + 1
                for dest, tokens in ((served, nxt), (ctl, first[r])):
                    dest.append(np.asarray(
                        reference._gaps_of(logits, jnp.asarray(tokens))[0][p - 1:total - 1]))
                del logits
            tokens = sum(g.size for g in served)
            row = {"seed": seed, "program_s": round(t1 - t0, 2),
                   "reference_s": round(time.time() - t1, 2),
                   "finished": len(driver.finished), "failed": out["failed"],
                   "weights_differ": driver.weights_differ,
                   "served_tokens": tokens}
            for name, got in (("", served), ("control_", ctl)):
                row[name + "logit_gap"] = max(float(g.max()) for g in got)
                row[name + "logit_gap_mean"] = float(
                    sum(g.sum() for g in got) / tokens)
                row[name + "gaps"] = [round(float(g.max()), 5) for g in got]
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
