"""The readings a serving cell's limit is set from, in one process on the
chip at the cell's own load: for every seed a short window (long enough
to finish the mix's longest requests), then the float32 reference over
the same sample a run compares -- the lower readings; for the first
``--controls`` seeds also the control: at each position of the same
prompts and tokens, the gap of the token that the int8 reference puts
first -- the upper readings.

    python benchmarks/proof/serve_readings.py <cell> --seeds 12 --controls 3 --seconds 12 --out chiprun_out/x.jsonl
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
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2000003)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--control", default="int8", help="the control's precision")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    import jax

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
            family_ref = driver.family.reference  # the architecture's own
            if args.control not in family_ref.PRECISIONS:
                raise SystemExit(f"the family has no control {args.control!r}")
            ref = family_ref.ServeReference(driver.arch, seed, "f32")
            control = (family_ref.ServeReference(driver.arch, seed, args.control)
                       if i < args.controls else None)
            gaps, control_gaps = reference.served_gaps(ref, seqs, lens, control)
            row = {"seed": seed, "program_s": round(t1 - t0, 2),
                   "reference_s": round(time.time() - t1, 2),
                   "finished": len(driver.finished), "failed": out["failed"],
                   "weights_differ": driver.weights_differ,
                   "served_tokens": sum(gaps["tokens"]),
                   "logit_gap": max(gaps["max"]),
                   "logit_gap_mean": sum(gaps["sum"]) / sum(gaps["tokens"]),
                   "gaps": [round(g, 5) for g in gaps["max"]]}
            if control_gaps:
                row["control_logit_gap"] = max(control_gaps["max"])
                row["control_logit_gap_mean"] = (
                    sum(control_gaps["sum"]) / sum(control_gaps["tokens"]))
                row["control_gaps"] = [round(g, 5) for g in control_gaps["max"]]
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
