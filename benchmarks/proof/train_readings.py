"""The readings a training cell's limits are set from, in one process on
the chip at the cell's own size:

- for every seed: the program (set-up's first steps, as a run makes them)
  against the float32 reference -- the lower readings;
- for the first ``--controls`` seeds also the control (the reference in
  int8, put in the program's place) and the planted fault "half of the
  batch left out" (the reference on half the rows), each against the
  float32 reference -- the upper readings.

    python benchmarks/proof/train_readings.py <cell> --seeds 12 --controls 3 --out chiprun_out/x.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402
from harness import check, loader  # noqa: E402


def numbers(driver, losses, grad, change, sample, ref):
    from harness import reference

    ref_loss, ref_grad, ref_change, ref_sample = ref
    out = {f"loss_gap_step{k + 1}": check.rel_gap(losses[k], ref_loss[k])
           for k in range(len(ref_loss))}
    out["grad_norm_gap"], out["grad_leaf"] = check.worst_leaf_gap(grad, ref_grad)
    skip = check.small_gradient_leaves(ref_grad)
    out["change_norm_gap"], out["change_leaf"] = check.worst_leaf_gap(
        change, ref_change, skip)
    med_g = statistics.median(ref_grad.values())
    med_c = statistics.median(ref_change.values())
    per_leaf = sorted(
        ((abs(change[k] - ref_change[k]) / max(ref_change[k], med_c), k)
         for k in ref_change if k not in skip), reverse=True)
    out["change_top"] = [(k, round(g, 5)) for g, k in per_leaf[:6]]
    out["change_median_leaf_gap"] = statistics.median(g for g, _ in per_leaf)
    per_leaf_g = sorted(
        ((abs(grad[k] - ref_grad[k]) / max(ref_grad[k], med_g), k)
         for k in ref_grad), reverse=True)
    out["grad_top"] = [(k, round(g, 5)) for g, k in per_leaf_g[:6]]
    out["skipped"] = sorted(skip)
    out["grad_diff_by_leaf"] = {n: float(reference.diff_rel(sample[n], ref_sample[n]))
                                for n in ref_sample}
    out["grad_diff"] = max(out["grad_diff_by_leaf"].values())
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=1000003)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", required=True)
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
            seed = args.first_seed + 7919 * i * i + (2**31 if i % 2 else 0)
            t0 = time.time()
            ctx, driver = run.make_driver(cell, seed, 0.0)
            driver.setup()
            prog = (driver.prog_loss, driver.prog_grad, driver.prog_change,
                    driver.prog_grad_sample)
            driver.free()
            t1 = time.time()
            ref = driver.reference_readings()
            t2 = time.time()
            row = {"seed": seed, "kind": "program",
                   "weights_differ": driver.weights_differ,
                   "setup_split_s": {k: round(v, 2) for k, v in ctx.spans.items()},
                   "setup_s": round(t1 - t0, 2), "reference_s": round(t2 - t1, 2),
                   # the plan's first and last leaf and the sampled ones
                   "ref_grad_norms": {k: ref[1][k] for k in dict.fromkeys(
                       (driver.plan[0][0], driver.plan[-1][0],
                        *driver.family.reference.sample_leaves(driver.arch)))},
                   **numbers(driver, *prog, ref)}
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
            if i < args.controls:
                for kind, kw in (("control_int8", {"precision": "int8"}),
                                 ("fault_half_batch",
                                  {"rows": slice(0, driver.batch // 2)})):
                    got = driver.reference_readings(**kw)
                    row = {"seed": seed, "kind": kind,
                           **numbers(driver, *got, ref)}
                    print(json.dumps(row), flush=True)
                    f.write(json.dumps(row) + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
