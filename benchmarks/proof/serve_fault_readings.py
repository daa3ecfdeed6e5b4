"""``serve_readings.py`` with a planted fault beside the control: the
readings a serving cell's limits are set from, and for the first
``--faults`` seeds what the SAME served tokens read against a reference
that carries a fault the family plants in itself (its serve reference
built with what ``family.reference.FAULTS[name]`` makes of the sample's
prompt lengths): a limit that decides must lie under that reading too, or the comparison
cannot see the fault.  One row of the comparison alive at a time, the
control first (as ``serve_control_readings.py``).

    python benchmarks/proof/serve_fault_readings.py <cell> --fault <name> --seeds 12 --controls 3 --faults 3 --seconds 12 --out chiprun_out/x.jsonl
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


def gaps_under(ref, seqs, lens, token_rows):
    """Per request, the gaps of each of ``token_rows``' (N, T) tokens
    under ``ref``'s best at the served positions: a list per row set."""
    import jax.numpy as jnp
    import numpy as np

    out = [[] for _ in token_rows]
    for r, logits in ref.logits_rows(seqs):
        p, total = int(lens[r][0]), int(lens[r][1])
        for dest, rows in zip(out, token_rows):
            dest.append(np.asarray(reference._gaps_of(
                logits, jnp.asarray(rows[r]))[0][p - 1:total - 1]))
        del logits
    return out


def summary(prefix, got, tokens):
    return {prefix + "logit_gap": max(float(g.max()) for g in got),
            prefix + "logit_gap_mean": float(sum(g.sum() for g in got) / tokens),
            prefix + "gaps": [round(float(g.max()), 5) for g in got]}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--fault", required=True,
                    help="a name in the family's reference.FAULTS")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2000003)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
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
            served = np.zeros_like(seqs)
            served[:, :-1] = seqs[:, 1:]  # position j predicts token j + 1
            token_rows = [served]
            if i < args.controls:
                control = family_ref.ServeReference(driver.arch, seed, args.control)
                token_rows.append([
                    np.asarray(jnp.argmax(logits, axis=-1), np.int32)
                    for _, logits in control.logits_rows(seqs)])
                del control
            ref = family_ref.ServeReference(driver.arch, seed, "f32")
            got = gaps_under(ref, seqs, lens, token_rows)
            del ref
            tokens = sum(g.size for g in got[0])
            row = {"seed": seed, "program_s": round(t1 - t0, 2),
                   "finished": len(driver.finished), "failed": out["failed"],
                   "weights_differ": driver.weights_differ,
                   "served_tokens": tokens, **summary("", got[0], tokens)}
            if i < args.controls:
                row.update(summary("control_", got[1], tokens))
            if i < args.faults:
                faulty = family_ref.ServeReference(
                    driver.arch, seed, "f32",
                    **family_ref.FAULTS[args.fault](lens))
                (bad,) = gaps_under(faulty, seqs, lens, [served])
                del faulty
                row.update(summary("fault_", bad, tokens), fault=args.fault)
            row["reference_s"] = round(time.time() - t1, 2)
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
