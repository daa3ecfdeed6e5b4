"""The runs a bound is set from: for one cell, ``--sets`` sets of
``--runs`` runs, the same seeds in every set, each run a new process of
the benchmark's own command; then ``--traced`` runs with ``--trace 1`` on
further seeds.  Prints every result line and, per metric and set, the
median and the spread (distance between the first and third quartile of
``statistics.quantiles(values, n=4)`` as a share of the median).

This parent never touches JAX: the chip belongs to one process at a time.

    python benchmarks/proof/sets.py <cell> --seconds 40 --out chiprun_out/sets_x.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def one_run(cell, seed, seconds, trace, tree=REPO):
    """The benchmark's own command, as ``tree``'s BENCHMARK.json gives it,
    run from ``tree``."""
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    t0 = time.time()
    proc = subprocess.run(
        command + ["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)],
        cwd=tree, capture_output=True, text=True)
    lines = [line for line in proc.stdout.splitlines() if line.strip()]
    row = {"cell": cell, "seed": seed, "trace": trace, "rc": proc.returncode,
           "wall_s": round(time.time() - t0, 2)}
    try:
        row["result"] = json.loads(lines[-1])
    except (IndexError, ValueError):
        row["stderr_tail"] = proc.stderr[-3000:]
    return row


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--traced", type=int, default=0)
    ap.add_argument("--first-seed", type=int, default=3000017)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seeds = [args.first_seed + 15485863 * i + (2**31 if i % 2 else 0)
             for i in range(args.runs + args.traced)]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    by_set = []
    with open(args.out, "a") as f:
        for s in range(args.sets):
            rows = []
            for seed in seeds[: args.runs]:
                row = one_run(args.cell, seed, args.seconds, 0)
                row["set"] = s
                rows.append(row)
                f.write(json.dumps(row) + "\n")
                f.flush()
                print(json.dumps({k: v for k, v in row.items() if k != "result"}),
                      json.dumps(row.get("result", {}).get("metrics")),
                      "correct" if row.get("result", {}).get("correct") else "NOT CORRECT",
                      flush=True)
            by_set.append(rows)
        for seed in seeds[args.runs:]:
            row = one_run(args.cell, seed, args.seconds, 1)
            f.write(json.dumps(row) + "\n")
            f.flush()
            res = row.get("result", {})
            print(json.dumps({k: v for k, v in row.items() if k != "result"}),
                  json.dumps(res.get("metrics")), json.dumps(res.get("device")),
                  "correct" if res.get("correct") else "NOT CORRECT", flush=True)
    summary = {}
    for s, rows in enumerate(by_set):
        good = [r["result"] for r in rows if "result" in r]
        names = sorted({n for r in good for n in r["metrics"]})
        for n in names:
            vals = [r["metrics"][n]["value"] for r in good if n in r["metrics"]]
            # set-up: each set's first run may compile; the driver leaves it out too
            use = vals[1:] if n == "setup_s" and s == 0 else vals
            if len(use) >= 2:
                summary.setdefault(n, []).append(
                    {"set": s, "n": len(use), "median": statistics.median(use),
                     "spread": spread(use), "values": vals})
    print(json.dumps({"cell": args.cell, "summary": summary}, indent=1))
    with open(args.out, "a") as f:
        f.write(json.dumps({"cell": args.cell, "summary": summary}) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
