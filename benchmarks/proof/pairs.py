"""Two trees on one chip, run for run: the benchmark's own command in a
``--parent`` tree (a ``git archive`` of the parent commit, unpacked into a
directory of the repo that ``.gitignore`` lists) and in this one, one seed
a pair, sides alternating (parent, change, change, parent, ...), the last
``--traced`` pairs with ``--trace 1``.  Prints one line a run (metrics,
the numbers compared, ``correct``) and, per pair, which compared numbers
and counts are equal to the digit.

This parent never touches JAX: the chip belongs to one process at a time.

    python benchmarks/proof/pairs.py <cell> --parent _export/parent --seeds 3 --traced 1 --seconds 40 --out chiprun_out/pairs_x.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from sets import REPO, one_run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--parent", required=True, help="the parent's tree")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--traced", type=int, default=1)
    ap.add_argument("--first-seed", type=int, default=29000039)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    trees = {"parent": os.path.abspath(args.parent), "change": REPO}
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as f:
        for i in range(args.seeds):
            seed = args.first_seed + 15485863 * i + (2**31 if i % 2 else 0)
            trace = int(i >= args.seeds - args.traced)
            sides = ("parent", "change") if i % 2 == 0 else ("change", "parent")
            got = {}
            for side in sides:
                row = one_run(args.cell, seed, args.seconds, trace, tree=trees[side])
                row["side"] = side
                f.write(json.dumps(row) + "\n")
                f.flush()
                res = got[side] = row.get("result", {})
                print(side, json.dumps({k: v for k, v in row.items() if k != "result"}),
                      json.dumps({k: v["value"] for k, v in res.get("metrics", {}).items()}),
                      json.dumps({k: v["value"] for k, v in res.get("compared", {}).items()}),
                      "correct" if res.get("correct") else "NOT CORRECT", flush=True)
            both = [got[s].get("compared", {}) for s in ("parent", "change")]
            print("pair", seed, "compared equal to the digit:",
                  sorted(k for k in both[0] if both[0][k] == both[1].get(k)),
                  "differ:", sorted(k for k in both[0] if both[0][k] != both[1].get(k)),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
