"""The join by count, shown once: a device gap put down to the host
WITHOUT the trace's clocks.

One run of a serve cell on the chip, profiled over the window's last
seconds as a ``--trace 1`` run of the benchmark is (the cell's own driver,
``benchmarks/run.py``'s context, no harness file changed).  Then:

- on the device's line of program executions the decode program is the
  one executed as often as the host has ``serve/dispatch`` spans; its n-th
  execution is dispatch (``cycle``) n.
  The count is anchored on the host's ``serve/dispatch`` spans, which carry
  ``cycle`` as a stat: each votes for the offset between its number and
  the index of the first decode execution that starts after it, and the
  commonest offset wins (the two clocks are 1-2 ms apart: enough to
  anchor a count on, not to split a gap by);
- the ten longest gaps of the device's busy union (as
  ``harness.tracered`` takes it) are each put down to the program that
  ran next: a decode execution, hence a cycle number, or a prefill;
- for a gap before decode execution ``m`` the host's account is the
  cycle in whose interval dispatch ``m`` was issued (``dispatched`` in
  ``ServeMetrics.to_json()["cycles"]["slowest"]``; on an engine that
  lags, record ``m - 1``).  Where that cycle is not among the longest
  kept, the same phases are read from the profile's spans BY THEIR
  ``cycle`` STAT (durations on the host's clock alone).

Beside each gap: the label ``harness.tracered``'s ``breakdown.idle_gaps``
files it under (the innermost host span at the gap's midpoint, by
timestamp), so that the two attributions can be compared.

    python scripts/cycle_gap_join.py jamba2-3b.batch256 --seed 38 --seconds 40
"""

from __future__ import annotations

import argparse
import bisect
import collections
import json
import os
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
sys.path.insert(0, REPO)

MODULES_LINE = "XLA Modules"
PHASES = ("schedule_s", "decode_args_s", "dispatch_s", "wait_s",
          "first_wait_s", "harvest_s", "caller_s")


def read_profile(xplane_path: str) -> dict:
    """``{"modules": [(name, start, end)], "ops": [(start, end)],
    "host": [(name, start, end, cycle or None)]}`` in nanoseconds, of the
    first TPU plane and the host's ``serve/*`` spans."""
    import jax

    from harness import tracered

    data = jax.profiler.ProfileData.from_file(xplane_path)
    out = {"modules": [], "ops": [], "host": [], "lines": {}}
    device = sorted(p.name for p in data.planes
                    if p.name.startswith("/device:TPU:"))[0]
    for plane in data.planes:
        if plane.name == device:
            for line in plane.lines:
                events = list(line.events)
                out["lines"][line.name] = len(events)
                if line.name == MODULES_LINE:
                    out["modules"] = sorted(
                        (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                        for e in events)
                elif line.name == tracered.OPS_LINE:
                    out["ops"] = [(int(e.start_ns), int(e.start_ns + e.duration_ns))
                                  for e in events if e.duration_ns > 0]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serve/"):
                        out["host"].append(
                            (e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns),
                             dict(e.stats).get("cycle")))
    out["modules"].sort(key=lambda m: m[1])
    out["host"].sort(key=lambda h: (h[1], -h[2]))
    return out


def number_decode_executions(profile: dict):
    """``(decode program's name, {index in modules: cycle})``."""
    # one decode execution a ``serve/dispatch`` span: the program whose
    # count comes nearest (an expert cell runs a small accumulate after
    # every decode AND every prefill, so the commonest is not it)
    names = collections.Counter(name for name, _, _ in profile["modules"])
    dispatches = sum(1 for h in profile["host"] if h[0] == "serve/dispatch")
    decode = min(names, key=lambda name: (abs(names[name] - dispatches), name))
    execs = [i for i, (name, _, _) in enumerate(profile["modules"])
             if name == decode]
    starts = [profile["modules"][i][1] for i in execs]
    votes = collections.Counter()
    for name, t0, _t1, cycle in profile["host"]:
        if name == "serve/dispatch" and cycle is not None:
            j = bisect.bisect_right(starts, t0)  # the first to start after it
            if j < len(starts):
                votes[cycle - j] += 1
    offset, agreed = votes.most_common(1)[0]
    return decode, {i: offset + j for j, i in enumerate(execs)}, {
        "dispatch_spans": sum(votes.values()), "agreed": agreed,
        "offsets": dict(votes.most_common(3))}


def phases_by_stat(profile: dict, dispatched: int) -> dict:
    """The phases of the interval in which dispatch ``dispatched`` was
    issued, on an engine that lags, from the spans that carry a number:
    the walk of block ``dispatched - 2``, the arguments and the call of
    ``dispatched``, the wait for block ``dispatched - 1``."""
    want = {("serve/harvest", dispatched - 2): "harvest_s",
            ("serve/decode_args", dispatched): "decode_args_s",
            ("serve/dispatch", dispatched): "dispatch_s",
            ("serve/wait", dispatched - 1): "wait_s"}
    out = {}
    for name, t0, t1, cycle in profile["host"]:
        key = want.get((name, cycle))
        if key:
            out[key] = (t1 - t0) / 1e9
    return out


def innermost_by_clock(profile: dict, t: int) -> str:
    """``tracered.reduce_events``' label for an instant: the innermost
    host span that holds it, by the profile's timestamps (its look-back
    is inline there: repeated here as ``proof/idle_split.py`` repeats it,
    until a ``benchmark`` issue gives the harness one)."""
    starts = [h[1] for h in profile["host"]]
    i = bisect.bisect_right(starts, t)
    for name, t0, t1, _ in reversed(profile["host"][max(0, i - 8):i]):
        if t0 <= t < t1:
            return name
    return "host (no annotation)"


def longest_gaps(profile: dict, numbered: dict, cycles: dict,
                 n: int = 10) -> list:
    """The ``n`` longest gaps of the device's busy union, each with the
    program that ran next and, before a decode execution (``numbered``:
    index in ``profile["modules"]`` -> cycle), the host's account."""
    from harness import tracered

    merged = tracered.union_intervals(profile["ops"])
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(merged, merged[1:])),
                  reverse=True)[:n]
    module_starts = [m[1] for m in profile["modules"]]
    records = {r["dispatched"]: r
               for r in cycles["slowest"] + cycles.get("slowest_plain", [])
               if r["dispatched"] is not None}
    origin = merged[0][0]
    rows = []
    for dur, t0, t1 in gaps:
        # the program that ran next: the first to start at or after the
        # gap's end, unless the gap lies inside one (between two of its
        # operations)
        i = bisect.bisect_left(module_starts, t1 - 1000)
        inside = next((m for m in profile["modules"][max(0, i - 2):i + 1]
                       if m[1] < t0 and t1 < m[2]), None)
        row = {"at_ms": round((t0 - origin) / 1e6, 3),
               "gap_us": round(dur / 1e3, 1),
               "by_clock": innermost_by_clock(profile, (t0 + t1) // 2)}
        if inside is not None:
            row["before"] = "inside " + inside[0][:40]
        elif i >= len(profile["modules"]):
            row["before"] = "the end of the trace"
        elif i in numbered:
            m = numbered[i]
            row["before"] = f"decode execution of cycle {m}"
            record = records.get(m)
            if record is not None:
                row["host_record"] = {k: round(record[k], 6) for k in PHASES}
                row["host_record"].update(
                    cycle=record["cycle"], gc_s=record["gc_s"],
                    descheduled_s=round(record["descheduled_s"], 6))
                source = record
            else:
                source = phases_by_stat(profile, m)
                row["host_spans_by_stat"] = {k: round(v, 6)
                                             for k, v in source.items()}
            busy = {k: v for k, v in source.items()
                    if k in PHASES and k not in ("wait_s", "first_wait_s")}
            if busy:
                row["phase_by_count"] = max(busy, key=busy.get)
        else:
            row["before"] = "prefill " + profile["modules"][i][0][:40]
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU, a tiny rehearsal cell: the run and the "
                    "account only (the CPU's profile has no device line)")
    ap.add_argument("--no-trace", action="store_true",
                    help="no profile: the run and the account only (the "
                    "slow-cycle log of a run as the benchmark times it)")
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out"))
    args = ap.parse_args(argv)

    import jax

    import run
    from harness import loader, tracered

    if not args.rehearsal and jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    run.configure_cache()
    cell = loader.load_cell(args.cell, rehearsal=args.rehearsal)
    traced = not (args.rehearsal or args.no_trace)
    ctx, driver = run.make_driver(cell, args.seed, args.seconds, trace=traced)
    kept = tempfile.mkdtemp(prefix="cycle_gap_join_")
    ctx.keep_trace = kept
    driver.setup()
    out = driver.window(args.seconds)
    ctx.stop_trace()
    from torchdistx_tpu.serve.metrics import latest_metrics

    account = latest_metrics().to_json()
    driver.free()
    result = {
        "cell": cell.name, "seed": args.seed,
        "tokens_per_s": out["end_to_end"]["serve_tokens_per_s"],
        "cycles": {k: v for k, v in account["cycles"].items()
                   if not k.startswith("slowest")},
        "phase_ms_p50": {
            name: None if h["p50"] is None else round(1e3 * h["p50"], 4)
            for name, h in account["histograms"].items()
            if name in ("schedule_s", "decode_args_s", "dispatch_s", "wait_s",
                        "harvest_s", "prefill_s", "cycle_s", "cycle_plain_s")},
        "slowest": account["cycles"]["slowest"],
        "slowest_plain": account["cycles"]["slowest_plain"],
    }
    if traced:
        profile = read_profile(tracered.find_xplane(kept))
        decode, numbered, anchor = number_decode_executions(profile)
        result.update({
            "device": jax.devices()[0].device_kind,
            "device_lines": profile["lines"],
            "decode_program": decode, "anchor": anchor,
            "idle_gaps_by_clock": ctx.reduction["breakdown"]["idle_gaps"],
            "idle_pct": ctx.reduction["idle_pct"],
            "longest_gaps": longest_gaps(profile, numbered,
                                         account["cycles"]),
        })
    shutil.rmtree(kept, ignore_errors=True)
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"cycle_gap_join.{cell.name}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({k: v for k, v in result.items()
                      if not k.startswith("slowest")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
