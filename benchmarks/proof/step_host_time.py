"""What the serve engine's host loop costs a decode step, with the
profiler off: one process on the chip at a serving cell's own load, the
cell's own driver, ``ServeEngine.step`` wrapped by a clock.

Per step it keeps the wall time of ``step()`` and what the engine's own
histograms put inside the dispatch regions (``decode_s``, ``prefill_s``:
dispatch to the end of the sync); the difference is the engine's host
time outside them.  Steps that admitted nothing are reported apart: an
admission builds a prefill's arguments.  The time between two ``step()``
calls is the caller's.  It reads nothing that the parent of PR 27 lacks,
so the same file measures both sides of a change to the host loop; where
the engine has the phase histograms (``schedule_s``, ``decode_args_s``,
``harvest_s``) their medians are printed too.  Last, the span primitive
alone: ``timed_annotation`` around nothing, microseconds each.

``--trace-seconds N`` takes a profile of the window's last N seconds, as
a ``--trace 1`` run of the benchmark does, and prints the tokens per
second before it and under it: what tracing costs when it is on.

    python benchmarks/proof/step_host_time.py mistral-7b.batch16 --seed 7 --seconds 30
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402
from harness import loader  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace-seconds", type=float, default=0.0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    import jax

    if not args.rehearsal and jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    run.configure_cache()
    cell = loader.load_cell(args.cell, rehearsal=args.rehearsal)
    ctx, driver = run.make_driver(cell, args.seed, args.seconds)
    driver.setup()
    engine = driver.engine
    inner = engine.step
    rows = []   # (wall, in decode dispatch, in prefill dispatch, admissions)
    profile = {}  # while it runs: its directory, its start, the tokens before it

    def step():
        m = engine.metrics
        now = time.monotonic()
        t_first = rows[0][4] if rows else now
        if (args.trace_seconds and not profile
                and now >= t_first + args.seconds - args.trace_seconds):
            profile["dir"] = tempfile.mkdtemp(prefix="step_host_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            profile["rate_before"] = driver.tokens / (now - t_first)
            jax.profiler.start_trace(profile["dir"], profiler_options=opts)
            profile["t0"], profile["tokens"] = time.monotonic(), driver.tokens
        d0, p0 = m.decode_s.total, m.prefill_s.total
        a0 = m.counters["prefill_calls"]
        t0 = time.perf_counter()
        out = inner()
        wall = time.perf_counter() - t0
        rows.append((wall, m.decode_s.total - d0, m.prefill_s.total - p0,
                     m.counters["prefill_calls"] - a0, now))
        return out

    engine.step = step
    out = driver.window(args.seconds)
    window_s = driver.window_s
    profile_on = None
    if profile:
        t1 = time.monotonic()
        jax.profiler.stop_trace()
        shutil.rmtree(profile["dir"], ignore_errors=True)
        profile_on = {
            "tokens_per_s_before": profile["rate_before"],
            "tokens_per_s_under": (driver.tokens - profile["tokens"]) / (t1 - profile["t0"]),
            "steps_under": sum(1 for r in rows if r[4] >= profile["t0"])}
    us = 1e6
    plain = [w - d for w, d, _p, a, _t in rows if a == 0]
    admit = [w - d - p for w, d, p, a, _t in rows if a]
    result = {
        "cell": cell.name, "seed": args.seed, "steps": len(rows),
        "steps_with_admissions": len(admit),
        "tokens_per_s": out["end_to_end"]["serve_tokens_per_s"],
        "step_wall_ms_p50": 1e3 * statistics.median(w for w, *_ in rows),
        "host_outside_dispatch_us": {
            "decode_only_p50": us * statistics.median(plain),
            "decode_only_mean": us * statistics.fmean(plain),
            "with_admission_p50": us * statistics.median(admit) if admit else None,
        },
        "caller_between_steps_us_mean":
            us * (window_s - sum(w for w, *_ in rows)) / len(rows),
        "phase_p50_us": {
            name: us * hist.quantile(0.5)
            for name in ("schedule_s", "decode_args_s", "decode_s", "harvest_s")
            if (hist := getattr(engine.metrics, name, None)) is not None
        },
    }
    if not args.rehearsal:
        result["device"] = jax.devices()[0].device_kind
        if profile_on:
            result["profile_on"] = profile_on
    driver.free()

    from torchdistx_tpu.serve.metrics import Histogram
    from torchdistx_tpu.utils.profiling import timed_annotation

    sink, n = Histogram().record, 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        with timed_annotation("serve/nothing", sink):
            pass
    result["timed_annotation_us"] = us * (time.perf_counter() - t0) / n
    if args.rehearsal:   # the CPU gives counts, never a time
        result = {"cell": cell.name, "steps": len(rows),
                  "steps_with_admissions": len(admit),
                  "phases": sorted(result["phase_p50_us"])}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
