"""Profile the single-chip training step (the bench.py phase-2 workload).

One run emits, through the unified telemetry layer (torchdistx_tpu.obs):
  - an XLA profiler trace (view in TensorBoard/XProf) of N timed steps,
  - a host-side Perfetto trace (``<logdir>/host_trace.json`` — open in
    ui.perfetto.dev) of the same run: warm-up calls, the timed window,
    any replay spans,
  - the compiled step's cost analysis (FLOPs, bytes accessed, arithmetic
    intensity) via utils.profiling.cost_summary,
  - recompile-watcher counters (obs.RecompileWatcher): every XLA compile
    attributed to warm-up vs the timed window — the donated-carry
    recompile is a NUMBER here, not a timing anomaly,
  - device memory stats and a Prometheus exposition snapshot of the
    run's metrics.

Output contract (same as bench.py): progress lines stream as they
happen, and the LAST stdout line is the full parseable JSON record.

This is the round-3 entry point for the MFU investigation: the measured
5.5% MFU (BENCH r2) with an XLA-counted ~0.87x-of-formula FLOP count and
very high bytes-accessed suggests an HBM-bound step — the trace says
where.

Usage:  python scripts/profile_train_step.py [--logdir /tmp/tdx-trace]
        TDX_BENCH_TRAIN_MODEL=llama_1b TDX_BENCH_SEQ=2048 control the
        workload like bench.py's train phase.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--logdir", default="/tmp/tdx-trace")
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()

    import jax

    import numpy as np

    from torchdistx_tpu import obs
    from torchdistx_tpu.utils import profiling
    from torchdistx_tpu.utils.benchmarks import (
        build_train_workload,
        peak_bf16_flops,
        warm_to_steady_state,
    )

    os.makedirs(args.logdir, exist_ok=True)
    record: dict = {"profile": "train_step", "logdir": args.logdir}
    tracer = obs.enable_tracing(
        jsonl_path=os.path.join(args.logdir, "events.jsonl")
    )
    watcher = obs.RecompileWatcher()
    registry = obs.MetricsRegistry()
    registry.register_collector(watcher.collector())

    # the SAME workload bench.py scores (shared builder)
    with tracer.span("profile/build_workload"):
        w = build_train_workload(args.steps)
    run, carry = w["run"], w["carry"]
    record["workload"] = {
        k: w[k] for k in ("name", "n_params", "batch", "seq")
    }

    # cost analysis BEFORE executing (compile-only)
    with tracer.span("profile/cost_analysis"), watcher.scope(
        "cost_analysis"
    ):
        record["cost_analysis"] = profiling.cost_summary(
            run, carry,
            peak_flops=peak_bf16_flops(jax.devices()[0].device_kind),
        )
    print(json.dumps({"cost_analysis": record["cost_analysis"]}), flush=True)

    # warm to the layout fixpoint outside the trace — a single warm call
    # would put the donated-carry recompile inside the traced window,
    # round-2's measurement bug (see utils.benchmarks.warm_to_steady_state;
    # shared with bench.py so what we profile stays what we score).  The
    # watcher attributes warm-up compiles to "warmup", so the record
    # shows the donated-carry recompile count explicitly.
    carry, warm_times, warm_converged = warm_to_steady_state(
        run,
        carry,
        sync=lambda losses: float(np.asarray(losses[-1])),
        watcher=watcher,
        label="warmup",
    )
    record["warm_calls_s"] = [round(t, 3) for t in warm_times]
    record["warm_converged"] = warm_converged
    if not warm_converged:
        print(
            json.dumps({"warning": "warm-up did not reach the compile "
                        "fixpoint; the trace may contain a recompile"}),
            file=sys.stderr,
        )

    with profiling.trace(args.logdir):
        with profiling.timed_annotation("timed_steps") as timing:
            carry, losses = run(carry)
            final = float(np.asarray(losses[-1]))
    record["final_loss"] = round(final, 4)
    record["timed_window_s"] = round(timing["seconds"], 3)
    # compiles attributed per phase: anything under "timed_steps" means
    # the timed window was NOT steady state — the exact artifact
    # warm_to_steady_state exists to prevent, now visible as a counter
    record["recompile"] = watcher.snapshot()
    record["memory_stats"] = profiling.device_memory_stats()
    print(profiling.format_memory_stats(record["memory_stats"]), flush=True)

    record["host_trace"] = tracer.export(
        os.path.join(args.logdir, "host_trace.json")
    )
    record["metrics_prom"] = os.path.join(args.logdir, "metrics.prom")
    with open(record["metrics_prom"], "w") as f:
        f.write(registry.render())
    obs.disable_tracing()  # flush + close the JSONL sink

    # the bench.py consumer contract: the full record is the LAST line
    print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()
