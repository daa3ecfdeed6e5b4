"""Serving throughput: continuous batching through ``serve.ServeEngine``,
with a fused multi-step decode A/B, a persistent-loop A/B, and an
optional shared-prefix A/B.

Phases: the K=1 baseline FIRST (one host sync per token), then one phase
per ``--decode-chunk`` value (K decode steps fused into one ``lax.scan``
dispatch, one sync per K tokens), then — with ``persistent`` in
``--decode-mode`` (the default) — the persistent whole-loop phase (one
``lax.while_loop`` dispatch per generation wave, host syncs = ring
drains only; its summary carries ``syncs_reduction_vs_k16`` against the
K=16 fused baseline that ran before it), then — with ``--speculate
0,2,4`` — one persistent-loop phase per K on a repetition-heavy workload
(the prompt-lookup drafter's food), K=0 FIRST as the baseline leg; the
K>0 summaries carry ``accepted_tokens_per_iteration`` and
``loop_iterations_reduction_vs_spec0``, and a K>0 phase flags ``error``
unless it accepted more than one token per iteration, ran strictly fewer
loop iterations than spec0, and kept ``host_syncs`` EXACTLY equal to the
baseline's (speculation multiplies tokens per sync; it may never add
one); then — with ``--prefix-share``
— one paged-engine phase that runs the SAME repeated-system-prompt burst
twice through one engine: cold (empty prefix index) and warm (index
populated by the cold pass).  Warm prefill must compute strictly fewer padded
tokens than cold (suffix-only prefill); the phase reports both passes'
full metrics (``ServeMetrics.to_json()``) plus the warm prefix hit-rate
and pages-in-use high water, and flags ``error`` when the inequality
fails (so ``TDX_SERVE_STRICT`` CI catches a broken prefix cache); then —
with ``--kv-dtype`` (every phase's engines store KV quantized) or
``--kv-quant-ab`` (only the A/B phase; default phases untouched) — the
``kv_quant`` phase: a bfloat16-cache baseline vs the quantized engine on
one greedy workload, STRICT on the exactly-halved ``memory_plan()`` KV
pool (int8), the pinned stream-divergence tolerance against the
model-dtype oracle, decode tok/s, and strictly-lower decode-program
``bytes_accessed``.  Each
phase embeds ``engine.metrics.to_json()`` verbatim under ``"metrics"`` —
one schema for tests, bench, and CI to parse — plus the recompile
watcher's counters (``recompile_warmup`` / ``recompile_measure``: XLA
compiles attributed serve/prefill vs serve/decode; the measured window
is expected to compile NOTHING, and ``measure_compiles`` in the summary
says so per phase).  With ``TDX_SERVE_TRACE_DIR`` set, each phase also
writes a Perfetto host trace (per-request lifecycle tracks included)
and a Prometheus exposition snapshot there, paths embedded in the
record (``trace_path`` / ``metrics_prom_path`` — what the nightly
observability smoke validates).

Same output contract as bench.py: a FULL parseable JSON record is the
LAST stdout line after EVERY phase, baseline included.  Each phase runs
in its own subprocess under the remaining share of ``TDX_BENCH_DEADLINE``
(default 1500 s total); phases run strictly serially and the parent never
imports jax (a chip belongs to one process at a time).  A phase that
errors or overruns makes the run exit non-zero.  The final record is also
written to ``BENCH_SERVE_<CPU|TPU>.json`` at the repo root.

Usage (TPU):  python scripts/bench_serve.py   # K=1 vs 4,8,16 vs persistent
Smoke (CPU):  JAX_PLATFORMS=cpu TDX_SERVE_MODEL=tiny \
                  python scripts/bench_serve.py --decode-chunk 4 \
                  --requests 6 --max-new 8 --slots 2
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _ledger():
    """Load ``torchdistx_tpu/obs/ledger.py`` WITHOUT importing the
    package: the supervising parent must never pull in jax or the
    native build (the parent-never-touches-the-device rule), and the
    ledger module is stdlib-only by design.  Memoized in ``sys.modules``
    so repeat calls share one module instance (and its git-sha cache)."""
    import importlib.util

    mod = sys.modules.get("_tdx_ledger")
    if mod is not None:
        return mod
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "torchdistx_tpu", "obs", "ledger.py",
    )
    spec = importlib.util.spec_from_file_location("_tdx_ledger", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    sys.modules["_tdx_ledger"] = mod
    return mod


def _parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=None)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument(
        "--decode-chunk",
        default="4,8,16",
        help="comma-separated fused-decode chunk sizes to A/B against the "
        "always-run K=1 baseline",
    )
    ap.add_argument(
        "--decode-mode",
        default="chunked,persistent",
        help="comma-separated engine decode modes to bench: 'chunked' "
        "runs the K=1 baseline + the --decode-chunk sweep, 'persistent' "
        "appends the whole-loop phase (always after a fused K baseline, "
        "so the record carries the A/B)",
    )
    ap.add_argument(
        "--ring",
        type=int,
        default=None,
        help="persistent-mode ring capacity (default: the engine's "
        "max_len — one drain per generation wave)",
    )
    ap.add_argument(
        "--speculate",
        default="",
        help="comma-separated self-speculation depths to A/B through the "
        "persistent loop on a repetition-heavy workload (e.g. '0,2,4'); "
        "the K=0 baseline leg always runs first, like the K=1 fused "
        "baseline",
    )
    ap.add_argument(
        "--spec-ngram",
        type=int,
        default=2,
        help="prompt-lookup n-gram width for the --speculate phases",
    )
    ap.add_argument(
        "--prefix-share",
        action="store_true",
        help="append a paged-engine phase A/Bing a repeated-system-prompt "
        "burst cold vs warm (prefix cache empty vs populated)",
    )
    ap.add_argument(
        "--page-size",
        type=int,
        default=16,
        help="KV page size (tokens) for the --prefix-share phase",
    )
    ap.add_argument(
        "--tp",
        type=int,
        default=1,
        help="tensor-parallel degree: every phase runs its engine on a "
        "('tp',) mesh of this many devices (params Megatron-sharded, KV "
        "head-sharded) and embeds the phase's comm-audit bytes; on the "
        "CPU smoke the parent raises the child's virtual device count "
        "to match",
    )
    ap.add_argument(
        "--chunked-prefill",
        type=int,
        default=None,
        metavar="T",
        help="append a chunked-prefill A/B phase: a long-prompt admission "
        "mid-decode, unchunked vs chunked at threshold T (must be a "
        "prefill bucket) — the headline is that the active requests "
        "receive tokens between the long prompt's chunks, by count",
    )
    ap.add_argument(
        "--migrate-tp-to",
        type=int,
        default=None,
        metavar="N",
        help="append an elastic-migration phase: drain a --tp engine "
        "mid-decode and migrate_to() a tp=N engine, pinning zero drops, "
        "bit-identical streams, and the closed-form migration wire bytes "
        "as ledger counter rows (workload key 'mesh_to')",
    )
    ap.add_argument(
        "--fleet",
        type=int,
        default=None,
        metavar="N",
        help="append the fleet phases (ISSUE 13): an N-replica "
        "ServeFleet A/B on a shared-prefix arrival stream — affinity vs "
        "round-robin routing, prefix hit-rate and p50 TTFT, streams "
        "pinned bit-identical to one engine — plus a mid-workload "
        "fleet.remove() drain leg (zero drops)",
    )
    ap.add_argument(
        "--disaggregate",
        action="store_true",
        help="with --fleet: append the disaggregated leg — a prefill "
        "(tp=2) and a decode (tp=1) engine behind the router, every "
        "finished prefill's KV handed off as an explicit head-axis "
        "redistribution pinned closed-form against the comm audit",
    )
    ap.add_argument(
        "--scenario",
        default=None,
        metavar="NAMES",
        help="comma-separated open-loop traffic scenarios from the "
        "serve/workload.py catalog (poisson, diurnal, bursty, "
        "flash_crowd): each appends an autoscale A/B phase replaying "
        "the scenario's deterministic tick-stamped arrival stream "
        "through every static fleet size the policy allows AND a "
        "closed-loop AutoscaleController fleet — the STRICT verdict is "
        "that autoscaling beats every static of equal-or-lower "
        "replica-tick cost on deadline attainment, is Pareto-undominated, "
        "executes a full scale-up + scale-down cycle, and keeps every "
        "stream bit-identical to the single-engine oracle",
    )
    ap.add_argument(
        "--autoscale",
        default=None,
        metavar="POLICY",
        help="ScalingPolicy for the --scenario phases: 'default', an "
        "inline JSON object, or a path to one (serve/autoscale.py "
        "schema); defaults to 'default' when --scenario is given",
    )
    ap.add_argument(
        "--slo",
        default=None,
        metavar="SPEC",
        help="path to a JSON SloSpec (obs/slo.py): every fleet phase "
        "evaluates it over the fleet's finished requests and embeds "
        "the tdx-slo-v1 report as the phase's 'slo' block (the routing "
        "A/B embeds one report per policy — the SLO-attainment axis of "
        "the affinity-vs-RR verdict); a breached evaluation lands a "
        "named slo_burn flight event",
    )
    ap.add_argument(
        "--slo-strict",
        action="store_true",
        help="with --slo: a breached report (or a burning window) is a "
        "phase error and the run exits nonzero — the nightly "
        "injected-burn leg's contract",
    )
    ap.add_argument(
        "--kv-dtype",
        default=None,
        metavar="DTYPE",
        help="KV-cache storage dtype for EVERY phase's engines (int8 "
        "quantizes on write with per-row power-of-two scales; bfloat16/"
        "float16/float32 cast).  Also appends the kv_quant A/B phase: "
        "a bfloat16-baseline vs --kv-dtype engine pair on the same "
        "greedy workload, STRICT on the halved memory_plan() KV pool, "
        "the pinned stream-divergence tolerance, decode tok/s, and a "
        "strictly-lower cost-card bytes_accessed for every decode "
        "program.  Phase records gain a 'kv_dtype' ledger workload key "
        "(only when set — default-run fingerprints never drift)",
    )
    ap.add_argument(
        "--kv-quant-ab",
        default=None,
        metavar="DTYPE",
        help="append ONLY the kv_quant A/B phase at this quantized dtype "
        "while every other phase keeps its default (model-dtype) cache — "
        "the nightly default-smoke rider: existing fingerprints stay "
        "byte-stable and the record gains the int8 family.  Use "
        "--kv-dtype instead to run the WHOLE sweep quantized",
    )
    ap.add_argument(
        "--numerics",
        action="store_true",
        help="append the numerics-observatory A/B phase (ISSUE 19): a "
        "digest-off and a digest-on engine serve the SAME greedy "
        "workload; STRICT on bit-identical streams and EXACTLY equal "
        "host_syncs / decode_dispatches / decode_steps (digests fuse "
        "into the existing programs and harvest at existing syncs — "
        "enabling them must cost zero dispatches).  The on-leg embeds "
        "the tdx-numerics-v1 digest book; its exact integer fields "
        "land as ledger counter rows (workload keys 'numerics' + "
        "'numerics_site') that perf_gate pins bit-identically across "
        "runs.  Default phases never build digest engines, so "
        "pre-existing fingerprints stay byte-stable",
    )
    ap.add_argument(
        "--record",
        action="store_true",
        help="incident time machine (ISSUE 20): after each phase's "
        "measured window, re-serve the identical workload on a fresh "
        "engine with session recording on (obs/blackbox.py tdx-session-v1 "
        "black box), then self-replay the recording and embed the STRICT "
        "verdict — every drain-boundary digest chain must be "
        "bit-identical, and the recording engine's counters must equal "
        "the unrecorded measured run's (the zero-overhead pin)",
    )
    ap.add_argument(
        "--artifact",
        default=None,
        help="override the BENCH_SERVE_<CPU|TPU>.json artifact path "
        "(the nightly 2-device-mesh leg writes its own file so the "
        "single-chip artifact is never clobbered)",
    )
    return ap.parse_args()


def _chunk_values(args) -> list:
    ks = [int(k) for k in str(args.decode_chunk).split(",") if str(k).strip()]
    if any(k < 1 for k in ks):
        raise SystemExit(f"--decode-chunk values must be >= 1, got {ks}")
    # K=1 baseline always runs first so a wedge mid-sweep still leaves a
    # comparable record; dedupe (order-preserving — repeats would burn a
    # phase's deadline share and silently overwrite its record)
    return [1] + [k for k in dict.fromkeys(ks) if k != 1]


def _spec_values(args) -> list:
    """The ``--speculate`` sweep: K=0 (the classic persistent program)
    always FIRST so a wedge mid-sweep still leaves the baseline leg of
    the A/B, then the deduped K>0 depths."""
    ks = [int(k) for k in str(args.speculate).split(",") if str(k).strip()]
    if not ks:
        return []
    if any(k < 0 for k in ks):
        raise SystemExit(f"--speculate values must be >= 0, got {ks}")
    return [0] + [k for k in dict.fromkeys(ks) if k != 0]


def _scenario_values(args) -> list:
    """The ``--scenario`` sweep, deduped in request order.  Validated
    against a literal copy of the serve/workload.py catalog names — the
    parent must stay import-free (a parent that has touched jax holds the
    chip, and a child that needs it then fails or hangs), so it cannot
    ask the module."""
    names = [
        s.strip() for s in str(args.scenario or "").split(",") if s.strip()
    ]
    unknown = set(names) - {"poisson", "diurnal", "bursty", "flash_crowd"}
    if unknown:
        raise SystemExit(f"unknown --scenario names: {sorted(unknown)}")
    return list(dict.fromkeys(names))


def _phase_summary(rec: dict) -> dict:
    """The A/B headline numbers of one phase record, lifted out of its
    embedded ``metrics`` (``ServeMetrics.to_json()``) object."""
    m = rec.get("metrics") or {}
    derived = m.get("derived") or {}
    counters = m.get("counters") or {}
    hists = m.get("histograms") or {}
    out = {
        "decode_tokens_per_sec": derived.get("decode_tokens_per_sec"),
        "wall_tokens_per_sec": derived.get("wall_tokens_per_sec"),
        "syncs_per_token": derived.get("syncs_per_token"),
        "host_syncs": counters.get("host_syncs"),
        "masked_slot_steps": counters.get("masked_slot_steps"),
        # compiles inside the measured window (recompile watcher):
        # anything nonzero means the phase's timings include XLA
        # compiles.  available=False means the jax.monitoring hook is
        # missing and the count is UNKNOWN — surface null, never a
        # clean-looking 0 (the watcher's snapshot contract)
        "measure_compiles": (
            (rec.get("recompile_measure") or {}).get("compiles_total")
            if (rec.get("recompile_measure") or {}).get("available")
            else None
        ),
        "error": rec.get("error"),
    }
    if rec.get("decode_mode") == "persistent":
        gauges = m.get("gauges") or {}
        out.update(
            ring_drains=counters.get("ring_drains"),
            loop_iterations=counters.get("loop_iterations"),
            ring_occupancy_hwm=gauges.get("ring_occupancy_hwm"),
        )
    if rec.get("speculate") is not None:  # the self-speculation A/B
        out.update(
            speculate=rec.get("speculate"),
            accept_rate=derived.get("accept_rate"),
            accepted_tokens_per_iteration=derived.get(
                "accepted_tokens_per_iteration"
            ),
            draft_tokens_proposed=counters.get("draft_tokens_proposed"),
            draft_tokens_accepted=counters.get("draft_tokens_accepted"),
            loop_iterations_reduction_vs_spec0=rec.get(
                "loop_iterations_reduction_vs_spec0"
            ),
        )
    if "warm" in rec:  # the prefix-share phase
        out.update(
            prefix_hit_rate_warm=rec.get("prefix_hit_rate_warm"),
            tokens_prefilled_cold=rec.get("tokens_prefilled_cold"),
            tokens_prefilled_warm=rec.get("tokens_prefilled_warm"),
            pages_in_use_hwm=rec.get("pages_in_use_hwm"),
        )
    if "shorts_rode_interleaved" in rec:  # the chunked-prefill A/B phase
        out.update(
            shorts_rode_interleaved=rec.get("shorts_rode_interleaved"),
            interleaved_dispatches=rec.get("interleaved_dispatches"),
        )
    if "prefix_hit_rate_affinity" in rec:  # the fleet routing A/B
        out.update(
            prefix_hit_rate_affinity=rec.get("prefix_hit_rate_affinity"),
            prefix_hit_rate_round_robin=rec.get(
                "prefix_hit_rate_round_robin"
            ),
            ttft_p50_s_affinity=rec.get("ttft_p50_s_affinity"),
            ttft_p50_s_round_robin=rec.get("ttft_p50_s_round_robin"),
            streams_identical=rec.get("streams_identical"),
        )
    if "kv_bytes_factor" in rec:  # the kv_quant A/B phase
        out.update(
            kv_dtype=rec.get("kv_dtype"),
            kv_bytes_factor=rec.get("kv_bytes_factor"),
            stream_prefix_agreement=rec.get("stream_prefix_agreement"),
            streams_identical_frac=rec.get("streams_identical_frac"),
            decode_tokens_per_sec_baseline=rec.get(
                "decode_tokens_per_sec_baseline"
            ),
        )
    if "remove_summary" in rec:  # the fleet drain leg
        out.update(
            streams_identical=rec.get("streams_identical"),
            migrated_running=(rec.get("remove_summary") or {}).get(
                "migrated_running"
            ),
            migrated_queued=(rec.get("remove_summary") or {}).get(
                "migrated_queued"
            ),
        )
    if "autoscale_verdict" in rec:  # the closed-loop autoscale A/B
        v = rec.get("autoscale_verdict") or {}
        out.update(
            scenario=rec.get("scenario"),
            autoscale_ok=v.get("ok"),
            requests=v.get("requests"),
            attained_autoscale=v.get("attained_autoscale"),
            replica_ticks_autoscale=v.get("replica_ticks_autoscale"),
            attained_static=v.get("attained_static"),
            replica_ticks_static=v.get("replica_ticks_static"),
            scale_ups=v.get("scale_ups"),
            scale_downs=v.get("scale_downs"),
            streams_identical=v.get("streams_identical"),
        )
    if "handoff_wire_bytes_expected" in rec:  # the disaggregated leg
        out.update(
            streams_identical=rec.get("streams_identical"),
            handoff_wire_bytes=counters.get("handoff_wire_bytes"),
            requests_handed_off=counters.get("requests_handed_off"),
        )
    if (rec.get("mesh") or 1) > 1:
        # the tdx-comm-v1 profile embedded by the TP phases
        comm = rec.get("comm") or {}
        out["comm_wire_bytes"] = sum(
            (comm.get("bytes_by_axis") or {}).values()
        )
    slo = rec.get("slo") or {}
    if "schema" in slo:  # one report per phase
        out["slo_attainment"] = (slo.get("attainment") or {}).get(
            "overall"
        )
        out["slo_breached"] = slo.get("breached")
        out["slo_burn_state"] = (slo.get("burn") or {}).get("state")
    elif slo:  # the routing A/B carries one report per policy
        for pol, r in sorted(slo.items()):
            if isinstance(r, dict) and "schema" in r:
                out[f"slo_attainment_{pol}"] = (
                    r.get("attainment") or {}
                ).get("overall")
                out[f"slo_breached_{pol}"] = r.get("breached")
    return out


def _supervise(args) -> None:
    """Run one child per K under the global deadline; the parent never
    touches the device (a chip belongs to one process at a time), and
    phases are strictly serial for the same reason."""
    deadline = float(os.environ.get("TDX_BENCH_DEADLINE", "1500"))
    t0 = time.monotonic()
    chunks = _chunk_values(args)
    modes = [m for m in str(args.decode_mode).split(",") if m.strip()]
    unknown = set(modes) - {"chunked", "persistent"}
    if unknown:
        raise SystemExit(f"unknown --decode-mode values: {sorted(unknown)}")
    if "chunked" not in modes:
        # the persistent A/B still needs its fused baselines: K=1 (the
        # sweep's anchor) and the largest requested K (the comparator)
        chunks = [1] + ([chunks[-1]] if chunks[-1] != 1 else [])
    specs = _spec_values(args)
    record: dict = {
        "bench": "serve",
        # commit + schema attribution (the perf-sentinel requirement:
        # a record that can't name its sha can't join the trajectory)
        **_ledger().record_stamp(),
        "model": os.environ.get("TDX_SERVE_MODEL", "llama_1b"),
        "deadline_s": deadline,
        "decode_chunks": chunks,
        "decode_modes": modes,
        "speculate_sweep": specs,
        "mesh": args.tp,
        "phases": {},
    }
    # phase plan: K=1 baseline, the chunk A/B, the persistent loop
    # (always AFTER its fused baselines), then (opt-in) the paged
    # shared-prefix cold/warm A/B at the largest requested chunk
    plan = [(f"k{k}", {"TDX_SERVE_CHUNK": str(k)}) for k in chunks]
    if "persistent" in modes:
        plan.append(("persistent", {"TDX_SERVE_PHASE": "persistent"}))
    for k in specs:
        plan.append(
            (
                f"spec{k}",
                {
                    "TDX_SERVE_PHASE": "speculate",
                    "TDX_SERVE_SPECULATE": str(k),
                },
            )
        )
    if args.prefix_share:
        plan.append(
            (
                "prefix_share",
                {
                    "TDX_SERVE_CHUNK": str(chunks[-1]),
                    "TDX_SERVE_PHASE": "prefix_share",
                },
            )
        )
    if args.chunked_prefill is not None:
        plan.append(
            (
                "chunked_prefill",
                {
                    "TDX_SERVE_CHUNK": str(chunks[-1]),
                    "TDX_SERVE_PHASE": "chunked_prefill",
                },
            )
        )
    if args.migrate_tp_to is not None:
        plan.append(
            (
                "migrate",
                {
                    "TDX_SERVE_CHUNK": str(chunks[-1]),
                    "TDX_SERVE_PHASE": "migrate",
                },
            )
        )
    if args.kv_dtype or args.kv_quant_ab:
        plan.append(
            (
                "kv_quant",
                {
                    "TDX_SERVE_CHUNK": str(chunks[-1]),
                    "TDX_SERVE_PHASE": "kv_quant",
                },
            )
        )
    if args.numerics:
        plan.append(
            (
                "numerics",
                {
                    "TDX_SERVE_CHUNK": str(chunks[-1]),
                    "TDX_SERVE_PHASE": "numerics",
                },
            )
        )
    if args.fleet is not None:
        # the routing A/B first (its STRICT verdict is the headline),
        # then the scale-event leg, then (opt-in) disaggregation
        for fname in ["fleet", "fleet_drain"] + (
            ["fleet_disagg"] if args.disaggregate else []
        ):
            plan.append(
                (
                    fname,
                    {
                        "TDX_SERVE_CHUNK": str(chunks[-1]),
                        "TDX_SERVE_PHASE": fname,
                    },
                )
            )
    for sc in _scenario_values(args):
        # one A/B phase per traffic scenario; the child pins its own
        # engine geometry to the scenario's token envelope, so no
        # TDX_SERVE_CHUNK override here
        plan.append(
            (
                f"autoscale_{sc}",
                {
                    "TDX_SERVE_PHASE": "autoscale",
                    "TDX_SERVE_SCENARIO": sc,
                },
            )
        )

    def emit():
        # the speculation A/B verdict, before the summary snapshots it:
        # a K>0 leg must beat spec0 on iteration economy WITHOUT moving
        # the sync count (speculation multiplies tokens per sync — one
        # extra host sync means the engine broke the drain discipline).
        # Idempotent across the per-phase emits: same inputs, same
        # fields, and a flagged error short-circuits further rewrites.
        spec0 = record["phases"].get("spec0") or {}
        base_c = (spec0.get("metrics") or {}).get("counters") or {}
        for name, rec in record["phases"].items():
            if not (name.startswith("spec") and name != "spec0"):
                continue
            if "error" in rec or "error" in spec0 or not base_c:
                continue
            c = (rec.get("metrics") or {}).get("counters") or {}
            it, base_it = c.get("loop_iterations"), base_c.get(
                "loop_iterations"
            )
            rec["loop_iterations_reduction_vs_spec0"] = (
                round(base_it / it, 3) if it and base_it else None
            )
            if it and base_it and not it < base_it:
                rec["error"] = (
                    "speculation did not reduce loop iterations "
                    f"({it} vs {base_it} at spec0)"
                )
            elif c.get("host_syncs") != base_c.get("host_syncs"):
                rec["error"] = (
                    "speculation changed the host sync count "
                    f"({c.get('host_syncs')} vs "
                    f"{base_c.get('host_syncs')} at spec0)"
                )
        # phases run (and are recorded) in plan order; dict order is the
        # summary order
        record["summary"] = {
            name: _phase_summary(rec)
            for name, rec in record["phases"].items()
        }
        summ = record["summary"]
        if "persistent" in summ:
            # the tentpole headline: persistent syncs/token vs the
            # largest fused-K baseline that ran before it (k16 on the
            # default sweep) — >= 4x is the acceptance bar
            baseline = max(
                (n for n in summ if n.startswith("k") and n[1:].isdigit()),
                key=lambda n: int(n[1:]),
                default=None,
            )
            if baseline is not None:
                spt = summ["persistent"].get("syncs_per_token")
                base_spt = summ[baseline].get("syncs_per_token")
                summ["persistent"][f"syncs_reduction_vs_{baseline}"] = (
                    base_spt / spt if spt and base_spt else None
                )
        print(json.dumps(record), flush=True)

    for name, phase_env in plan:
        left = deadline - (time.monotonic() - t0)
        if left <= 5:
            record["phases"][name] = {
                "error": "global deadline exhausted before phase start"
            }
            emit()
            continue
        cmd = [sys.executable, os.path.abspath(__file__)] + sys.argv[1:]
        env = dict(os.environ, TDX_SERVE_CHILD="1", **phase_env)
        n_dev = max(
            args.tp,
            args.migrate_tp_to or 1,
            # the disaggregated fleet leg builds its prefill engine on a
            # 2-device ('tp',) mesh regardless of --tp
            2 if (args.fleet is not None and args.disaggregate) else 1,
        )
        if n_dev > 1 and env.get("JAX_PLATFORMS") == "cpu":
            # the CPU smoke needs enough virtual devices for the mesh
            # (the migrate phase may need MORE than --tp for its target);
            # the flag must be set before the child imports jax
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={n_dev}"
            ).strip()
        phase: dict = {}
        try:
            proc = subprocess.run(
                cmd, env=env, timeout=left, capture_output=True, text=True
            )
            lines = [
                ln for ln in (proc.stdout or "").splitlines() if ln.strip()
            ]
            if lines:
                try:
                    phase = json.loads(lines[-1])
                except ValueError:
                    phase = {"error": f"unparseable child record: {lines[-1][:200]}"}
            else:
                phase = {
                    "error": f"child exited {proc.returncode} with no "
                    f"record: {(proc.stderr or '')[-400:]}"
                }
        except subprocess.TimeoutExpired:
            phase = {"error": f"deadline share ({left:.0f}s) exceeded"}
            record["phases"][name] = phase
            emit()
            break  # nothing is left of the deadline for later phases
        record["phases"][name] = phase
        emit()  # full record after EVERY phase — the consumer contract

    _write_artifact(record, args.artifact)
    # perf-sentinel hook: normalize this run into LEDGER.jsonl rows so
    # the trajectory (and the nightly gate's baselines) grow with every
    # run — never raises, disabled by TDX_LEDGER=0
    _ledger().append_record_rows(record, source="bench_serve")
    failed = [
        name
        for name, p in sorted(record["phases"].items())
        if "error" in p
    ] or (["no phase ran"] if not record["phases"] else [])
    if failed:
        # the record stays parseable on stdout either way, but a phase
        # error FAILS the run: a broken path must never exit 0
        print(f"bench_serve: failed phases: {failed}", file=sys.stderr)
        sys.exit(1)


def _write_artifact(record: dict, artifact: str = None) -> None:
    """Persist the record as BENCH_SERVE_<CPU|TPU>.json (or the --artifact
    override) — but never let a
    run that produced no phase evidence misfile or clobber real evidence
    (the KERNEL_ACCEPT guard convention): the platform comes from what
    the phases actually REPORTED, falling back to the requested platform,
    and an all-error record never replaces an existing error-free one."""
    phases = record["phases"].values()
    if artifact:
        out_path = os.path.abspath(artifact)
    else:
        reported = {p.get("platform") for p in phases if p.get("platform")}
        if not reported:
            return  # nothing reported where it ran: print-only, no file
        plat = "CPU" if "cpu" in reported else "TPU"
        out_path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            f"BENCH_SERVE_{plat}.json",
        )
    all_error = all("error" in p for p in phases) or not record["phases"]
    if all_error and os.path.exists(out_path):
        try:
            with open(out_path) as f:
                prior = json.load(f)
            if any(
                "error" not in p for p in prior.get("phases", {}).values()
            ):
                return  # keep the prior good evidence; stdout has this run
        except (OSError, ValueError):
            pass  # unreadable prior record: replacing it loses nothing
    try:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
    except OSError:
        pass  # the stdout record is the contract; the file is a courtesy


def _phase_setup(args, **extra) -> tuple:
    """Shared child-phase bring-up: place the compile cache and build
    the common record header.  One definition for every phase flavor,
    so a setup change (env knob, dtype rule) can never leave one phase
    benchmarking a differently-configured engine."""
    import jax

    from torchdistx_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    plat = jax.devices()[0].platform
    if os.environ.get("TDX_SERVE_TRACE_DIR"):
        # host tracing for this phase: spans land in the per-phase
        # Perfetto file _dump_obs writes at the end of the child
        from torchdistx_tpu import obs

        obs.enable_tracing()
    k_chunk = int(os.environ.get("TDX_SERVE_CHUNK", "1"))
    mode = (
        "persistent"
        if os.environ.get("TDX_SERVE_PHASE") == "persistent"
        else "chunked"
    )
    name = os.environ.get("TDX_SERVE_MODEL", "llama_1b")
    record: dict = {
        "bench": "serve",
        "model": name,
        "platform": plat,
        "device_kind": jax.devices()[0].device_kind,
        "requests": args.requests,
        "max_new_tokens": args.max_new,
        "num_slots": args.slots,
        "decode_chunk": k_chunk,
        "decode_mode": mode,
        # ALWAYS emitted (1 when single-chip): a ledger workload key, so
        # TP-mesh counter rows can never collide with single-chip pins
        "mesh": args.tp,
        **extra,
    }
    if args.kv_dtype:
        # a ledger workload key ONLY when requested: int8 fingerprints
        # get their own family while default-run pins stay byte-stable
        record["kv_dtype"] = args.kv_dtype
    return record, name, k_chunk, plat


def _mesh_kwargs(args, tp: int = None) -> dict:
    """``ServeEngine(mesh=...)`` kwargs for the requested TP degree
    (empty when tp is 1: the single-chip engine path stays the
    reference).  ``tp`` overrides ``args.tp`` — the migrate phase builds
    its target engine on a different degree."""
    tp = args.tp if tp is None else tp
    if tp <= 1:
        return {}
    import numpy as np

    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < tp:
        raise RuntimeError(
            f"--tp {tp} needs {tp} devices, found {len(devs)}"
        )
    return {"mesh": Mesh(np.asarray(devs[:tp]), ("tp",))}


def _kv_kwargs(args, kv_dtype: str = None) -> dict:
    """``ServeEngine(kv_dtype=...)`` kwargs (empty without ``--kv-dtype``,
    so default phases build byte-identical engines).  ``kv_dtype``
    overrides ``args.kv_dtype`` — the kv_quant phase builds its bfloat16
    baseline engine beside the quantized one."""
    kv = args.kv_dtype if kv_dtype is None else kv_dtype
    return {"kv_dtype": kv} if kv else {}


def _kv_entry_wire_bytes(entry, g: int) -> int:
    """Ring all-gather wire for ONE slot row (or page) of one layer's
    full cache entry at gather group ``g``: ``unit * (g-1)/g`` summed
    per array — the ``(k, v)`` pair, plus the f32 scale arrays when the
    cache is quantized, each priced at its OWN dtype (the int8 closed
    form's dtype factor)."""
    import numpy as np

    if g <= 1:
        return 0
    total = 0
    for a in entry:
        unit = int(np.prod(a.shape[1:])) * np.dtype(a.dtype).itemsize
        total += unit * (g - 1) // g
    return total


def _embed_cost(record: dict, engine) -> None:
    """Cost-observatory fields of one phase record (obs.cost): the
    per-program CostCards (ledger counter rows + the --cost CI schema
    check read these), the live HBM capacity plan the admission gate
    consults, and per-span roofline/MFU attribution — prefill and
    decode each get their own measured MFU instead of one end-of-run
    number.  A span's MFU is only computed when ONE program served it
    (several prefill buckets mixing would attribute dishonestly) and a
    chip peak is known (None on the CPU smoke, by design).  The
    persistent while-loop program's XLA FLOP count covers ONE loop
    body, so its executions count is ``loop_iterations`` (bodies run),
    not ``decode_dispatches`` (ring drains) — using drains would
    understate MFU by the iterations-per-drain factor; the remaining
    per-dispatch caveat is flagged in the entry's note."""
    from torchdistx_tpu.obs.cost import span_mfu
    from torchdistx_tpu.utils.benchmarks import PEAK_BF16_FLOPS

    record["cost_cards"] = engine.cost_book.to_json()
    record["memory_plan"] = engine.memory_plan()
    # no peak on record for the device kind -> no span MFU at all
    peak = PEAK_BF16_FLOPS.get(record.get("device_kind"))
    m = engine.metrics
    cards = engine.cost_book.cards()
    spans = {}
    groups = {
        "prefill": (
            "serve/prefill",
            m.counters["prefill_calls"],
            m.prefill_s.total,
        ),
        "decode": (
            "serve/decode",
            m.counters["decode_dispatches"],
            m.decode_s.total,
        ),
    }
    for span, (prefix, execs, secs) in groups.items():
        cs = [c for n, c in sorted(cards.items()) if n.startswith(prefix)]
        if not cs:
            continue
        entry: dict = {
            "programs": [c.program for c in cs],
            "executions": execs,
            "span_s": round(secs, 4),
        }
        if len(cs) == 1:
            entry["flops_per_dispatch"] = cs[0].flops
            if "persistent" in cs[0].program:
                # the card counts ONE while_loop body: executions for
                # the MFU must be bodies run (loop_iterations), never
                # ring drains
                entry["executions"] = m.counters["loop_iterations"]
                entry["note"] = (
                    "while-loop program: XLA counts one loop body; "
                    "executions = loop_iterations, and "
                    "flops_per_dispatch understates a multi-iteration "
                    "dispatch"
                )
            entry["mfu"] = span_mfu(
                cs[0],
                executions=entry["executions"],
                seconds=secs,
                peak_flops=peak,
            )
        spans[span] = entry
    record["roofline"] = spans


def _dump_obs(record: dict, engine, tag: str) -> None:
    """Per-phase observability artifacts (opt-in via
    ``TDX_SERVE_TRACE_DIR``): a Perfetto trace of the phase — tracer
    spans + one lifecycle track per finished request — and the
    Prometheus exposition of the phase's final metrics.  Paths and a
    small summary are embedded in the phase record (additive keys;
    existing consumers parse the last line unchanged)."""
    out_dir = os.environ.get("TDX_SERVE_TRACE_DIR")
    if not out_dir:
        return
    from torchdistx_tpu import obs

    os.makedirs(out_dir, exist_ok=True)
    trace_path = os.path.join(out_dir, f"{tag}_trace.json")
    engine.dump_trace(trace_path)
    finished = engine.finished_requests()
    record["trace_path"] = trace_path
    record["trace_summary"] = {
        "requests": len(finished),
        "lifecycle_events": sum(len(r.events) for r in finished),
        "tracer_spans": len(obs.get_tracer().events()),
    }
    registry = obs.MetricsRegistry()
    registry.register_collector(engine.metrics.collector())
    # the cost observatory's third export: the same cards the record
    # embeds, as tdx_cost_*{program=...} gauges on the exposition
    registry.register_collector(engine.cost_book.collector())
    # numerics observatory: tdx_numerics_*{site=...} gauges — only
    # digest engines register it, so default phases' expositions stay
    # byte-stable; check_obs_artifacts --numerics cross-checks these
    # samples against the embedded book exactly
    book = getattr(engine, "numerics_book", None)
    if getattr(engine, "numerics", False) and book is not None:
        registry.register_collector(book.collector(), obj=book)
    prom_path = os.path.join(out_dir, f"{tag}_metrics.prom")
    with open(prom_path, "w") as f:
        f.write(registry.render())
    record["metrics_prom_path"] = prom_path


def _build_model(name: str, plat):
    import jax.numpy as jnp

    import torchdistx_tpu as tdx
    from torchdistx_tpu.models import Llama

    dtype = jnp.bfloat16 if plat != "cpu" else jnp.float32
    tdx.manual_seed(0)
    model = tdx.deferred_init(Llama.from_name, name, dtype=dtype)
    tdx.materialize_module(model)
    return model


def _session_selftest(
    args, record, model, name, plat, engine_kw, work, tag
) -> None:
    """``--record``: the phase's incident-time-machine leg.  Re-serves
    the phase's measured workload on a FRESH engine with session
    recording on (a fresh engine because recording must start at
    construction — mid-run ``reset_metrics`` would fold negative
    counter deltas), writes the ``tdx-session-v1`` black box, then
    self-replays it in-process and embeds the verdict.  STRICT: a
    non-match verdict is a phase ``error``.  The recording engine's
    counters are compared against the unrecorded measured run's — the
    zero-overhead evidence (recording adds no host syncs, no
    dispatches, nothing countable).

    Call AFTER ``record['recompile_measure']`` and ``_dump_obs`` so
    this leg's compiles never pollute the measured compile count."""
    if not getattr(args, "record", False):
        return
    from torchdistx_tpu.obs.blackbox import (
        geometry_kwargs,
        load_session,
        replay_session,
    )
    from torchdistx_tpu.serve import ServeEngine

    rec, path = _session_recorder(args, name, plat, tag)
    engine = ServeEngine(model, record=rec, **engine_kw)
    engine.run([dict(w) for w in work])
    rec.close()

    events, _notes = load_session(path)

    def engine_factory(rep_rec, geom):
        # recorded geometry wins; non-geometry extras (mesh, numerics)
        # come from the phase's own kwargs
        return ServeEngine(
            model, record=rep_rec, **{**engine_kw, **geometry_kwargs(geom)}
        )

    verdict = replay_session(events, engine_factory=engine_factory)

    counters = {
        k: v
        for k, v in engine.metrics.counters.items()
        if isinstance(v, int)
    }
    _embed_session_verdict(record, path, verdict, counters)


def _session_recorder(args, name, plat, tag):
    """The selftest recording sink: one ``tdx-session-v1`` file per
    phase under ``TDX_SERVE_TRACE_DIR`` (tmpdir fallback), seeded with
    the ``model_spec`` event ``scripts/replay_session.py`` rebuilds
    the model from."""
    from torchdistx_tpu.obs.blackbox import SessionRecorder

    out_dir = os.environ.get("TDX_SERVE_TRACE_DIR") or tempfile.gettempdir()
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"session_{tag}_{os.getpid()}.jsonl")
    if os.path.exists(path):
        os.remove(path)
    rec = SessionRecorder(path, enabled=True)
    rec.record(
        "model_spec",
        name=name,
        seed=0,
        dtype="bfloat16" if plat != "cpu" else "float32",
    )
    return rec, path


def _embed_session_verdict(record, path, verdict, counters) -> None:
    """Embed the self-replay verdict + the zero-overhead counter pin
    in the phase record; STRICT turns either failure into the phase
    ``error``."""
    measured = ((record.get("metrics") or {}).get("counters")) or {}
    unequal = {
        k: (counters.get(k), measured.get(k))
        for k in sorted(counters)
        if counters.get(k) != measured.get(k)
    }
    record["session"] = {
        "path": path,
        "drains": verdict.get("drains_recorded"),
        "verdict": verdict.get("verdict"),
        "match": bool(verdict.get("match")),
        "first_divergence": verdict.get("first_divergence"),
        "counters_equal": not unequal,
        "counters_unequal": unequal,
    }
    if not verdict.get("match") and "error" not in record:
        d = verdict.get("first_divergence") or {}
        record["error"] = (
            f"session replay {verdict.get('verdict')}: first divergence "
            f"at drain seq={d.get('seq')} tick={d.get('tick')} "
            f"counters={d.get('counters')} rids={d.get('rids')}"
        )
    elif unequal and "error" not in record:
        record["error"] = (
            "session recording moved engine counters vs the unrecorded "
            f"measured run (recorded, measured): {unequal}"
        )


def _session_selftest_fleet(
    args, record, model, name, plat, build, work, tag, *, policy="affinity"
) -> None:
    """``--record``, fleet posture: re-drives the phase's workload
    through a FRESH recording fleet (same online arrival, same policy
    as the measured affinity side), writes the ``tdx-session-v1`` black
    box with the FLEET as the driver (per-replica geometry, routing
    ticks), then self-replays it from the recording alone — each
    replica rebuilt from ITS geometry event, the shared model from
    ``model_spec``.  Same STRICT verdict and zero-overhead counter pin
    as the single-engine selftest, against the fleet's summed
    aggregate."""
    if not getattr(args, "record", False):
        return
    from torchdistx_tpu.obs.blackbox import (
        geometry_kwargs,
        load_session,
        replay_session,
    )
    from torchdistx_tpu.serve import ServeEngine, ServeFleet

    rec, path = _session_recorder(args, name, plat, tag)
    fleet = ServeFleet(
        [build() for _ in range(int(args.fleet))],
        policy=policy,
        record=rec,
    )
    for w in work:  # online arrival, like the measured A/B
        fleet.submit(**dict(w))
        fleet.step()
    while fleet.step():
        pass
    rec.close()

    events, _notes = load_session(path)

    def engine_factory(rep_rec, geom):
        return ServeEngine(model, record=rep_rec, **geometry_kwargs(geom))

    verdict = replay_session(events, engine_factory=engine_factory)
    counters = {
        k: v
        for k, v in fleet.metrics_json()["counters"].items()
        if isinstance(v, int)
    }
    _embed_session_verdict(record, path, verdict, counters)


def _child(args) -> None:
    """One phase: one engine at one decode_chunk (or the persistent
    loop), warm then measure."""
    record, name, k_chunk, plat = _phase_setup(args)
    persistent = record["decode_mode"] == "persistent"

    import numpy as np

    from torchdistx_tpu import obs
    from torchdistx_tpu.serve import ServeEngine

    # counts every XLA compile, attributed serve/prefill vs serve/decode
    # by the engine's timed_annotation regions; warm-up compiles and
    # steady-state compiles (expected: zero) are reported separately
    watcher = obs.RecompileWatcher()
    try:
        model = _build_model(name, plat)
        limit = model.cfg.max_seq_len
        max_len = args.max_len or min(limit, 8 * args.max_new)
        engine_kw: dict = dict(decode_chunk=k_chunk)
        if persistent:
            engine_kw = dict(decode_mode="persistent", ring_capacity=args.ring)
        engine = ServeEngine(
            model,
            num_slots=args.slots,
            max_len=max_len,
            **engine_kw,
            **_mesh_kwargs(args),
            **_kv_kwargs(args),
        )
        if persistent:
            record["ring_capacity"] = engine.ring_capacity
        rs = np.random.RandomState(0)
        max_prompt = max(1, min(max_len - args.max_new, max_len // 2))
        prompts = [
            rs.randint(0, 256, (int(n),)).astype(np.int32)
            for n in rs.randint(1, max_prompt + 1, args.requests)
        ]

        # Warm every program the workload can reach PAST the
        # donated-carry layout recompile (CLAUDE.md: never time the
        # second call): two requests per reachable prefill bucket, with
        # enough tokens that the decode program dispatches at least
        # twice (k_chunk + 2 => two chunks past the prefill token; the
        # persistent loop dispatches once per run, so the two warm runs
        # per bucket cover its second-call recompile too), then reset
        # metrics so TTFT/prefill/decode histograms measure steady-state
        # dispatch, not XLA compiles.
        warm_new = min(max(3, k_chunk + 2), max_len - max_prompt)
        for b in engine.prefill_buckets:
            plen = max(1, min(b, max_prompt))
            for j in range(2):
                # two SERIAL runs of a two-request batch: the repeat
                # covers the donated-carry second-call recompile even
                # when one persistent loop drains the whole wave, and
                # the simultaneous pair covers the persistent path's
                # chained pending-first-token splice (its second
                # scatter has a different committed-ness signature
                # than the first)
                engine.run([
                    {"prompt": rs.randint(0, 256, (plen,)).astype(np.int32),
                     "max_new_tokens": warm_new,
                     "temperature": args.temperature,
                     "seed": 10**6 + 2 * j + i}
                    for i in range(2)
                ])
            if plen < b:
                break  # larger buckets unreachable by this workload
        engine.reset_metrics()
        record["recompile_warmup"] = watcher.snapshot()
        watcher.reset()  # the measured window must compile NOTHING

        from torchdistx_tpu.obs.comm import comm_audit

        work = [
            {
                "prompt": p,
                "max_new_tokens": args.max_new,
                "temperature": args.temperature,
                "seed": i,
            }
            for i, p in enumerate(prompts)
        ]
        t0 = time.perf_counter()
        with comm_audit() as comm_prof:
            results = engine.run([dict(w) for w in work])
        wall = time.perf_counter() - t0

        # per-phase collective traffic (tdx-comm-v1): the engine's
        # closed-form TP all-reduce accounting — empty at --tp 1
        record["comm"] = comm_prof.to_json()
        record["metrics"] = engine.metrics.to_json()
        _embed_cost(record, engine)
        # compiles DURING the measured window: nonzero means the warm-up
        # missed a program and the timings above include XLA compiles
        record["recompile_measure"] = watcher.snapshot()
        record.update(
            max_len=max_len,
            drain_wall_s=round(wall, 3),
            compiled_programs=engine.num_compiled_programs(),
            prompt_tokens=int(sum(p.size for p in prompts)),
            finish_reasons=sorted({r.finish_reason for r in results}),
            kv_cache_gb=round(engine.cache.nbytes / 1e9, 3),
        )
        tag = "persistent" if persistent else f"k{k_chunk}"
        _dump_obs(record, engine, tag)
        _session_selftest(
            args,
            record,
            model,
            name,
            plat,
            dict(
                num_slots=args.slots,
                max_len=max_len,
                **engine_kw,
                **_mesh_kwargs(args),
                **_kv_kwargs(args),
            ),
            work,
            tag,
        )
    except Exception as e:  # degraded-but-parseable, bench.py contract
        record["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(record))


def _child_spec(args) -> None:
    """One leg of the self-speculation A/B: a persistent-loop engine at
    ``speculate=K`` (K=0 compiles the classic persistent program — the
    baseline leg) over a repetition-heavy workload, the shape
    prompt-lookup drafting feeds on (vLLM's ngram speculator makes the
    same bet).  The prompts are period-1..4 cycles and every leg draws
    them from the same seeded stream, so the K legs serve the IDENTICAL
    workload and greedy bit-identity (pinned by tests) makes their
    token streams — and therefore token totals — comparable.  The
    headline is iteration economy: ``accepted_tokens_per_iteration``
    must clear 1.0 (flagged ``error`` here otherwise), and the
    supervisor cross-checks strictly-fewer ``loop_iterations`` plus an
    unchanged ``host_syncs`` against the spec0 leg."""
    spec_k = int(os.environ.get("TDX_SERVE_SPECULATE", "0"))
    record, name, k_chunk, plat = _phase_setup(
        args, phase="speculate", speculate=spec_k, spec_ngram=args.spec_ngram
    )
    record["decode_mode"] = "persistent"

    import numpy as np

    from torchdistx_tpu import obs
    from torchdistx_tpu.serve import ServeEngine

    watcher = obs.RecompileWatcher()
    try:
        model = _build_model(name, plat)
        limit = model.cfg.max_seq_len
        # a cycle only earns acceptance once it has RECURRED in the
        # history: give every request enough budget to get past the
        # first occurrence even on the tiny-model smoke geometry
        spec_new = min(max(args.max_new, 24), limit // 2)
        max_len = args.max_len or min(limit, 8 * spec_new)
        engine_kw: dict = dict(
            decode_mode="persistent", ring_capacity=args.ring
        )
        if spec_k:
            engine_kw.update(speculate=spec_k, spec_ngram=args.spec_ngram)
        engine = ServeEngine(
            model,
            num_slots=args.slots,
            max_len=max_len,
            **engine_kw,
            **_mesh_kwargs(args),
            **_kv_kwargs(args),
        )
        record["ring_capacity"] = engine.ring_capacity
        record["max_new_tokens"] = spec_new
        rs = np.random.RandomState(0)
        max_prompt = max(2, min(max_len - spec_new, max_len // 2))
        prompts = []
        for _ in range(args.requests):
            period = int(rs.randint(1, 5))
            pat = rs.randint(0, 256, (period,)).astype(np.int32)
            plen = int(rs.randint(period + 1, max_prompt + 1))
            prompts.append(np.tile(pat, -(-plen // period))[:plen])

        # warm every reachable program past the donated-carry recompile
        # (CLAUDE.md: never time the second call) — same discipline as
        # the fused/persistent phases
        warm_new = min(8, max_len - max_prompt)
        for b in engine.prefill_buckets:
            plen = max(1, min(b, max_prompt))
            for j in range(2):
                engine.run([
                    {"prompt": rs.randint(0, 256, (plen,)).astype(np.int32),
                     "max_new_tokens": warm_new,
                     "temperature": args.temperature,
                     "seed": 10**6 + 2 * j + i}
                    for i in range(2)
                ])
            if plen < b:
                break
        engine.reset_metrics()
        record["recompile_warmup"] = watcher.snapshot()
        watcher.reset()  # the measured window must compile NOTHING

        from torchdistx_tpu.obs.comm import comm_audit

        work = [
            {
                "prompt": p,
                "max_new_tokens": spec_new,
                "temperature": args.temperature,
                "seed": i,
            }
            for i, p in enumerate(prompts)
        ]
        t0 = time.perf_counter()
        with comm_audit() as comm_prof:
            results = engine.run([dict(w) for w in work])
        wall = time.perf_counter() - t0

        record["comm"] = comm_prof.to_json()
        m = engine.metrics.to_json()
        record["metrics"] = m
        record["accept_rate"] = m["derived"]["accept_rate"]
        record["accepted_tokens_per_iteration"] = m["derived"][
            "accepted_tokens_per_iteration"
        ]
        _embed_cost(record, engine)
        record["recompile_measure"] = watcher.snapshot()
        record.update(
            max_len=max_len,
            drain_wall_s=round(wall, 3),
            compiled_programs=engine.num_compiled_programs(),
            prompt_tokens=int(sum(p.size for p in prompts)),
            finish_reasons=sorted({r.finish_reason for r in results}),
            kv_cache_gb=round(engine.cache.nbytes / 1e9, 3),
        )
        atpi = record["accepted_tokens_per_iteration"]
        if spec_k and not (atpi or 0) > 1.0:
            record["error"] = (
                "speculation accepted no drafts "
                f"(accepted_tokens_per_iteration={atpi})"
            )
        _dump_obs(record, engine, f"spec{spec_k}")
        _session_selftest(
            args,
            record,
            model,
            name,
            plat,
            dict(
                num_slots=args.slots,
                max_len=max_len,
                **engine_kw,
                **_mesh_kwargs(args),
                **_kv_kwargs(args),
            ),
            work,
            f"spec{spec_k}",
        )
    except Exception as e:  # degraded-but-parseable, bench.py contract
        record["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(record))


def _child_prefix(args) -> None:
    """The shared-prefix A/B phase: ONE paged engine, the SAME
    repeated-system-prompt burst twice — cold (empty radix index) then
    warm (index populated by the cold pass).  Metrics reset between
    passes, so each pass's ``to_json()`` is self-contained; the headline
    is warm prefill tokens strictly below cold (suffix-only prefill)."""
    record, name, k_chunk, plat = _phase_setup(
        args, phase="prefix_share", page_size=args.page_size
    )

    import numpy as np

    from torchdistx_tpu import obs
    from torchdistx_tpu.serve import ServeEngine

    watcher = obs.RecompileWatcher()
    try:
        model = _build_model(name, plat)
        limit = model.cfg.max_seq_len
        ps = args.page_size
        max_len = args.max_len or min(limit, 8 * args.max_new)
        # paged geometry needs max_len | page_size: round UP (capped at
        # the model limit's own page multiple) — rounding down could
        # zero out a small --max-new budget entirely
        max_len = min(-(-max_len // ps) * ps, limit - limit % ps)
        engine = ServeEngine(
            model,
            num_slots=args.slots,
            max_len=max_len,
            decode_chunk=k_chunk,
            page_size=ps,
            **_mesh_kwargs(args),
            **_kv_kwargs(args),
        )
        # the production shape: every request opens with the same long
        # system prompt, tails differ
        rs = np.random.RandomState(0)
        max_prompt = max(1, min(max_len - args.max_new, max_len // 2))
        sys_len = max(ps, (max_prompt // 2) - (max_prompt // 2) % ps)
        system = rs.randint(0, 256, (sys_len,)).astype(np.int32)
        burst = []
        for i in range(args.requests):
            tail = rs.randint(
                0, 256, (1 + int(rs.randint(0, max(1, max_prompt - sys_len))),)
            ).astype(np.int32)
            burst.append(
                {
                    "prompt": np.concatenate([system, tail])[:max_prompt],
                    "max_new_tokens": args.max_new,
                    "temperature": args.temperature,
                    "seed": i,
                }
            )

        def run_pass():
            engine.reset_metrics()
            t0 = time.perf_counter()
            results = engine.run([dict(r) for r in burst])
            wall = time.perf_counter() - t0
            return {
                "metrics": engine.metrics.to_json(),
                "drain_wall_s": round(wall, 3),
                "finish_reasons": sorted(
                    {r.finish_reason for r in results}
                ),
            }

        # Warm every reachable program past the donated-carry recompile
        # (CLAUDE.md: never time the second call): one throwaway burst
        # compiles the COLD prefill buckets + decode scan, a second
        # compiles the WARM (prefix-hit) prefill family those hits
        # unlock.  Then evict the index back to empty so the timed cold
        # pass is cold of CONTENT while the programs stay compiled —
        # otherwise the warm pass would be charged its own program
        # family's XLA compiles and could read slower than cold.
        engine.run([dict(r) for r in burst])
        engine.run([dict(r) for r in burst])
        engine.prefix_index.evict(engine.pool, engine.pool.capacity)
        record["recompile_warmup"] = watcher.snapshot()
        watcher.reset()  # both timed passes must compile nothing

        from torchdistx_tpu.obs.comm import comm_audit

        with comm_audit() as comm_prof:
            record["cold"] = run_pass()
            record["warm"] = run_pass()
        record["recompile_measure"] = watcher.snapshot()
        # both passes' analytic collective profile (mesh runs)
        record["comm"] = comm_prof.to_json()
        cold_m, warm_m = record["cold"]["metrics"], record["warm"]["metrics"]
        record["tokens_prefilled_cold"] = cold_m["counters"][
            "tokens_prefilled"
        ]
        record["tokens_prefilled_warm"] = warm_m["counters"][
            "tokens_prefilled"
        ]
        record["prefill_calls_cold"] = cold_m["counters"]["prefill_calls"]
        record["prefill_calls_warm"] = warm_m["counters"]["prefill_calls"]
        record["prefix_hit_rate_warm"] = warm_m["derived"]["prefix_hit_rate"]
        record["pages_in_use_hwm"] = warm_m["gauges"]["pages_in_use_hwm"]
        # the phase's whole point: the warm cache must shrink prefill
        # work — surface a broken prefix cache as a phase error so the
        # STRICT nightly fails on it
        if not record["tokens_prefilled_warm"] < record["tokens_prefilled_cold"]:
            record["error"] = (
                "warm prefix cache did not reduce prefill tokens "
                f"({record['tokens_prefilled_warm']} vs "
                f"{record['tokens_prefilled_cold']} cold)"
            )
        # the warm pass's full metrics double as the phase metrics for
        # the shared summary schema
        record["metrics"] = warm_m
        _embed_cost(record, engine)
        _dump_obs(record, engine, "prefix_share")
    except Exception as e:  # degraded-but-parseable, bench.py contract
        record["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(record))


def _child_chunked_prefill(args) -> None:
    """The chunked-prefill A/B phase: short requests decoding, then ONE
    long-prompt admission mid-flight — unchunked (the long prefill is a
    single dispatch that stalls every active slot) vs chunked at
    threshold T (the engine interleaves a decode dispatch between
    chunks).  The headline is a count: every short request receives
    tokens in the decode dispatches that run between the long prompt's
    chunks (the requests' ``first_decode_cycle`` / ``last_decode_cycle``);
    the phase flags ``error`` when none does, so the STRICT nightly
    catches a broken interleave.  Token streams must
    be bit-identical between the two engines (chunking may never change
    what a request decodes, only when the host sees it)."""
    t_chunk = int(args.chunked_prefill)
    record, name, k_chunk, plat = _phase_setup(
        args, phase="chunked_prefill", chunked_prefill=t_chunk
    )

    import numpy as np

    from torchdistx_tpu import obs
    from torchdistx_tpu.serve import ServeEngine

    watcher = obs.RecompileWatcher()
    try:
        model = _build_model(name, plat)
        limit = model.cfg.max_seq_len
        max_len = args.max_len or min(limit, 8 * args.max_new)
        if t_chunk >= max_len:
            raise ValueError(
                f"--chunked-prefill {t_chunk} must be < max_len {max_len}"
            )
        # one bucket per side of the threshold: long prompts pad to
        # max_len (the stall being A/B'd), chunks dispatch through the
        # T-bucket program
        buckets = (t_chunk, max_len)
        # geometry: the shorts must still be DECODING through the whole
        # admission window — two settled chunks before the admission
        # (1 + 2K tokens) plus one chunk per interleave — while the long
        # request only needs its first token, so it gets the minimum
        # budget and the longest admissible prompt
        short_len = max(1, t_chunk // 2)
        short_new = min(
            max_len - short_len,
            max(args.max_new, 4 * k_chunk + 4),
        )
        long_new = 2
        long_len = max_len - long_new
        if long_len <= t_chunk:
            raise ValueError(
                f"max_len {max_len} leaves no long prompt above the "
                f"chunk threshold {t_chunk}"
            )
        n_short = max(1, min(args.slots - 1, 4))
        rs = np.random.RandomState(0)
        shorts = [
            rs.randint(0, 256, (short_len,)).astype(np.int32)
            for _ in range(n_short)
        ]
        long_prompt = rs.randint(0, 256, (long_len,)).astype(np.int32)

        def scenario(engine):
            """Shorts first, two settled decode chunks, then the long
            admission; returns (short_results, long_result)."""
            hs = [
                engine.submit(
                    p,
                    max_new_tokens=short_new,
                    temperature=args.temperature,
                    seed=100 + i,
                )
                for i, p in enumerate(shorts)
            ]
            engine.step()
            engine.step()
            hl = engine.submit(
                long_prompt,
                max_new_tokens=long_new,
                temperature=args.temperature,
                seed=7,
            )
            while engine.step():
                pass
            return [h.result() for h in hs], hl.result()

        def rode_interleaved(short_results, long_result, interleaved):
            """Whether every short request received tokens BETWEEN the
            long prompt's chunks, by count: the long request's first
            decode block is the first dispatch after its last chunk, the
            ``interleaved`` dispatches before it ran between its chunks,
            and a short request rode them if its own blocks reach from
            before the first of them to the last (the requests' decode
            cycle numbers; the per-tick ``decode_chunk`` events whose
            timestamps gave ``max_gap_s_*`` went in PR 38)."""
            first = long_result.first_decode_cycle
            if first is None or interleaved < 1:
                return False
            return all(
                r.first_decode_cycle is not None
                and r.first_decode_cycle < first - interleaved
                and r.last_decode_cycle >= first - 1
                for r in short_results
            )

        def run_side(chunked: bool):
            engine = ServeEngine(
                model,
                num_slots=args.slots,
                max_len=max_len,
                decode_chunk=k_chunk,
                prefill_buckets=buckets,
                chunked_prefill=t_chunk if chunked else None,
                **_mesh_kwargs(args),
                **_kv_kwargs(args),
            )
            # warm both prefill buckets (+ the chunked warm-prefill
            # program) and the decode program past the donated-carry
            # second-call recompile: the full scenario, twice
            scenario(engine)
            scenario(engine)
            # metrics and the comm profile are reset so the embedded
            # (deterministic, gated) counters cover exactly ONE scenario
            engine.reset_metrics()
            watcher.reset()
            with comm_audit() as comm_prof:
                s, l = scenario(engine)
            return engine, s, l, comm_prof

        from torchdistx_tpu.obs.comm import comm_audit

        eng_a, shorts_a, long_a, _ = run_side(chunked=False)
        eng_b, shorts_b, long_b, comm_b = run_side(chunked=True)
        record["recompile_measure"] = watcher.snapshot()
        # the chunked side's analytic collective profile (mesh runs)
        record["comm"] = comm_b.to_json()

        mb = eng_b.metrics.to_json()
        record["interleaved_dispatches"] = mb["counters"].get(
            "prefill_interleaved_dispatches", 0
        )
        record["shorts_rode_interleaved"] = rode_interleaved(
            shorts_b, long_b, record["interleaved_dispatches"]
        )
        record["prefill_chunks"] = mb["counters"].get("prefill_chunks", 0)
        streams_equal = all(
            np.array_equal(ra.tokens, rb.tokens)
            for ra, rb in zip(shorts_a, shorts_b)
        ) and np.array_equal(long_a.tokens, long_b.tokens)
        record["streams_identical"] = streams_equal
        record["max_len"] = max_len
        record["long_prompt_tokens"] = int(long_len)
        # the chunked engine's metrics double as the phase metrics
        record["metrics"] = mb
        _embed_cost(record, eng_b)
        if not streams_equal:
            record["error"] = (
                "chunked prefill changed a token stream — interleaving "
                "must be latency-only"
            )
        elif record["interleaved_dispatches"] < 1:
            record["error"] = (
                "chunked prefill never interleaved a decode dispatch "
                f"(long prompt {long_len} tokens, threshold {t_chunk})"
            )
        elif not record["shorts_rode_interleaved"]:
            record["error"] = (
                "the short requests received no tokens between the long "
                "prompt's chunks"
            )
        _dump_obs(record, eng_b, "chunked_prefill")
    except Exception as e:  # degraded-but-parseable, bench.py contract
        record["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(record))


def _child_migrate(args) -> None:
    """The elastic-migration phase (ISSUE 12): a tp=``--tp`` engine is
    drained mid-decode and ``migrate_to()``'d onto a tp=``--migrate-tp-to``
    engine with a different slot count.  The phase flags ``error`` unless
    every request completes (zero drops), the greedy token streams are
    BIT-identical to an undrained run on the source shape, and the
    migration's wire bytes match the ``parallel/reshard.py`` ring closed
    form — the counters land as ledger rows under workload key
    ``mesh_to`` so ``perf_gate.py --strict`` pins each shape pair."""
    tp_to = int(args.migrate_tp_to)
    record, name, k_chunk, plat = _phase_setup(
        args, phase="migrate", mesh_to=tp_to
    )

    import numpy as np

    from torchdistx_tpu.obs.comm import comm_audit
    from torchdistx_tpu.serve import ServeEngine

    try:
        model = _build_model(name, plat)
        limit = model.cfg.max_seq_len
        max_len = args.max_len or min(limit, 8 * args.max_new)
        bucket = 16
        if max_len <= bucket:
            raise ValueError(
                f"max_len {max_len} leaves no decode room past the "
                f"{bucket}-token prefill bucket"
            )
        max_new = min(args.max_new, max_len - bucket)
        n_req = max(2, min(args.requests, args.slots + 2))
        rs = np.random.RandomState(0)
        prompts = [
            rs.randint(0, 256, (int(rs.randint(5, bucket)),)).astype(np.int32)
            for _ in range(n_req)
        ]
        work = [
            dict(prompt=p, max_new_tokens=max_new, temperature=0.0)
            for p in prompts
        ]

        def build(tp, slots):
            return ServeEngine(
                model,
                num_slots=slots,
                max_len=max_len,
                decode_chunk=k_chunk,
                prefill_buckets=(bucket,),
                **_mesh_kwargs(args, tp=tp),
                **_kv_kwargs(args),
            )

        # undrained reference on the source shape: the bit-identity oracle
        ref_tokens = [
            r.tokens for r in build(args.tp, args.slots).run(work)
        ]

        src = build(args.tp, args.slots)
        dst = build(tp_to, args.slots + 1)  # a DIFFERENT slot count
        handles = [src.submit(**w) for w in work]
        # decode just far enough that the drain suspends requests
        # MID-stream (the KV handoff being pinned) — never to completion
        for _ in range(max(1, (max_new - 1) // (2 * k_chunk))):
            src.step()
        t0 = time.monotonic()
        src.drain()
        with comm_audit() as prof:
            summary = src.migrate_to(dst)
        record["migrate_s"] = round(time.monotonic() - t0, 6)
        while dst.step():
            pass

        results = [h.result() for h in handles]
        streams_equal = all(
            np.array_equal(r.tokens, ref)
            for r, ref in zip(results, ref_tokens)
        )
        record["streams_identical"] = streams_equal
        record["migrate_summary"] = summary
        record["max_len"] = max_len
        record["comm"] = prof.to_json()
        # the ring closed form, computed independently of the engine:
        # gather group g = tp_from / gcd(tp_from, tp_to), one all-gather
        # per migrated slot row per layer per cache array at unit*(g-1)/g
        # — summed over the layer's FULL entry (k/v plus the f32 scale
        # arrays of a quantized cache, each at its own dtype width)
        g = max(1, args.tp // int(np.gcd(args.tp, tp_to)))
        expect = (
            summary["migrated_running"]
            * len(src.cache.kv)
            * _kv_entry_wire_bytes(src.cache.kv[0], g)
        )
        # the target finishes the streams, so its metrics are the phase
        # metrics; graft the source-side migration counters in so ONE
        # counter dict carries the whole pinned footprint
        mb = dst.metrics.to_json()
        for cname in ("migration_wire_bytes", "requests_migrated_out"):
            mb["counters"][cname] = src.metrics.counters[cname]
        mb["counters"]["migration_collectives"] = summary["collectives"]
        record["metrics"] = mb
        _embed_cost(record, dst)
        if not streams_equal:
            record["error"] = (
                "migration changed a token stream — the handoff must be "
                "value-exact"
            )
        elif summary["migrated_running"] < 1:
            record["error"] = (
                "nothing was suspended mid-stream — the workload finished "
                "before drain(), so the phase pinned no KV handoff"
            )
        elif any(r.finish_reason != "length" for r in results):
            record["error"] = (
                "a migrated request was dropped or cut short: "
                f"{[r.finish_reason for r in results]}"
            )
        elif summary["wire_bytes"] != expect:
            record["error"] = (
                f"migration wire bytes {summary['wire_bytes']} != ring "
                f"closed form {expect} (tp {args.tp}->{tp_to}, g={g})"
            )
        elif int(prof.wire_bytes()) != summary["wire_bytes"]:
            record["error"] = (
                f"comm audit wire {int(prof.wire_bytes())} disagrees with "
                f"the migration summary {summary['wire_bytes']}"
            )
        _dump_obs(record, dst, "migrate")
    except Exception as e:  # degraded-but-parseable, bench.py contract
        record["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(record))


def _child_kv_quant(args) -> None:
    """The ``--kv-dtype`` A/B (ISSUE 17 tentpole evidence): one
    bfloat16-cache baseline engine and one ``--kv-dtype`` engine serve
    the SAME greedy workload, and the phase flags ``error`` unless
    (int8) the ``memory_plan()`` KV pool is EXACTLY halved (the
    double-the-pages factor at a constant byte budget), the greedy
    streams stay within the pinned divergence tolerance against the
    model-dtype oracle (``TDX_KV_QUANT_STREAM_TOL``, mean
    longest-common-prefix fraction), decode tok/s holds the baseline
    (``TDX_KV_QUANT_TOKS_SLACK`` — CPU-smoke timing noise gets slack,
    the TPU leg runs tight), and every decode program's cost-card
    ``bytes_accessed`` is STRICTLY lower than its baseline twin (the
    halved-HBM-traffic claim, priced by XLA, not assumed)."""
    record, name, k_chunk, plat = _phase_setup(args, phase="kv_quant")
    kv_dtype = args.kv_dtype or args.kv_quant_ab or "int8"
    record["kv_dtype"] = kv_dtype

    import numpy as np

    from torchdistx_tpu.serve import ServeEngine

    try:
        model = _build_model(name, plat)
        limit = model.cfg.max_seq_len
        max_len = args.max_len or min(limit, 8 * args.max_new)
        n_req = max(2, min(args.requests, 2 * args.slots))
        rs = np.random.RandomState(5)
        max_prompt = max(1, min(max_len - args.max_new, max_len // 2))
        work = [
            dict(
                prompt=rs.randint(0, 256, (int(n),)).astype(np.int32),
                max_new_tokens=args.max_new,
                temperature=0.0,  # the verdict IS greedy-argmax robustness
            )
            for n in rs.randint(1, max_prompt + 1, n_req)
        ]
        record["max_len"] = max_len

        def build(kv):
            # kv=None is the MODEL-dtype oracle — never fall back to
            # --kv-dtype here (that leg must stay unquantized)
            return ServeEngine(
                model,
                num_slots=args.slots,
                max_len=max_len,
                decode_chunk=k_chunk,
                kv_dtype=kv,
                **_mesh_kwargs(args),
            )

        def measure(engine):
            # warm past the donated-carry second-call recompile (two
            # serial runs), then measure steady-state dispatch only
            for _ in range(2):
                engine.run([dict(w) for w in work])
            engine.reset_metrics()
            out = engine.run([dict(w) for w in work])
            return [r.tokens for r in out]

        base = build("bfloat16")
        quant = build(kv_dtype)
        base_tokens = measure(base)
        quant_tokens = measure(quant)

        # the divergence oracle is the MODEL-dtype cache (f32 on the CPU
        # smoke); when the model already runs bf16 the baseline IS the
        # oracle and the third run would duplicate it
        if base.cache.kv[0][0].dtype == np.dtype(model.cfg.dtype):
            ref_tokens = base_tokens
        else:
            ref_tokens = measure(build(None))

        def lcp_frac(a, b):
            a, b = np.asarray(a), np.asarray(b)
            n = min(a.size, b.size)
            neq = np.nonzero(a[:n] != b[:n])[0]
            lcp = int(neq[0]) if neq.size else n
            return lcp / max(1, max(a.size, b.size))

        fracs = [lcp_frac(q, r) for q, r in zip(quant_tokens, ref_tokens)]
        agreement = float(np.mean(fracs)) if fracs else 1.0
        identical = sum(
            np.array_equal(q, r) for q, r in zip(quant_tokens, ref_tokens)
        )
        record["stream_prefix_agreement"] = round(agreement, 4)
        record["streams_identical_frac"] = round(identical / n_req, 4)

        plan_base = base.memory_plan()
        plan_quant = quant.memory_plan()
        record["memory_plan"] = plan_quant
        record["memory_plan_baseline"] = plan_base
        kv_base = plan_base["components"]["kv_cache"]
        kv_quant = plan_quant["components"]["kv_cache"]
        # data-plane halving == doubled page capacity at a constant HBM
        # budget; the f32 scale sidecar is priced separately (kv_scales)
        record["kv_bytes_factor"] = round(kv_base / kv_quant, 4)

        mb = base.metrics.to_json()
        mq = quant.metrics.to_json()
        record["metrics"] = mq
        record["metrics_baseline"] = mb
        toks_base = (mb["derived"] or {}).get("decode_tokens_per_sec")
        toks_quant = (mq["derived"] or {}).get("decode_tokens_per_sec")
        record["decode_tokens_per_sec_baseline"] = toks_base

        _embed_cost(record, quant)
        cards_base = base.cost_book.to_json()
        cards_quant = quant.cost_book.to_json()
        decode_bytes = {}
        for prog, cq in sorted(cards_quant.items()):
            if not prog.startswith("serve/decode"):
                continue
            cb = cards_base.get(prog) or {}
            decode_bytes[prog] = {
                "bytes_accessed": cq.get("bytes_accessed"),
                "bytes_accessed_baseline": cb.get("bytes_accessed"),
            }
        record["decode_bytes_accessed"] = decode_bytes

        stream_tol = float(
            os.environ.get("TDX_KV_QUANT_STREAM_TOL", "0.5")
        )
        # CPU interpret-mode dequant is real ALU work with no HBM saving
        # to offset it (and tiny-workload timings are noisy), so the CPU
        # smoke gets a sanity floor; the TPU leg — where the halved HBM
        # read is the point — runs tight
        toks_slack = float(
            os.environ.get(
                "TDX_KV_QUANT_TOKS_SLACK",
                "0.5" if record["platform"] == "cpu" else "0.05",
            )
        )
        record["stream_tol"] = stream_tol
        record["toks_slack"] = toks_slack
        not_priced = [
            p
            for p, d in decode_bytes.items()
            if not (
                d["bytes_accessed"] and d["bytes_accessed_baseline"]
            )
        ]
        if kv_dtype == "int8" and kv_quant * 2 != kv_base:
            record["error"] = (
                f"int8 KV pool {kv_quant} B is not exactly half the "
                f"bfloat16 pool {kv_base} B in memory_plan()"
            )
        elif agreement < stream_tol:
            record["error"] = (
                f"greedy stream prefix agreement {agreement:.3f} below "
                f"the pinned tolerance {stream_tol}"
            )
        elif not (toks_base and toks_quant):
            record["error"] = "a leg produced no decode throughput figure"
        elif toks_quant < toks_base * (1.0 - toks_slack):
            record["error"] = (
                f"quantized decode {toks_quant:.1f} tok/s fell below the "
                f"baseline {toks_base:.1f} beyond the {toks_slack} slack"
            )
        elif not decode_bytes:
            record["error"] = (
                "no decode cost cards — the bytes_accessed verdict has "
                "no evidence (is TDX_COST_CARDS off?)"
            )
        elif not_priced:
            record["error"] = (
                f"decode programs missing bytes_accessed: {not_priced}"
            )
        elif not all(
            d["bytes_accessed"] < d["bytes_accessed_baseline"]
            for d in decode_bytes.values()
        ):
            worst = {
                p: (d["bytes_accessed"], d["bytes_accessed_baseline"])
                for p, d in decode_bytes.items()
                if d["bytes_accessed"] >= d["bytes_accessed_baseline"]
            }
            record["error"] = (
                "a quantized decode program reads at least as many bytes "
                f"as its bfloat16 twin: {worst}"
            )
        _dump_obs(record, quant, "kv_quant")
        # record + self-replay the QUANTIZED leg (the one the verdict
        # rides on); record["metrics"] is the quant leg's counters, so
        # the zero-overhead comparison lines up
        _session_selftest(
            args,
            record,
            model,
            name,
            plat,
            dict(
                num_slots=args.slots,
                max_len=max_len,
                decode_chunk=k_chunk,
                kv_dtype=kv_dtype,
                **_mesh_kwargs(args),
            ),
            work,
            "kv_quant",
        )
    except Exception as e:  # degraded-but-parseable, bench.py contract
        record["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(record))


def _child_numerics(args) -> None:
    """The numerics-observatory A/B (ISSUE 19 tentpole evidence): one
    digest-off engine and one digest-on engine serve the SAME greedy
    workload, and the phase flags ``error`` unless the streams are
    bit-identical AND every deterministic engine counter is EXACTLY
    equal — digests fuse into the existing jitted programs as one extra
    trailing output and harvest only at existing sync boundaries, so
    enabling them must change neither ``host_syncs`` nor
    ``decode_dispatches`` nor anything else countable.  The on-leg's
    digest book (``tdx-numerics-v1``) is embedded whole; its integer
    fields are reduction-order-invariant counts, so the ledger rows
    they become gate bit-identically across runs in ``perf_gate
    --strict``."""
    record, name, k_chunk, plat = _phase_setup(
        args, phase="numerics", numerics=True
    )

    import numpy as np

    from torchdistx_tpu.serve import ServeEngine

    try:
        model = _build_model(name, plat)
        limit = model.cfg.max_seq_len
        max_len = args.max_len or min(limit, 8 * args.max_new)
        n_req = max(2, min(args.requests, 2 * args.slots))
        rs = np.random.RandomState(5)
        max_prompt = max(1, min(max_len - args.max_new, max_len // 2))
        work = [
            dict(
                prompt=rs.randint(0, 256, (int(n),)).astype(np.int32),
                max_new_tokens=args.max_new,
                temperature=0.0,  # the verdict is bit-identity
            )
            for n in rs.randint(1, max_prompt + 1, n_req)
        ]
        record["max_len"] = max_len

        def build(numerics):
            return ServeEngine(
                model,
                num_slots=args.slots,
                max_len=max_len,
                decode_chunk=k_chunk,
                numerics=numerics,
                **_mesh_kwargs(args),
                **_kv_kwargs(args),
            )

        def measure(engine):
            for _ in range(2):  # warm past the donated-carry recompile
                engine.run([dict(w) for w in work])
            engine.reset_metrics()
            out = engine.run([dict(w) for w in work])
            return [r.tokens for r in out]

        off = build(False)
        on = build(True)
        off_tokens = measure(off)
        on_tokens = measure(on)

        m_off = off.metrics.to_json()
        m_on = on.metrics.to_json()
        record["metrics"] = m_on
        record["metrics_baseline"] = m_off
        book = on.numerics_book
        record["numerics_book"] = book.to_json()
        record["numerics_sites"] = book.sites()
        _embed_cost(record, on)

        identical = all(
            np.array_equal(a, b) for a, b in zip(on_tokens, off_tokens)
        )
        c_off = m_off.get("counters") or {}
        c_on = m_on.get("counters") or {}
        unequal = {
            k: (c_on.get(k), c_off.get(k))
            for k in sorted(set(c_off) | set(c_on))
            if c_on.get(k) != c_off.get(k)
        }
        bad_sites = [
            s
            for s, d in (record["numerics_book"].get("sites") or {}).items()
            if d["count"]
            != d["nonfinite"] + d["zeros"] + sum(d["exp_hist"])
        ]
        if not identical:
            record["error"] = (
                "enabling digests changed a sampled stream — taps must "
                "be identities"
            )
        elif unequal:
            record["error"] = (
                "enabling digests moved engine counters (on vs off): "
                f"{unequal}"
            )
        elif not book.sites():
            record["error"] = (
                "digest-on engine harvested no sites — is the tape "
                "wired into the programs?"
            )
        elif book.digest("logits") is None:
            record["error"] = (
                f"no 'logits' digest (sites: {book.sites()})"
            )
        elif bad_sites:
            record["error"] = (
                "digest partition identity violated (count != nonfinite "
                f"+ zeros + sum(exp_hist)) at: {bad_sites}"
            )
        elif book.first_nonfinite_site() is not None:
            record["error"] = (
                "healthy workload digested a nonfinite at "
                f"{book.first_nonfinite_site()}"
            )
        _dump_obs(record, on, "numerics")
        # record + self-replay the digest-ON leg; numerics is not a
        # geometry field (digests are counter-neutral by ISSUE 19's
        # contract), so the replay engine rebuilds digest-on via the
        # phase kwargs and must still chain bit-identically
        _session_selftest(
            args,
            record,
            model,
            name,
            plat,
            dict(
                num_slots=args.slots,
                max_len=max_len,
                decode_chunk=k_chunk,
                numerics=True,
                **_mesh_kwargs(args),
                **_kv_kwargs(args),
            ),
            work,
            "numerics",
        )
    except Exception as e:  # degraded-but-parseable, bench.py contract
        record["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(record))


def _slo_spec(args):
    """The committed ``--slo`` spec, parsed per use (cheap; children are
    one-shot processes).  None without the flag."""
    if not getattr(args, "slo", None):
        return None
    from torchdistx_tpu.obs.slo import SloSpec

    return SloSpec.from_json(args.slo)


def _eval_slo(args, requests, policy=None):
    """Evaluate the ``--slo`` spec over finished requests into a
    ``tdx-slo-v1`` report (obs/slo.py) — a breached evaluation also
    lands a named ``slo_burn`` flight event in the global recorder.
    None without ``--slo``."""
    spec = _slo_spec(args)
    if spec is None:
        return None
    from torchdistx_tpu.obs.slo import evaluate_slo

    return evaluate_slo(spec, requests, policy=policy)


def _maybe_slo_error(args, record: dict) -> None:
    """``--slo-strict``: a breached report (or a burning window — the
    same condition that fires the flight event) becomes the phase
    ``error``, which the parent's strict path turns into a nonzero
    exit.  A phase already in error keeps its original cause."""
    if not getattr(args, "slo_strict", False) or "error" in record:
        return
    slo = record.get("slo") or {}
    reports = (
        [slo]
        if "schema" in slo
        else [v for v in slo.values() if isinstance(v, dict) and "schema" in v]
    )
    bad = [
        r
        for r in reports
        if r.get("breached") or (r.get("burn") or {}).get("state") != "ok"
    ]
    if bad:
        detail = "; ".join(
            f"{(r.get('spec') or {}).get('name', '?')}"
            f"[{r.get('policy') or '-'}]: attainment="
            f"{(r.get('attainment') or {}).get('overall')} "
            f"target={(r.get('attainment') or {}).get('target')} "
            f"state={(r.get('burn') or {}).get('state')} "
            f"axes={r.get('breached_axes')}"
            for r in bad
        )
        record["error"] = f"SLO breached under --slo-strict: {detail}"


def _dump_obs_fleet(
    record: dict, fleet, tag: str, slo_spec=None, collectors=()
) -> None:
    """``_dump_obs`` for a whole fleet: ONE scrape surface — the
    exposition renders the fleet collector (replica-summed
    ``tdx_serve_*_total`` counters, so ``check_obs_artifacts`` validates
    them against the embedded aggregate ``metrics`` exactly as for a
    single engine, plus per-replica ``tdx_fleet_*`` gauges and latency
    quantile summaries, plus — with ``--slo`` — the ``tdx_slo_*``
    projection) — and ONE merged Perfetto trace
    (``fleet.dump_trace``): per-replica process tracks with every
    request's route/queued/prefill/handoff/decode spans flow-linked on
    its ``trace_id``, retired replicas included."""
    out_dir = os.environ.get("TDX_SERVE_TRACE_DIR")
    if not out_dir:
        return
    from torchdistx_tpu import obs

    os.makedirs(out_dir, exist_ok=True)
    trace_path = os.path.join(out_dir, f"{tag}_trace.json")
    fleet.dump_trace(trace_path)
    finished = fleet.finished_requests()
    record["trace_path"] = trace_path
    record["trace_summary"] = {
        "requests": len(finished),
        "lifecycle_events": sum(len(r.events) for r in finished),
        "tracer_spans": len(obs.get_tracer().events()),
    }
    rep = max(
        fleet.replicas, key=lambda r: len(r.engine.finished_requests())
    )
    registry = obs.MetricsRegistry()
    registry.register_collector(fleet.collector())
    registry.register_collector(rep.engine.cost_book.collector())
    for extra in collectors:
        # e.g. the AutoscaleController's tdx_autoscale_* family — the
        # scale loop scrapes from the SAME surface as the fleet
        registry.register_collector(extra)
    if slo_spec is not None:
        registry.register_collector(
            obs.slo_collector(slo_spec, fleet), obj=fleet
        )
    prom_path = os.path.join(out_dir, f"{tag}_metrics.prom")
    with open(prom_path, "w") as f:
        f.write(registry.render())
    record["metrics_prom_path"] = prom_path


def _fleet_workload(args, n_replicas: int, page_size: int, bucket: int):
    """The shared-prefix arrival stream of the fleet A/B: n_replicas + 1
    prefix groups (one MORE group than replicas, so round-robin can
    never accidentally colocate every group) arriving interleaved —
    request k belongs to group k % groups.  Prefixes are page-aligned
    (two pages each) so a follower's radix match is exact."""
    import numpy as np

    groups = n_replicas + 1
    rs = np.random.RandomState(0)
    prefix_len = 2 * page_size
    prefixes = [
        rs.randint(0, 256, (prefix_len,)).astype(np.int32)
        for _ in range(groups)
    ]
    work = []
    for k in range(args.requests):
        tail = rs.randint(
            0, 256, (1 + int(rs.randint(0, bucket - prefix_len)),)
        ).astype(np.int32)
        work.append(
            {
                "prompt": np.concatenate([prefixes[k % groups], tail])[
                    :bucket
                ],
                "max_new_tokens": None,  # filled by the caller
                "temperature": args.temperature,
                "seed": k,
            }
        )
    return work, groups


def _child_fleet(args) -> None:
    """The fleet routing A/B (ISSUE 13 tentpole): the SAME shared-prefix
    arrival stream through an N-replica ``ServeFleet`` twice — affinity
    (read-only ``match_len`` warmth, headroom tie-break) vs round-robin
    — with fresh engines per policy.  Requests arrive online (one
    ``submit`` + one ``step`` each), so affinity sees the caches its own
    earlier routing warmed.  STRICT errors unless BOTH policies' greedy
    streams are bit-identical to one engine serving the same requests
    (routing decides where, never what) AND affinity's aggregate
    ``prefix_hit_rate`` strictly beats round-robin's."""
    n = int(args.fleet)
    ps = 4  # small pages so a 16-token-bucket prompt spans whole pages
    record, name, k_chunk, plat = _phase_setup(
        args, phase="fleet", fleet=n, page_size=ps
    )

    import numpy as np

    from torchdistx_tpu.serve import ServeEngine, ServeFleet

    try:
        model = _build_model(name, plat)
        limit = model.cfg.max_seq_len
        bucket = 16
        max_len = args.max_len or min(limit, 8 * args.max_new)
        max_len = min(-(-max_len // ps) * ps, limit - limit % ps)
        max_new = min(args.max_new, max_len - bucket)
        work, groups = _fleet_workload(args, n, ps, bucket)
        for w in work:
            w["max_new_tokens"] = max_new
        record["max_len"] = max_len
        record["prefix_groups"] = groups

        def build():
            return ServeEngine(
                model,
                num_slots=args.slots,
                max_len=max_len,
                decode_chunk=k_chunk,
                prefill_buckets=(bucket,),
                page_size=ps,
                **_mesh_kwargs(args),
                **_kv_kwargs(args),
            )

        # the bit-identity oracle: one engine, same requests
        ref_tokens = [r.tokens for r in build().run([dict(w) for w in work])]

        def run_policy(policy):
            fleet = ServeFleet([build() for _ in range(n)], policy=policy)
            t0 = time.perf_counter()
            handles = []
            for w in work:  # online arrival: submit, then one tick
                handles.append(fleet.submit(**dict(w)))
                fleet.step()
            while fleet.step():
                pass
            wall = time.perf_counter() - t0
            results = [h.result() for h in handles]
            ttft = sorted(
                s
                for rep in fleet.replicas
                for s in rep.engine.metrics.ttft_s._samples
            )
            return fleet, {
                "streams": [r.tokens for r in results],
                "hit_rate": fleet.metrics_json()["derived"][
                    "prefix_hit_rate"
                ],
                "ttft_p50_s": (
                    round(ttft[len(ttft) // 2], 6) if ttft else None
                ),
                "wall_s": round(wall, 3),
            }

        fleet_rr, rr = run_policy("round-robin")
        fleet_aff, aff = run_policy("affinity")
        streams_equal = all(
            np.array_equal(s, ref)
            for side in (rr, aff)
            for s, ref in zip(side["streams"], ref_tokens)
        )
        record["streams_identical"] = streams_equal
        record["prefix_hit_rate_affinity"] = aff["hit_rate"]
        record["prefix_hit_rate_round_robin"] = rr["hit_rate"]
        record["ttft_p50_s_affinity"] = aff["ttft_p50_s"]
        record["ttft_p50_s_round_robin"] = rr["ttft_p50_s"]
        record["drain_wall_s"] = aff["wall_s"]
        record["routed_per_replica_affinity"] = [
            r["requests_routed"]
            for r in fleet_aff.metrics_json()["fleet"]["replicas"]
        ]
        # the affinity fleet's aggregate is the phase metrics: its
        # counters (hit/lookup tokens included) are the pinned rows
        record["metrics"] = fleet_aff.metrics_json()
        # the SLO-attainment axis of the A/B: one tdx-slo-v1 report per
        # policy, each over that fleet's own finished-request history
        slo_aff = _eval_slo(
            args, fleet_aff.finished_requests(), policy="affinity"
        )
        if slo_aff is not None:
            record["slo"] = {
                "affinity": slo_aff,
                "round_robin": _eval_slo(
                    args,
                    fleet_rr.finished_requests(),
                    policy="round_robin",
                ),
            }
        busiest = max(
            fleet_aff.replicas,
            key=lambda r: len(r.engine.finished_requests()),
        )
        _embed_cost(record, busiest.engine)
        if not streams_equal:
            record["error"] = (
                "a fleet-routed stream diverged from the single-engine "
                "oracle — routing must decide where, never what"
            )
        elif not (
            aff["hit_rate"] is not None
            and rr["hit_rate"] is not None
            and aff["hit_rate"] > rr["hit_rate"]
        ):
            record["error"] = (
                f"affinity prefix_hit_rate {aff['hit_rate']} does not "
                f"strictly beat round-robin {rr['hit_rate']}"
            )
        _maybe_slo_error(args, record)
        _dump_obs_fleet(record, fleet_aff, "fleet", slo_spec=_slo_spec(args))
        _session_selftest_fleet(
            args, record, model, name, plat, build, work, "fleet"
        )
    except Exception as e:  # degraded-but-parseable, bench.py contract
        record["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(record))


def _child_fleet_drain(args) -> None:
    """The fleet scale-event leg: N replicas mid-workload, one
    ``fleet.remove()`` — the victim drains and its in-flight requests
    redistribute into the survivors (whole-engine ``migrate_to`` fast
    path, or per-request scatter when no single survivor fits).  STRICT
    errors unless every request completes with streams bit-identical to
    an undisturbed single engine — zero drops."""
    n = int(args.fleet)
    record, name, k_chunk, plat = _phase_setup(
        args, phase="fleet_drain", fleet=n
    )

    import numpy as np

    from torchdistx_tpu.serve import ServeEngine, ServeFleet

    try:
        model = _build_model(name, plat)
        limit = model.cfg.max_seq_len
        bucket = 16
        max_len = args.max_len or min(limit, 8 * args.max_new)
        max_new = min(args.max_new, max_len - bucket)
        # scale-down needs headroom: cap the in-flight load at what the
        # survivors can absorb ((n-1) replicas x slots), or the victim's
        # requests would have nowhere to land until slots free up
        n_req = max(2, min(args.requests, (n - 1) * args.slots))
        rs = np.random.RandomState(1)
        work = [
            dict(
                prompt=rs.randint(
                    0, 256, (int(rs.randint(5, bucket)),)
                ).astype(np.int32),
                max_new_tokens=max_new,
                temperature=0.0,
            )
            for _ in range(n_req)
        ]
        record["max_len"] = max_len

        def build():
            return ServeEngine(
                model,
                num_slots=args.slots,
                max_len=max_len,
                decode_chunk=k_chunk,
                prefill_buckets=(bucket,),
                **_mesh_kwargs(args),
                **_kv_kwargs(args),
            )

        ref_tokens = [r.tokens for r in build().run([dict(w) for w in work])]

        fleet = ServeFleet([build() for _ in range(n)], policy="round-robin")
        handles = [fleet.submit(**dict(w)) for w in work]
        # decode just far enough that the remove() lands MID-stream
        for _ in range(max(1, (max_new - 1) // (2 * k_chunk))):
            fleet.step()
        victim = fleet.replicas[0]
        if not victim.engine.scheduler.has_work():
            raise RuntimeError(
                "the victim replica holds no in-flight work — nothing "
                "to redistribute"
            )
        t0 = time.monotonic()
        summary = fleet.remove(victim.rid)
        record["remove_s"] = round(time.monotonic() - t0, 6)
        while fleet.step():
            pass
        results = [h.result() for h in handles]
        streams_equal = all(
            np.array_equal(r.tokens, ref)
            for r, ref in zip(results, ref_tokens)
        )
        record["streams_identical"] = streams_equal
        record["remove_summary"] = {
            k: v for k, v in summary.items() if k != "to"
        }
        # retired-replica counters stay in the fleet aggregate (the
        # scrape surface is monotonic), so migration counters are
        # pinnable straight off the embedded metrics
        record["metrics"] = fleet.metrics_json()
        slo_rep = _eval_slo(args, fleet.finished_requests())
        if slo_rep is not None:
            record["slo"] = slo_rep
        busiest = max(
            fleet.replicas,
            key=lambda r: len(r.engine.finished_requests()),
        )
        _embed_cost(record, busiest.engine)
        if not streams_equal:
            record["error"] = (
                "fleet.remove() changed a token stream — the "
                "redistribution must be value-exact"
            )
        elif any(r.finish_reason != "length" for r in results):
            record["error"] = (
                "a request was dropped or cut short across the remove: "
                f"{[r.finish_reason for r in results]}"
            )
        elif (
            summary["migrated_running"] + summary["migrated_queued"] < 1
        ):
            record["error"] = (
                "the victim held nothing by remove() time — the leg "
                "pinned no redistribution"
            )
        _maybe_slo_error(args, record)
        _dump_obs_fleet(
            record, fleet, "fleet_drain", slo_spec=_slo_spec(args)
        )
    except Exception as e:  # degraded-but-parseable, bench.py contract
        record["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(record))


def _child_fleet_disagg(args) -> None:
    """The disaggregated fleet leg: a prefill engine on a 2-device
    ('tp',) mesh and a single-chip decode engine behind the router.
    Every request prefills on the prefill role, hands its KV slab row to
    the decode role (explicit head-axis redistribution: tp=2 -> tp=1 is
    gather group g=2), and decodes there.  STRICT errors unless streams
    are bit-identical to a co-located engine, every request handed off
    exactly once, and the handoff wire bytes equal the
    ``parallel/reshard.py`` ring closed form — summary == comm audit ==
    counters."""
    n = int(args.fleet) if args.fleet else 2
    record, name, k_chunk, plat = _phase_setup(
        args, phase="fleet_disagg", fleet=2, disaggregate=True
    )

    import numpy as np

    from torchdistx_tpu.obs.comm import comm_audit
    from torchdistx_tpu.serve import ServeEngine, ServeFleet

    try:
        del n  # the leg is always 1 prefill + 1 decode
        model = _build_model(name, plat)
        limit = model.cfg.max_seq_len
        bucket = 16
        max_len = args.max_len or min(limit, 8 * args.max_new)
        max_new = min(args.max_new, max_len - bucket)
        n_req = max(2, min(args.requests, args.slots + 2))
        rs = np.random.RandomState(2)
        work = [
            dict(
                prompt=rs.randint(
                    0, 256, (int(rs.randint(5, bucket)),)
                ).astype(np.int32),
                max_new_tokens=max_new,
                temperature=0.0,
            )
            for _ in range(n_req)
        ]
        record["max_len"] = max_len

        def build(tp):
            return ServeEngine(
                model,
                num_slots=args.slots,
                max_len=max_len,
                decode_chunk=k_chunk,
                prefill_buckets=(bucket,),
                **_mesh_kwargs(args, tp=tp),
                **_kv_kwargs(args),
            )

        ref_tokens = [
            r.tokens for r in build(1).run([dict(w) for w in work])
        ]
        tp_pre, tp_dec = 2, 1
        pre, dec = build(tp_pre), build(tp_dec)
        fleet = ServeFleet(
            [pre, dec], disaggregate=True, roles=["prefill", "decode"]
        )
        with comm_audit() as prof:
            results = fleet.run(
                [dict(w) for w in work], max_new_tokens=max_new
            )
        streams_equal = all(
            np.array_equal(r.tokens, ref)
            for r, ref in zip(results, ref_tokens)
        )
        record["streams_identical"] = streams_equal
        record["comm"] = prof.to_json()
        # the ring closed form, computed independently of the engine —
        # per-array dtype widths over the full entry tuple, so a
        # quantized pool prices int8 data + f32 scales exactly
        g = max(1, tp_pre // int(np.gcd(tp_pre, tp_dec)))
        expect = (
            n_req
            * len(pre.cache.kv)
            * _kv_entry_wire_bytes(pre.cache.kv[0], g)
        )
        record["handoff_wire_bytes_expected"] = expect
        record["metrics"] = fleet.metrics_json()
        slo_rep = _eval_slo(args, fleet.finished_requests())
        if slo_rep is not None:
            record["slo"] = slo_rep
        c = record["metrics"]["counters"]
        _embed_cost(record, dec)
        if not streams_equal:
            record["error"] = (
                "disaggregated streams diverged from the co-located "
                "oracle — the handoff must be value-exact"
            )
        elif c.get("requests_handed_off") != n_req:
            record["error"] = (
                f"{c.get('requests_handed_off')} handoffs for {n_req} "
                "requests — every request must hand off exactly once"
            )
        elif c.get("handoff_wire_bytes") != expect:
            record["error"] = (
                f"handoff wire bytes {c.get('handoff_wire_bytes')} != "
                f"ring closed form {expect} (tp {tp_pre}->{tp_dec}, "
                f"g={g})"
            )
        elif int(prof.wire_bytes("all_gather", "tp")) != expect:
            record["error"] = (
                f"comm audit wire {int(prof.wire_bytes('all_gather', 'tp'))} "
                f"disagrees with the closed form {expect}"
            )
        _maybe_slo_error(args, record)
        _dump_obs_fleet(
            record, fleet, "fleet_disagg", slo_spec=_slo_spec(args)
        )
    except Exception as e:  # degraded-but-parseable, bench.py contract
        record["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(record))


def _child_autoscale(args) -> None:
    """The closed-loop autoscale A/B (ISSUE 16 tentpole): one
    deterministic open-loop scenario (serve/workload.py — every sample
    from the utils/rng.py counter stream, so same seed => bit-identical
    arrival stream) replayed tick-for-tick through every STATIC fleet
    size the policy allows and through a fleet driven by an
    ``AutoscaleController``.  Attainment and cost are measured in fleet
    TICKS (finish_tick - arrival_tick <= deadline_ticks; cost =
    replica-ticks), so the verdict is wall-clock-free and the counter
    rows pin exactly.  STRICT errors unless autoscaling strictly beats
    every static of equal-or-lower cost on attainment, no static
    dominates it, at least one scale-up AND one scale-down executed,
    and every stream (static and autoscaled) is bit-identical to the
    single-engine oracle — scaling decides capacity, never tokens."""
    sc_name = os.environ["TDX_SERVE_SCENARIO"]
    policy_arg = args.autoscale or "default"
    record, name, _k, plat = _phase_setup(
        args, phase=f"autoscale_{sc_name}", scenario=sc_name,
        autoscale=policy_arg,
    )

    import numpy as np

    from torchdistx_tpu import obs
    from torchdistx_tpu.serve import (
        AutoscaleController,
        ScalingPolicy,
        ServeEngine,
        ServeFleet,
        generate,
        scenario,
        workload_counters,
    )

    try:
        policy = ScalingPolicy.from_json(policy_arg)
        spec = scenario(sc_name)
        work = generate(spec)
        model = _build_model(name, plat)
        limit = model.cfg.max_seq_len
        # geometry pinned to the scenario's token envelope (NOT the
        # sweep's --decode-chunk/--slots): the catalog's arrival rates
        # are calibrated against this capacity, so the A/B's pressure
        # dynamics must not drift with unrelated CLI knobs
        bucket = -(-spec.max_prompt_len // 8) * 8
        max_len = bucket + spec.max_output_len
        if max_len > limit:
            raise RuntimeError(
                f"scenario {sc_name} needs max_len {max_len} > model "
                f"limit {limit}"
            )
        slots, k_chunk = 2, 4
        record.update(
            decode_chunk=k_chunk,
            num_slots=slots,
            requests=len(work),
            max_len=max_len,
            scenario_spec=spec.to_json(),
            policy=policy.to_json(),
        )

        def build(role="serve"):
            return ServeEngine(
                model,
                num_slots=slots,
                max_len=max_len,
                decode_chunk=k_chunk,
                prefill_buckets=(bucket,),
                **_mesh_kwargs(args),
                **_kv_kwargs(args),
            )

        watcher = obs.RecompileWatcher()
        # the bit-identity oracle compiles every program the replays can
        # reach (both donated-carry call signatures included): engines
        # share the model-level jit store, so the A/B fleets below —
        # and the controller's warmed mid-replay adds — dispatch
        # compile-free
        ref_tokens = [
            r.tokens for r in build().run([w.submit_kwargs() for w in work])
        ]
        record["recompile_warmup"] = watcher.snapshot()
        watcher.reset()  # the measured replays must compile NOTHING

        def replay(fleet, ctrl=None):
            """Open-loop tick replay: submissions between step N and
            N+1 carry arrival tick N (the fleet.tick contract), one
            controller evaluation per fleet tick."""
            handles, finish_tick, i, tick = {}, {}, 0, 0
            while i < len(work) or any(
                not h.done() for h in handles.values()
            ):
                while i < len(work) and work[i].arrival_tick <= tick:
                    handles[i] = fleet.submit(**work[i].submit_kwargs())
                    i += 1
                fleet.step()
                tick = fleet.tick
                if ctrl is not None:
                    ctrl.tick()
                for k, h in handles.items():
                    if k not in finish_tick and h.done():
                        finish_tick[k] = tick
            streams_ok = len(handles) == len(work) and all(
                np.array_equal(handles[k].result().tokens, ref_tokens[k])
                for k in range(len(work))
            )
            attained = sum(
                1
                for k, ft in finish_tick.items()
                if ft - work[k].arrival_tick <= work[k].deadline_ticks
            )
            return attained, tick, streams_ok

        statics = {}
        for n in range(policy.min_replicas, policy.max_replicas + 1):
            att, ticks, s_ok = replay(
                ServeFleet([build() for _ in range(n)])
            )
            statics[n] = {
                "attained": att,
                "replica_ticks": n * ticks,
                "ticks": ticks,
                "streams_identical": s_ok,
            }

        fleet_auto = ServeFleet(
            [build() for _ in range(policy.min_replicas)]
        )
        ctrl = AutoscaleController(
            fleet_auto, policy, engine_factory=build
        )
        att_auto, ticks_auto, auto_ok = replay(fleet_auto, ctrl)
        record["recompile_measure"] = watcher.snapshot()

        auto_cost = ctrl.counters["autoscale_replica_ticks"]
        ups = ctrl.counters["autoscale_scale_ups"]
        downs = ctrl.counters["autoscale_scale_downs"]
        streams_equal = auto_ok and all(
            s["streams_identical"] for s in statics.values()
        )
        comparable = {
            n: s
            for n, s in statics.items()
            if s["replica_ticks"] <= auto_cost
        }
        dominated = any(
            s["attained"] >= att_auto and s["replica_ticks"] <= auto_cost
            for s in statics.values()
        )
        verdict_ok = (
            streams_equal
            and bool(comparable)
            and all(
                att_auto > s["attained"] for s in comparable.values()
            )
            and not dominated
            and ups >= 1
            and downs >= 1
        )
        record["autoscale_verdict"] = {
            "ok": verdict_ok,
            "requests": len(work),
            "attained_autoscale": att_auto,
            "replica_ticks_autoscale": auto_cost,
            "ticks_autoscale": ticks_auto,
            "attained_static": {
                str(n): s["attained"] for n, s in statics.items()
            },
            "replica_ticks_static": {
                str(n): s["replica_ticks"] for n, s in statics.items()
            },
            "scale_ups": ups,
            "scale_downs": downs,
            "reroles": ctrl.counters["autoscale_reroles"],
            "streams_identical": streams_equal,
        }
        # every scale decision with its FULL signal vector — the
        # flight recorder and check_obs_artifacts --autoscale read the
        # same stream from the record
        record["scale_events"] = [
            data for ev, _ts, data in fleet_auto.events if ev == "scale"
        ]
        # the pinned counter rows: the autoscaled fleet's aggregate
        # stays pure in ``metrics`` (its exposition projection is
        # exact-gated), while the controller's decision counters, the
        # workload's exact shape, and both sides' tick-space A/B axes
        # ride in ``autoscale_metrics`` (ints only — the ledger ingests
        # both blocks and perf_gate --strict holds every row exactly)
        record["metrics"] = fleet_auto.metrics_json()
        ab = dict(workload_counters(work))
        ab.update(ctrl.counters)
        # NOT autoscale_-prefixed: that namespace is reserved for the
        # controller counters the tdx_autoscale_* exposition projects
        ab["attained_requests_auto"] = att_auto
        ab["total_ticks_auto"] = ticks_auto
        for n, s in statics.items():
            ab[f"static{n}_attained_requests"] = s["attained"]
            ab[f"static{n}_replica_ticks"] = s["replica_ticks"]
        record["autoscale_metrics"] = {
            "counters": ab,
            "gauges": ctrl.metrics_json()["gauges"],
        }
        busiest = max(
            fleet_auto.replicas,
            key=lambda r: len(r.engine.finished_requests()),
        )
        _embed_cost(record, busiest.engine)
        slo = _eval_slo(args, fleet_auto.finished_requests())
        if slo is not None:
            record["slo"] = slo
        if not streams_equal:
            record["error"] = (
                "a replayed stream diverged from the single-engine "
                "oracle — scaling must decide capacity, never tokens"
            )
        elif not verdict_ok:
            record["error"] = (
                f"autoscale A/B verdict failed on {sc_name}: "
                f"auto {att_auto}/{len(work)} @ {auto_cost} "
                "replica-ticks vs static "
                + ", ".join(
                    f"n={n}: {s['attained']}/{len(work)} @ "
                    f"{s['replica_ticks']}"
                    for n, s in statics.items()
                )
                + f" (scale_ups={ups}, scale_downs={downs})"
            )
        _maybe_slo_error(args, record)
        _dump_obs_fleet(
            record,
            fleet_auto,
            f"autoscale_{sc_name}",
            slo_spec=_slo_spec(args),
            collectors=[ctrl.collector()],
        )
        out_dir = os.environ.get("TDX_SERVE_TRACE_DIR")
        if out_dir:
            # the flight dump carries every scale decision (controller
            # records them as kind="scale") for postmortem replay
            from torchdistx_tpu.obs.flight import get_flight_recorder

            record["flight_path"] = get_flight_recorder().dump(
                os.path.join(
                    out_dir, f"autoscale_{sc_name}_flight.jsonl"
                ),
                reason=f"bench_serve autoscale_{sc_name}",
            )
    except Exception as e:  # degraded-but-parseable, bench.py contract
        record["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(record))


def main() -> None:
    args = _parse_args()
    if os.environ.get("TDX_SERVE_CHILD") == "1":
        phase = os.environ.get("TDX_SERVE_PHASE")
        if phase == "prefix_share":
            _child_prefix(args)
        elif phase == "chunked_prefill":
            _child_chunked_prefill(args)
        elif phase == "speculate":
            _child_spec(args)
        elif phase == "migrate":
            _child_migrate(args)
        elif phase == "kv_quant":
            _child_kv_quant(args)
        elif phase == "numerics":
            _child_numerics(args)
        elif phase == "fleet":
            _child_fleet(args)
        elif phase == "fleet_drain":
            _child_fleet_drain(args)
        elif phase == "fleet_disagg":
            _child_fleet_disagg(args)
        elif phase == "autoscale":
            _child_autoscale(args)
        else:
            _child(args)
    else:
        _supervise(args)


if __name__ == "__main__":
    main()
