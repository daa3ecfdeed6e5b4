"""Unified tracing & telemetry (net-new vs the reference — SURVEY §5.1
documents that torchdistx ships no tracing or metrics at all).

Three zero-dependency layers, instrumented end-to-end through the serve
engine, trainer, and deferred-init replay (docs/observability.md):

- :mod:`~torchdistx_tpu.obs.trace` — host-side span tracer with
  Chrome-trace (Perfetto) JSON export and a JSONL structured-event
  sink; per-request serving lifecycle tracks via
  :func:`request_trace_events`.
- :mod:`~torchdistx_tpu.obs.metrics` — metrics registry (counters /
  gauges / summaries with labels) with Prometheus text exposition, a
  stdlib round-trip parser, and an optional ``http.server``
  ``/metrics`` endpoint.
- :mod:`~torchdistx_tpu.obs.recompile` — ``jax.monitoring``-backed
  recompile watcher counting and attributing XLA compiles per scope
  (the donated-carry double compile from CLAUDE.md becomes a named
  counter instead of a timing artifact).

PR 5 adds the *training-side* layer on the same substrate
(docs/observability.md "Training telemetry"):

- :mod:`~torchdistx_tpu.obs.comm` — trace-time collective-traffic audit
  with analytic per-axis byte accounting (arXiv:2112.01075), assertable
  in tests.
- :mod:`~torchdistx_tpu.obs.memory` — post-materialization sharding &
  HBM audit (accidental replication, unsharded optimizer state, device
  watermark).
- :mod:`~torchdistx_tpu.obs.flight` — bounded flight-recorder ring with
  per-event-flush streaming and atomic crash dumps (the NCCL flight
  recorder analog).

PR 7 adds the *perf sentinel* — the layer that reads the evidence back
(docs/observability.md "Perf sentinel"):

- :mod:`~torchdistx_tpu.obs.ledger` — schema-versioned
  (``tdx-ledger-v1``) append-only JSONL benchmark ledger with ingest
  adapters for every artifact family; counter rows are deterministic,
  timing rows are noisy, degraded runs are recorded but never baseline.
- :mod:`~torchdistx_tpu.obs.gate` — expectations-driven regression
  gate: exact compare for counters, direction-aware tolerance bands
  for timings (``scripts/perf_gate.py`` is the CI entry point;
  ``scripts/perf_report.py`` renders trends and A/B deltas).

PR 8 adds the *device cost observatory* — what the compiler actually
built (docs/observability.md "Cost observatory & capacity planner"):

- :mod:`~torchdistx_tpu.obs.cost` — per-program **CostCards** (XLA
  cost/memory analysis behind ``utils.compat`` shims) with roofline/
  MFU attribution, exported to Prometheus + Perfetto + ledger counter
  rows.
- :func:`~torchdistx_tpu.obs.memory.capacity_plan` — the live HBM
  budget report (weights + optimizer + KV + per-program temps) the
  serve engine consults as a second admission gate.
- :mod:`~torchdistx_tpu.obs.watchdog` — dispatch-stall deadline timer
  that dumps the flight recorder naming the in-flight program and its
  cost card (the hung-dispatch black box).

PR 14 adds the *fleet SLO observatory* (docs/observability.md "Fleet
tracing & SLO observatory"):

- :mod:`~torchdistx_tpu.obs.slo` — declarative TTFT/TPOT/e2e/deadline
  SLO specs evaluated over the engines' per-request histories into
  ``tdx-slo-v1`` reports: deterministic attainment counters, goodput
  under SLO, multi-window burn-rate alert states, a Prometheus
  projection (:func:`slo_collector`), and ``slo_burn`` flight events.
- cross-replica request tracing: :func:`fleet_request_spans` /
  :func:`fleet_request_trace_events` tile each request's life into
  route/queued/prefill/handoff/decode spans on the shared monotonic
  timebase and stitch them with Perfetto flow events keyed on the
  process-unique ``Request.trace_id`` (``ServeFleet.dump_trace``).

PR 19 adds the *numerics observatory* — the first layer over values
rather than resources (docs/observability.md "Numerics observatory"):

- :mod:`~torchdistx_tpu.obs.numerics` — ``tdx-numerics-v1`` digests
  (exact nonfinite/zero counts + base-2 exponent histograms, plus
  per-platform max-abs/rms) fused into the existing jitted train /
  serve / replay programs and harvested only at their existing sync
  boundaries; nonfinite provenance names the earliest bad site in
  flight events; exported as ``tdx_numerics_*`` gauges, Perfetto
  counter tracks, and exact ledger counter rows.

PR 20 adds the *incident time machine* — the layer that re-executes
(docs/observability.md "Incident time machine"):

- :mod:`~torchdistx_tpu.obs.blackbox` — streaming ``tdx-session-v1``
  session black box: every boundary crossing into a serve session
  (geometry, submits with token ids + sampling params, fleet ticks,
  autoscale signal vectors, env stamp) with per-event flush, plus a
  rolling SHA-256 digest chain folded at every drain boundary over the
  deterministic integer counters + emitted tokens (zero extra host
  syncs; periodic full-counter snapshots as bisection waypoints).
  :func:`replay_session` rebuilds the engine/fleet from the recording,
  re-drives the exact stream, and on mismatch bisects to the first
  divergent drain (seq + tick), the differing counters, and the
  affected request ids.  ``ServeEngine(record=...)`` /
  ``ServeFleet(record=...)`` / ``Trainer(record=...)`` wire it in;
  ``TDX_SESSION_RECORD=0`` is the kill switch;
  ``scripts/replay_session.py`` is the CLI.
"""

from .blackbox import (
    SESSION_SCHEMA,
    SessionRecorder,
    geometry_kwargs,
    load_session,
    rechain,
    recording_enabled,
    replay_session,
    resolve_record,
    session_force_disabled,
    signals_from_session,
    validate_session_jsonl,
)
from .comm import CommProfile, comm_audit, record_collective
from .cost import (
    CostBook,
    CostCard,
    compute_cost_card,
    validate_cost_card,
)
from .flight import FlightRecorder, get_flight_recorder
from .gate import (
    build_expectations,
    gate_rows,
    render_gate_markdown,
    timing_direction,
)
from .ledger import (
    append_record_rows,
    append_rows,
    ingest_artifact,
    make_row,
    read_ledger,
    record_stamp,
    validate_ledger_file,
    validate_ledger_row,
)
from .memory import (
    capacity_plan,
    device_hbm_budget,
    hbm_watermark,
    memory_report,
    sharding_report,
)
from .metrics import (
    Counter,
    Gauge,
    MetricFamily,
    MetricsRegistry,
    Summary,
    default_registry,
    parse_prometheus,
    render_prometheus,
    start_metrics_server,
)
from .numerics import (
    NUMERICS_SCHEMA,
    HostDigest,
    NumericsBook,
    array_digest,
    numerics_enabled,
    numerics_tape,
    tap,
    tap_error,
    tree_digest,
)
from .recompile import RecompileWatcher, recompile_scope, track_jit_cache
from .slo import (
    SLO_SCHEMA,
    SloSpec,
    evaluate_slo,
    slo_collector,
    validate_slo_report,
)
from .watchdog import DispatchWatchdog
from .trace import (
    Tracer,
    disable_tracing,
    enable_tracing,
    fleet_request_spans,
    fleet_request_trace_events,
    get_tracer,
    request_trace_events,
)

__all__ = [
    "append_record_rows",
    "append_rows",
    "build_expectations",
    "gate_rows",
    "ingest_artifact",
    "make_row",
    "read_ledger",
    "record_stamp",
    "render_gate_markdown",
    "timing_direction",
    "validate_ledger_file",
    "validate_ledger_row",
    "Tracer",
    "get_tracer",
    "enable_tracing",
    "disable_tracing",
    "request_trace_events",
    "fleet_request_spans",
    "fleet_request_trace_events",
    "SLO_SCHEMA",
    "SloSpec",
    "evaluate_slo",
    "slo_collector",
    "validate_slo_report",
    "MetricFamily",
    "Counter",
    "Gauge",
    "Summary",
    "MetricsRegistry",
    "default_registry",
    "render_prometheus",
    "parse_prometheus",
    "start_metrics_server",
    "RecompileWatcher",
    "recompile_scope",
    "track_jit_cache",
    "CommProfile",
    "comm_audit",
    "record_collective",
    "FlightRecorder",
    "get_flight_recorder",
    "sharding_report",
    "hbm_watermark",
    "memory_report",
    "capacity_plan",
    "device_hbm_budget",
    "CostBook",
    "CostCard",
    "compute_cost_card",
    "validate_cost_card",
    "DispatchWatchdog",
    "NUMERICS_SCHEMA",
    "HostDigest",
    "NumericsBook",
    "array_digest",
    "numerics_enabled",
    "numerics_tape",
    "tap",
    "tap_error",
    "tree_digest",
    "SESSION_SCHEMA",
    "SessionRecorder",
    "geometry_kwargs",
    "load_session",
    "rechain",
    "recording_enabled",
    "replay_session",
    "resolve_record",
    "session_force_disabled",
    "signals_from_session",
    "validate_session_jsonl",
]
