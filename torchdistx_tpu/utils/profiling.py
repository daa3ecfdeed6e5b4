"""Observability: profiler traces and device memory stats.

The reference has no tracing/metrics at all (SURVEY §5.1, §5.5); on TPU the
canonical tools are XLA profiler traces (viewable in TensorBoard/XProf) and
PJRT device memory counters.  These helpers wrap them with zero deps.

:func:`timed_annotation` is the span primitive of
:mod:`~torchdistx_tpu.obs.trace` (profiler annotation + host tracer
event) with a metrics histogram (the ``sink``) and a
recompile-attribution scope (``obs.recompile``) added — so the serve
engine's ``serve/*`` phases mean the same thing in every view.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Iterator, Optional

import jax

from ..obs.recompile import recompile_scope
from ..obs.trace import get_tracer

__all__ = [
    "trace",
    "timed_annotation",
    "device_memory_stats",
    "format_memory_stats",
    "cost_summary",
]


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """Capture an XLA profiler trace into ``log_dir``."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class timed_annotation:
    """The span primitive (``obs.trace.Tracer.span``) plus wall-clock
    timing.  Entering yields a dict that gains ``{"seconds": ...}`` on
    exit; ``sink(seconds)`` is called if given (e.g. a
    ``serve.metrics.Histogram.record``).  The serving engine wraps every
    phase of a step with this, so a profiler trace and the metrics
    snapshot describe the same regions.

    The span enters the profiler annotation (once) and, with the tracer
    enabled, records the host event; ``stats`` ride along as the
    annotation's stats (``cycle=7``), never in its name, and are
    formatted only while a profile is being taken.  The region is also a
    recompile-attribution scope (``obs.recompile``): an XLA compile
    fired inside it is counted under ``name`` by any installed
    ``RecompileWatcher``.  A body that raises leaves no ``seconds`` and
    calls no sink.  (A class, not a generator: a decode step enters nine
    of these with the host's caches cold.)
    """

    __slots__ = ("_sink", "_span", "_scope", "_out", "_t0")

    def __init__(self, name: str, sink: Optional[Any] = None, **stats: Any):
        self._sink = sink
        self._span = get_tracer().span(name, cat="dispatch", **stats)
        self._scope = recompile_scope(name)

    def __enter__(self) -> dict:
        self._out = {}
        self._t0 = time.perf_counter()
        self._span.__enter__()
        self._scope.__enter__()
        return self._out

    def __exit__(self, exc_type, exc, tb) -> None:
        try:
            self._scope.__exit__(exc_type, exc, tb)
        finally:
            self._span.__exit__(exc_type, exc, tb)
        if exc_type is None:
            self._out["seconds"] = seconds = time.perf_counter() - self._t0
            if self._sink is not None:
                self._sink(seconds)


def cost_summary(fn: Any, *args: Any, peak_flops: Optional[float] = None, **kwargs: Any) -> dict:
    """XLA cost analysis of ``fn(*args)`` — compile-time FLOP and memory-
    traffic counts, the first stop when a measured MFU looks wrong.

    ``fn`` may be jitted or plain (it is jitted here).  Nothing executes:
    the function is lowered and compiled only.  Returns
    ``{"flops", "bytes_accessed", "arithmetic_intensity", "output_bytes",
    ...}`` plus, with ``peak_flops`` (e.g. 197e12 for v5e bf16), a
    ``compute_bound_s`` roofline floor; for the memory side divide
    ``bytes_accessed`` by your HBM bandwidth.

    Since the cost observatory landed this is a PROJECTION of a
    :class:`~torchdistx_tpu.obs.cost.CostCard` (the single
    implementation of the lower/compile/cost_analysis dance lives in
    ``obs.cost.compute_cost_card``); the record schema
    ``scripts/profile_train_step.py`` emits is unchanged.
    """
    from ..obs.cost import compute_cost_card

    card = compute_cost_card(fn, *args, name="cost_summary", **kwargs)
    flops = card.flops or 0.0
    byts = card.bytes_accessed or 0.0
    out = {
        "flops": flops,
        "bytes_accessed": byts,
        # the pre-refactor contract: 0.0 (not None) for a 0-FLOP
        # program with traffic; None only when bytes are zero
        "arithmetic_intensity": flops / byts if byts else None,
        "output_bytes": card.output_bytes_accessed or 0.0,
        "transcendentals": card.transcendentals or 0.0,
    }
    if peak_flops:
        out["compute_bound_s"] = flops / peak_flops
    return out


def device_memory_stats(device: Optional[Any] = None) -> dict:
    """Per-device memory counters (bytes_in_use, peak_bytes_in_use, ...).

    Returns ``{device_str: stats_dict}``; devices without PJRT memory stats
    (e.g. CPU) report an empty dict.
    """
    devices = [device] if device is not None else jax.devices()
    out = {}
    for d in devices:
        try:
            out[str(d)] = dict(d.memory_stats() or {})
        except Exception:
            out[str(d)] = {}
    return out


def format_memory_stats(stats: Optional[dict] = None) -> str:
    stats = stats if stats is not None else device_memory_stats()
    lines = []
    for dev, s in stats.items():
        if not s:
            lines.append(f"{dev}: (no memory stats)")
            continue
        in_use = s.get("bytes_in_use", 0) / 1e9
        peak = s.get("peak_bytes_in_use", 0) / 1e9
        limit = s.get("bytes_limit", 0) / 1e9
        lines.append(
            f"{dev}: {in_use:.2f} GB in use (peak {peak:.2f} GB, "
            f"limit {limit:.2f} GB)"
        )
    return "\n".join(lines)
