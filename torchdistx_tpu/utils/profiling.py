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


@contextlib.contextmanager
def timed_annotation(name: str, sink: Optional[Any] = None) -> Iterator[dict]:
    """The span primitive (``obs.trace.Tracer.span``) plus wall-clock
    timing.  Yields a dict that gains ``{"seconds": ...}`` on exit;
    ``sink(seconds)`` is called if given (e.g. a
    ``serve.metrics.Histogram.record``).  The serving engine wraps every
    phase of a step with this, so a profiler trace and the metrics
    snapshot describe the same regions.

    The span enters the profiler annotation (once) and, with the tracer
    enabled, records the host event.  The region is also a
    recompile-attribution scope (``obs.recompile``): an XLA compile
    fired inside it is counted under ``name`` by any installed
    ``RecompileWatcher``.
    """
    out: dict = {}
    t0 = time.perf_counter()
    with get_tracer().span(name, cat="dispatch"), recompile_scope(name):
        yield out
    out["seconds"] = time.perf_counter() - t0
    if sink is not None:
        sink(out["seconds"])


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
