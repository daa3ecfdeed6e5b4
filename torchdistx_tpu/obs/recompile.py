"""Recompile/dispatch watcher: count and attribute XLA compilations.

Every hard-to-diagnose perf artifact this repo has hit was a HIDDEN
compile: the round-2 "5.5% MFU" was a donated-carry jit recompiling on
its second call inside the timed window (CLAUDE.md), and LazyTensor
(PAPERS.md) names recompilation as the cost a staged stack must surface
to be debuggable.  This watcher makes compiles a first-class counter
instead of an inference from timings.

Mechanism: ``jax.monitoring`` emits a
``/jax/core/compile/backend_compile_duration`` duration event per
backend compile; one module-level listener fans it out to every active
watcher.  A program served from the persistent compilation cache emits
no such event, so a warm cache shows as fewer compiles, which is what
it is.  Attribution is a thread-local scope stack: compiles fired
while a :func:`recompile_scope` label is active are counted under that
label, everything else under ``"unattributed"``.
``utils.profiling.timed_annotation`` enters a scope named after its
region, so the serve engine's ``serve/prefill`` / ``serve/decode``
dispatches are attributed without any engine-side plumbing.

Expectation the tests pin (tests/test_obs.py): a donated-carry jit
compiles ONCE on backends where donation is a no-op (the CPU test mesh)
and recompiles exactly once on its second call on donation-capable
backends — ``warm_to_steady_state(..., watcher=...)`` turns that from a
timing inference into an asserted counter.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

from jax import monitoring

__all__ = [
    "RecompileWatcher",
    "recompile_scope",
    "current_scope",
    "track_jit_cache",
    "jit_cache_collector",
]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_tls = threading.local()
_lock = threading.Lock()
_watchers: List["RecompileWatcher"] = []
_listener_registered = False


def _scope_stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def current_scope() -> Optional[str]:
    st = _scope_stack()
    return st[-1] if st else None


class recompile_scope:
    """Attribute any XLA compile inside the body to ``label`` (innermost
    scope wins).  Safe to nest; near-free when no watcher is active (a
    class, not a generator: every ``serve/*`` span of a decode step
    enters one)."""

    __slots__ = ("_label", "_stack")

    def __init__(self, label: str):
        self._label = label

    def __enter__(self) -> None:
        self._stack = _scope_stack()
        self._stack.append(self._label)

    def __exit__(self, *exc) -> None:
        self._stack.pop()


def _on_event(key: str, dur: float, **_metadata) -> None:
    # jax.monitoring passes event metadata (fun_name=...) by keyword
    if key != COMPILE_EVENT:
        return
    label = current_scope() or "unattributed"
    with _lock:
        for w in _watchers:
            w._record(label, dur)


def _ensure_listener() -> None:
    """Register the module's single dispatcher once (jax.monitoring has
    no public unregister — per-watcher registration would leak
    listeners)."""
    global _listener_registered
    with _lock:
        if not _listener_registered:
            monitoring.register_event_duration_secs_listener(_on_event)
            _listener_registered = True


_tracked_jits: Dict[str, object] = {}


def track_jit_cache(name: str, fn: object) -> None:
    """Register a jitted callable so its compiled-executable count shows
    up as ``tdx_jit_cache_size{fn="<name>"}`` on the default registry's
    ``/metrics`` — compile-cache growth during a long serve/train becomes
    scrapeable instead of a post-mortem ``_cache_size`` probe.

    Held by weakref when the callable supports it (jit wrappers do), so
    tracking never pins a step program; a later registration under the
    same name replaces the earlier one (rebuilt steps).
    """
    import weakref

    try:
        ref = weakref.ref(fn)
    except TypeError:
        ref = lambda _fn=fn: _fn  # non-weakrefable: hold it
    with _lock:
        _tracked_jits[str(name)] = ref


def jit_cache_collector(prefix: str = "tdx_jit"):
    """An ``obs.metrics`` collector over every tracked jit cache
    (auto-registered on the default registry — obs.metrics)."""
    from .metrics import MetricFamily

    def collect():
        from ..utils.compat import jit_cache_size

        with _lock:
            tracked = dict(_tracked_jits)
        fam = MetricFamily(
            f"{prefix}_cache_size",
            "gauge",
            "compiled executables behind tracked jitted callables",
        )
        dead = []
        for name, ref in tracked.items():
            fn = ref()
            if fn is None:
                dead.append(name)
                continue
            size = jit_cache_size(fn)
            if size is not None:
                fam.add(size, fn=name)
        if dead:
            with _lock:
                for name in dead:
                    if _tracked_jits.get(name) is tracked[name]:
                        del _tracked_jits[name]
        return [fam] if fam.samples else []

    return collect


class RecompileWatcher:
    """Subscribe to backend-compile events; read ``counts``/``seconds``
    per attribution label.  ``install()`` is idempotent; ``uninstall()``
    stops this watcher without touching others."""

    def __init__(self, install: bool = True):
        self.counts: Dict[str, int] = {}
        self.seconds: Dict[str, float] = {}
        self.available = False
        if install:
            self.install()

    def install(self) -> "RecompileWatcher":
        _ensure_listener()
        self.available = True
        with _lock:
            if self not in _watchers:
                _watchers.append(self)
        return self

    def uninstall(self) -> None:
        with _lock:
            if self in _watchers:
                _watchers.remove(self)

    def __enter__(self) -> "RecompileWatcher":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()

    # called under the module lock
    def _record(self, label: str, dur: float) -> None:
        self.counts[label] = self.counts.get(label, 0) + 1
        self.seconds[label] = self.seconds.get(label, 0.0) + float(dur)

    scope = staticmethod(recompile_scope)

    @property
    def total(self) -> int:
        with _lock:
            return sum(self.counts.values())

    @property
    def total_seconds(self) -> float:
        with _lock:
            return sum(self.seconds.values())

    def reset(self) -> None:
        with _lock:
            self.counts.clear()
            self.seconds.clear()

    def snapshot(self) -> dict:
        """JSON-able record: total compiles + seconds, per-label split.
        ``available: False`` means the watcher was never installed and
        every count is structurally zero — consumers must treat that as
        "unknown", not "no compiles"."""
        with _lock:
            return {
                "available": self.available,
                "compiles_total": sum(self.counts.values()),
                "compile_seconds_total": round(
                    sum(self.seconds.values()), 4
                ),
                "by_scope": {
                    k: {
                        "compiles": self.counts[k],
                        "seconds": round(self.seconds[k], 4),
                    }
                    for k in sorted(self.counts)
                },
            }

    def collector(self, prefix: str = "tdx_jit"):
        """A :mod:`~torchdistx_tpu.obs.metrics` collector exposing
        ``<prefix>_compiles_total{fn=...}`` and
        ``<prefix>_compile_seconds_total{fn=...}``."""
        from .metrics import MetricFamily

        def collect():
            with _lock:
                counts = dict(self.counts)
                seconds = dict(self.seconds)
            c = MetricFamily(
                f"{prefix}_compiles_total",
                "counter",
                "XLA backend compiles, attributed by recompile_scope",
            )
            s = MetricFamily(
                f"{prefix}_compile_seconds_total",
                "counter",
                "Seconds spent in XLA backend compiles",
            )
            for k in sorted(counts):
                c.add(counts[k], fn=k)
                s.add(seconds[k], fn=k)
            if not counts:
                c.add(0.0)
                s.add(0.0)
            return [c, s]

        return collect
