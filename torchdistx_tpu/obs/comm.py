"""Collective-traffic audit: trace-time op counts and analytic bytes per
mesh axis for every Python-level collective choke point.

"Memory-efficient array redistribution through portable collective
communication" (arXiv:2112.01075) observes that collective traffic is
*analytically accountable*: for ring algorithms the wire bytes of each
primitive are a closed-form function of payload size and axis size.  This
module turns that observation into an assertable profile — the training
analog of veScale's per-rank introspection (arXiv:2509.07003): instead of
*hoping* FSDP reduce-scatters exactly the parameter bytes once per step,
tests pin it (tests/test_comm_audit.py).

Accounting model — important to read before trusting the numbers:

- Collectives run INSIDE jit, so recording happens at **trace time**: the
  Python bodies of ``parallel.collectives`` (and the instrumented call
  sites in ``parallel/fsdp.py`` / ``parallel/pp.py``) execute once per
  compiled program, while a :func:`comm_audit` profile is active on the
  tracing thread.  A cached program's later calls record nothing — the
  profile describes *one execution of the traced program* and is cached
  alongside it by the caller (``Trainer`` keeps one per step program).
- ``lax.scan`` bodies trace once regardless of length, so loop-executed
  collectives must record their static trip counts explicitly — the
  pipeline schedule does (``pipeline_train_step`` records ``2*ticks``
  exchanges, the closed form of the 1F1B schedule).
- Scope: Python-level collectives only.  Jaxpr-level transposes (the
  backward of a plain ``lax.psum``) and GSPMD-inserted collectives
  (``GSPMDTrainStep``) are invisible here — use
  ``utils.profiling.cost_summary`` for compiler-side traffic.  The
  custom-VJP pairs (``allreduce_linear`` / ``copy_psum_grad``) DO record
  their backward psum, because their bwd rules are Python that runs under
  the vjp trace.
- ``lax.switch`` branches all trace, so e.g. a multi-topology GossipGraD
  schedule records every branch's exchange — a conservative upper bound.
  Pinned tests use single-branch schedules where the count is exact.

Per-device wire bytes (ring algorithms over an axis of size ``n``,
arXiv:2112.01075 §2; ``payload`` is the full logical operand):

=================  =====================  ==========================
kind               payload definition     wire bytes per device
=================  =====================  ==========================
all_reduce/-mean   operand bytes S        2 * (n-1)/n * S
reduce_scatter     input bytes S          (n-1)/n * S
all_gather         gathered bytes S       (n-1)/n * S
broadcast          operand bytes S        (n-1)/n * S  (pipelined 1-to-all)
exchange/shift     operand bytes S        S * len(perm)/n  (senders only)
ppermute           operand bytes S        S  (full-rotation ring hop)
all_to_all         operand bytes S        (n-1)/n * S  (keeps own slice)
=================  =====================  ==========================

``broadcast`` is lowered here as mask+psum (collectives.broadcast); the
analytic figure above is the *recognized* broadcast cost — if XLA fails
to pattern-match it you pay psum cost instead, which is exactly the kind
of drift the audit exists to surface when compared against
``cost_summary``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from typing import Any, Dict, Iterator, Optional, Tuple

__all__ = [
    "CommProfile",
    "comm_audit",
    "current_comm_profile",
    "record_collective",
    "tree_bytes",
    "validate_comm_profile",
]

_KINDS = (
    "all_reduce",
    "all_mean",
    "broadcast",
    "exchange",
    "shift",
    "all_gather",
    "reduce_scatter",
    "allreduce_linear",
    "allreduce_linear_bwd",
    "copy_psum_grad_bwd",
    "pmean",
    "ppermute",
    "all_to_all",
)


def tree_bytes(tree: Any) -> int:
    """Total bytes of a pytree of arrays/tracers (shape x itemsize; works
    on traced abstract values, which is where the audit runs)."""
    import numpy as np
    from jax import tree_util

    total = 0
    for leaf in tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        total += int(math.prod(shape)) * np.dtype(dtype).itemsize
    return total


@dataclasses.dataclass
class _Entry:
    ops: int = 0
    payload_bytes: int = 0
    wire_bytes: float = 0.0


class CommProfile:
    """Accumulated per-(kind, axis) collective traffic for one traced
    program execution.  Thread-safe to read; writes happen on the tracing
    thread under :func:`comm_audit`."""

    def __init__(self) -> None:
        self._entries: Dict[Tuple[str, str], _Entry] = {}
        self._lock = threading.Lock()

    # -- recording (tracing thread) --------------------------------------

    def _record(
        self, kind: str, axis: str, count: int, payload: int, wire: float
    ) -> None:
        key = (kind, str(axis))
        with self._lock:
            e = self._entries.setdefault(key, _Entry())
            e.ops += count
            e.payload_bytes += payload * count
            e.wire_bytes += wire * count

    # -- queries ----------------------------------------------------------

    def _select(self, kind: Optional[str], axis: Optional[str]):
        with self._lock:
            return [
                e
                for (k, a), e in self._entries.items()
                if (kind is None or k == kind) and (axis is None or a == axis)
            ]

    def ops(self, kind: Optional[str] = None, axis: Optional[str] = None) -> int:
        return sum(e.ops for e in self._select(kind, axis))

    def payload_bytes(
        self, kind: Optional[str] = None, axis: Optional[str] = None
    ) -> int:
        return sum(e.payload_bytes for e in self._select(kind, axis))

    def wire_bytes(
        self, kind: Optional[str] = None, axis: Optional[str] = None
    ) -> float:
        return sum(e.wire_bytes for e in self._select(kind, axis))

    def bytes_by_axis(self) -> Dict[str, int]:
        """Wire bytes per mesh axis — the per-leg comparison number the
        multichip telemetry lines print."""
        out: Dict[str, float] = {}
        with self._lock:
            for (_, a), e in self._entries.items():
                out[a] = out.get(a, 0.0) + e.wire_bytes
        return {a: int(round(v)) for a, v in sorted(out.items())}

    def to_json(self) -> dict:
        """Schema-stable record (validated by
        :func:`validate_comm_profile` / scripts/check_obs_artifacts.py):
        ``{"schema": "tdx-comm-v1", "entries": [{kind, axis, ops,
        payload_bytes, wire_bytes}], "bytes_by_axis": {...}}``."""
        with self._lock:
            entries = [
                {
                    "kind": k,
                    "axis": a,
                    "ops": e.ops,
                    "payload_bytes": e.payload_bytes,
                    "wire_bytes": int(round(e.wire_bytes)),
                }
                for (k, a), e in sorted(self._entries.items())
            ]
        return {
            "schema": "tdx-comm-v1",
            "entries": entries,
            "bytes_by_axis": self.bytes_by_axis(),
        }

    def digest(self) -> dict:
        """Compact one-line form for flight records: total ops + wire
        bytes per axis."""
        return {"ops": self.ops(), "bytes_by_axis": self.bytes_by_axis()}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def __bool__(self) -> bool:
        with self._lock:
            return bool(self._entries)


_tls = threading.local()


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def current_comm_profile() -> Optional[CommProfile]:
    st = _stack()
    return st[-1] if st else None


@contextlib.contextmanager
def comm_audit(profile: Optional[CommProfile] = None) -> Iterator[CommProfile]:
    """Activate ``profile`` (or a fresh one) for Python-level collective
    recording on this thread.  Wrap the call that TRACES the program —
    typically the first invocation of a jitted step::

        with comm_audit() as prof:
            params, opt_state, loss = step(params, opt_state, batch)
        assert prof.payload_bytes("reduce_scatter", "fsdp") == param_bytes

    Nested audits ALL record: a dryrun leg's audit sees the collectives
    even when the Trainer inside it wraps the step in its own per-step
    audit.
    """
    prof = profile if profile is not None else CommProfile()
    st = _stack()
    st.append(prof)
    try:
        yield prof
    finally:
        st.pop()


# wire-byte ratio per executed op, as a function of axis size n (and the
# sender count s for permutes); see the module-docstring table
_WIRE = {
    "all_reduce": lambda n, s: 2.0 * (n - 1) / n,
    "all_mean": lambda n, s: 2.0 * (n - 1) / n,
    "allreduce_linear": lambda n, s: 2.0 * (n - 1) / n,
    "allreduce_linear_bwd": lambda n, s: 0.0,  # identity backward
    "copy_psum_grad_bwd": lambda n, s: 2.0 * (n - 1) / n,
    "pmean": lambda n, s: 2.0 * (n - 1) / n,
    "broadcast": lambda n, s: (n - 1) / n,
    "all_gather": lambda n, s: (n - 1) / n,
    "reduce_scatter": lambda n, s: (n - 1) / n,
    "exchange": lambda n, s: (s if s is not None else n) / n,
    "shift": lambda n, s: 1.0,  # every device sends in a ring shift
    "ppermute": lambda n, s: 1.0,  # full rotation: every device sends
    "all_to_all": lambda n, s: (n - 1) / n,  # own slice stays local
}


def record_collective(
    kind: str,
    axis: Any,
    tree: Any = None,
    *,
    payload_bytes: Optional[int] = None,
    count: int = 1,
    axis_size: Optional[int] = None,
    senders: Optional[int] = None,
) -> None:
    """Record ``count`` executions of a collective into the active profile
    (no-op, one thread-local read, when no audit is active).

    ``payload_bytes`` overrides the ``tree`` measurement; ``axis_size``
    must be passed when the caller is outside a mapped-axis trace (the
    instrumented call sites all know it statically or via
    ``lax.axis_size``); ``senders`` is the permutation length for
    exchange-style ops.
    """
    profs = _stack()
    if not profs:
        return
    payload = (
        payload_bytes if payload_bytes is not None else tree_bytes(tree)
    )
    n = axis_size
    if n is None:
        try:
            from jax import lax

            n = int(lax.axis_size(axis))
        except Exception:
            n = None
    if n is None or n <= 0:
        wire = float(payload)  # unknown axis: degrade to payload
    else:
        ratio = _WIRE.get(kind)
        wire = payload * ratio(n, senders) if ratio else float(payload)
    for prof in profs:
        prof._record(str(kind), str(axis), int(count), int(payload), wire)


def validate_comm_profile(doc: Any) -> list:
    """Schema check for :meth:`CommProfile.to_json` output.  Returns a
    list of error strings (empty = valid) — shared by
    scripts/check_obs_artifacts.py and the tests."""
    errors: list = []
    if not isinstance(doc, dict):
        return [f"comm profile is {type(doc).__name__}, not dict"]
    if doc.get("schema") != "tdx-comm-v1":
        errors.append(f"bad comm-profile schema tag {doc.get('schema')!r}")
    entries = doc.get("entries")
    if not isinstance(entries, list):
        return errors + ["comm profile has no entries list"]
    for i, e in enumerate(entries):
        if not isinstance(e, dict):
            errors.append(f"entry {i} is not an object")
            continue
        for field, typ in (
            ("kind", str),
            ("axis", str),
            ("ops", int),
            ("payload_bytes", int),
            ("wire_bytes", int),
        ):
            if not isinstance(e.get(field), typ):
                errors.append(
                    f"entry {i}: {field} is "
                    f"{type(e.get(field)).__name__}, want {typ.__name__}"
                )
        if isinstance(e.get("ops"), int) and e["ops"] < 0:
            errors.append(f"entry {i}: negative ops")
    bba = doc.get("bytes_by_axis")
    if not isinstance(bba, dict) or not all(
        isinstance(v, int) for v in bba.values()
    ):
        errors.append("bytes_by_axis must map axis -> int")
    return errors
