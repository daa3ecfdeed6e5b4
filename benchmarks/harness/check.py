"""The comparison that decides ``correct``: every number compared stands
beside its limit, and one number over its limit is enough."""

from __future__ import annotations

import math
import statistics


def small_gradient_leaves(ref_grad_norms: dict) -> set:
    """Leaves whose reference gradient is nought to rounding (under a
    thousandth of the median leaf's): Adam moves them by round-off alone,
    so their change is not compared."""
    med = statistics.median(ref_grad_norms.values())
    return {k for k, v in ref_grad_norms.items() if v < 1e-3 * med}


def worst_leaf_gap(program: dict, reference: dict, skip=()) -> tuple[float, str]:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    names = [k for k in reference if k not in skip]
    med = statistics.median(reference[k] for k in names)
    worst, where = 0.0, ""
    for k in names:
        gap = abs(program[k] - reference[k]) / max(reference[k], med)
        if not math.isfinite(gap):
            return math.inf, k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def rel_gap(program: float, reference: float) -> float:
    gap = abs(program - reference) / abs(reference)
    return gap if math.isfinite(gap) else math.inf


class Verdict:
    """Numbers beside their limits.  ``add`` a number with its limit;
    ``correct`` is whether each is finite and within it."""

    def __init__(self):
        self.rows = []  # (name, value, limit, note)
        self.read = []  # (name, value, note): read and shown, not compared

    def add(self, name: str, value: float, limit: float, note: str = ""):
        self.rows.append((name, float(value), float(limit), note))

    def show(self, name: str, value: float, note: str = ""):
        """A reading that decides nothing (``PERF.md`` says why)."""
        self.read.append((name, float(value), note))

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(
            math.isfinite(v) and v <= lim for _, v, lim, _ in self.rows)

    def as_dict(self) -> dict:
        return {n: {"value": v, "limit": lim} for n, v, lim, _ in self.rows}

    def lines(self) -> list[str]:
        out = [f"read (not compared) {n} = {v:.6g}" + (f" ({note})" if note else "")
               for n, v, note in self.read]
        for n, v, lim, note in self.rows:
            ok = "ok" if math.isfinite(v) and v <= lim else "OVER"
            out.append(f"compared {n} = {v:.6g} limit {lim:.6g} {ok}"
                       + (f" ({note})" if note else ""))
        return out
