"""Sharding & HBM audit: is the memory plan what you think it is?

Two of this repo's past incident classes were silent placement bugs: an
optimizer state that landed fully replicated because jit does not
propagate input shardings into ``zeros_like`` outputs (CLAUDE.md /
``parallel.fsdp.optimizer_state_shardings``), and HBM overcommit.  Both are *statically checkable*
after materialization — this module is that check, machine-readable so
``bench.py`` and ``dryrun_multichip`` can carry it as evidence.

- :func:`sharding_report` — walk a materialized module (or params dict),
  report per-entry global/per-device bytes and the actual
  ``PartitionSpec``, compare against an intended sharding rule when
  given, and FLAG: large parameters left fully replicated on a >1-device
  mesh (``accidental_replication``) and optimizer-state slots whose
  parameter is sharded but whose state is not
  (``unsharded_optimizer_state`` — the missing
  ``optimizer_state_shardings`` signature).
- :func:`hbm_watermark` — per-device ``memory_stats()`` peak via
  ``utils.profiling.device_memory_stats``, degrading to the host
  ``ru_maxrss`` watermark on backends without PJRT memory stats (the
  CPU test mesh) — the source is always named, never guessed.
- :func:`capacity_plan` — the LIVE half (ISSUE 8): roll named
  components (weights, optimizer state, KV pool, per-program temp/peak
  from the cost observatory's cards) into a per-device budget report
  with headroom.  ``ServeEngine`` consults it as a second admission
  gate, and ``sharding_report(budget_bytes_per_device=...)`` extends
  the audit to per-shard budgets — ROADMAP item 1's
  "admission/scheduling aware of per-shard HBM budgets" prerequisite.
"""

from __future__ import annotations

import math
import resource
from typing import Any, Callable, Dict, Optional

import numpy as np

__all__ = [
    "sharding_report",
    "hbm_watermark",
    "memory_report",
    "capacity_plan",
    "device_hbm_budget",
    "tree_device_bytes",
    "last_materialize_report",
]


def tree_device_bytes(tree: Any) -> int:
    """Per-device bytes of a params pytree: the largest addressable
    shard of each array leaf, summed — the weights component of a
    :func:`capacity_plan` (``ServeEngine.memory_plan`` uses this; the
    same accounting :func:`sharding_report` applies per entry)."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            total += _device_bytes(leaf, _entry_bytes(leaf))
    return total


def _spec_str(arr: Any) -> str:
    sharding = getattr(arr, "sharding", None)
    spec = getattr(sharding, "spec", None)
    return str(spec) if spec is not None else str(sharding)


def _entry_bytes(arr: Any) -> int:
    return int(math.prod(arr.shape)) * np.dtype(arr.dtype).itemsize


def _device_bytes(arr: Any, global_bytes: int) -> int:
    """Per-device bytes of one array (largest addressable shard)."""
    try:
        shards = arr.addressable_shards
        return max(
            int(math.prod(s.data.shape)) * np.dtype(arr.dtype).itemsize
            for s in shards
        )
    except Exception:
        return global_bytes


def _named_entries(target: Any):
    """(path, array) pairs from a Module, a dict, or any params pytree."""
    import jax

    if hasattr(target, "named_parameters"):
        yield from target.named_parameters()
        if hasattr(target, "named_buffers"):
            yield from target.named_buffers()
        return
    if isinstance(target, dict) and all(
        not isinstance(v, (dict, list, tuple)) for v in target.values()
    ):
        # the repo's flat {"blocks.0.attn.wq.weight": arr} convention:
        # keep the plain keys so intended_rule sees the same paths
        # materialize_module's sharding rules do
        yield from target.items()
        return
    for path, leaf in jax.tree_util.tree_flatten_with_path(target)[0]:
        yield jax.tree_util.keystr(path), leaf


def sharding_report(
    target: Any,
    *,
    intended_rule: Optional[Callable[[str, Any], Any]] = None,
    optimizer_state: Any = None,
    min_shard_elems: int = 1024,
    budget_bytes_per_device: Optional[int] = None,
) -> dict:
    """Post-materialization sharding audit.

    ``target`` is a materialized Module or a params pytree.
    ``intended_rule(path, array)`` (same signature as a
    ``materialize_module`` sharding rule) marks entries whose actual
    sharding differs from the plan.  ``optimizer_state`` is checked for
    param-shaped slots that are replicated while their parameter is
    sharded.  Returns a JSON-able report; ``report["flags"]`` is the
    actionable list (empty = the memory plan holds).

    ``budget_bytes_per_device`` extends the audit to PER-SHARD HBM
    budgets (ROADMAP item 1): the report gains a ``shard_budget``
    section — per-device bytes (params + buffers + optimizer state)
    against the budget, with headroom — and an ``over_budget`` flag
    when the per-device footprint exceeds it.  The dryrun TP leg
    asserts this section flag-free before any TP-serve work trusts the
    plan.
    """
    import jax

    n_devices = len(jax.devices())
    entries = []
    flags = []
    total_bytes = 0
    device_bytes = 0
    by_sharded_path: Dict[str, Any] = {}

    for path, arr in _named_entries(target):
        if not isinstance(arr, jax.Array):
            entries.append(
                {"path": path, "status": "unmaterialized",
                 "type": type(arr).__name__}
            )
            continue
        g = _entry_bytes(arr)
        d = _device_bytes(arr, g)
        total_bytes += g
        device_bytes += d
        sharding = arr.sharding
        replicated = bool(
            getattr(sharding, "is_fully_replicated", d >= g)
        )
        n_arr_devices = len(getattr(sharding, "device_set", [None]))
        entry = {
            "path": path,
            "shape": list(arr.shape),
            "dtype": str(arr.dtype),
            "bytes": g,
            "bytes_per_device": d,
            "sharding": _spec_str(arr),
            "replicated": replicated,
        }
        if not replicated:
            by_sharded_path[path] = arr
        planned = False  # replication the intended rule explicitly asked for
        if intended_rule is not None:
            try:
                want = intended_rule(path, arr)
            except Exception as e:  # a partial rule must not kill the audit
                want = None
                entry["intended_error"] = str(e)[:120]
            if want is not None:
                if sharding.is_equivalent_to(want, arr.ndim):
                    planned = True
                else:
                    # the mismatch flag subsumes accidental_replication:
                    # one actionable finding per entry
                    planned = True
                    entry["flag"] = "sharding_mismatch"
                    entry["intended"] = str(getattr(want, "spec", want))
                    flags.append(
                        {
                            "kind": "sharding_mismatch",
                            "path": path,
                            "actual": _spec_str(arr),
                            "intended": entry["intended"],
                        }
                    )
        if (
            replicated
            and not planned
            and n_arr_devices > 1
            and arr.size >= min_shard_elems
        ):
            entry["flag"] = "accidental_replication"
            flags.append(
                {
                    "kind": "accidental_replication",
                    "path": path,
                    "bytes": g,
                    "detail": f"{arr.size} elems fully replicated over "
                    f"{n_arr_devices} devices",
                }
            )
        entries.append(entry)

    opt_entries = 0
    opt_bytes = 0
    opt_device_bytes = 0
    if optimizer_state is not None:
        shape_by_path = {
            p: tuple(a.shape) for p, a in by_sharded_path.items()
        }
        for path, leaf in _named_entries(optimizer_state):
            if not isinstance(leaf, jax.Array):
                continue
            opt_entries += 1
            leaf_bytes = _entry_bytes(leaf)
            opt_bytes += leaf_bytes
            opt_device_bytes += _device_bytes(leaf, leaf_bytes)
            # match the slot to its parameter by path suffix + shape: optax
            # state paths look like "[0].mu['fc1.weight']" around the
            # param's own key
            owner = next(
                (
                    p
                    for p, shp in shape_by_path.items()
                    if p in path and tuple(leaf.shape) == shp
                ),
                None,
            )
            if owner is None:
                continue
            leaf_repl = bool(
                getattr(leaf.sharding, "is_fully_replicated", True)
            )
            if leaf_repl and leaf.size >= min_shard_elems:
                flags.append(
                    {
                        "kind": "unsharded_optimizer_state",
                        "path": path,
                        "param": owner,
                        "bytes": _entry_bytes(leaf),
                        "detail": "param is sharded but this state slot is "
                        "fully replicated — derive the slot shardings from "
                        "the plan (ShardingPlan.optimizer_state_shardings, "
                        "parallel/plan.py) and pass them as out_shardings",
                    }
                )

    report = {
        "schema": "tdx-sharding-v1",
        "n_devices": n_devices,
        "n_entries": len(entries),
        "n_optimizer_entries": opt_entries,
        "total_bytes": total_bytes,
        "bytes_per_device": device_bytes,
        "optimizer_bytes": opt_bytes,
        "optimizer_bytes_per_device": opt_device_bytes,
        "replication_factor": round(
            device_bytes * n_devices / total_bytes, 3
        )
        if total_bytes
        else None,
        "entries": entries,
        "flags": flags,
    }
    if budget_bytes_per_device is not None:
        # the per-shard budget: everything this report accounted that
        # must co-reside on one device (params/buffers + optimizer
        # state, largest shard each)
        shard_total = device_bytes + opt_device_bytes
        budget = int(budget_bytes_per_device)
        report["shard_budget"] = {
            "budget_bytes": budget,
            "bytes_per_device": shard_total,
            "headroom_bytes": budget - shard_total,
            "utilization": round(shard_total / budget, 4) if budget else None,
        }
        if shard_total > budget:
            flags.append(
                {
                    "kind": "over_budget",
                    "path": None,
                    "bytes": shard_total,
                    "detail": f"per-device footprint {shard_total} exceeds "
                    f"the per-shard HBM budget {budget}",
                }
            )
    return report


def hbm_watermark() -> dict:
    """Device memory watermark: ``{"source": "pjrt", "devices": {dev:
    {bytes_in_use, peak_bytes_in_use, bytes_limit}}, "peak_bytes": max}``
    or, when no device reports PJRT stats (CPU meshes), the host fallback
    ``{"source": "host_rusage", "peak_bytes": ru_maxrss}``."""
    from ..utils.profiling import device_memory_stats

    stats = device_memory_stats()
    devices = {
        d: {
            k: s[k]
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
            if k in s
        }
        for d, s in stats.items()
        if s
    }
    if devices:
        return {
            "source": "pjrt",
            "devices": devices,
            "peak_bytes": max(
                s.get("peak_bytes_in_use", s.get("bytes_in_use", 0))
                for s in devices.values()
            ),
        }
    # the existing profiling fallback: no PJRT stats on this backend —
    # report the host high-water mark and SAY that is what it is
    ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return {
        "source": "host_rusage",
        # ru_maxrss is KiB on Linux
        "peak_bytes": int(ru) * 1024,
    }


def memory_report(
    target: Any = None,
    *,
    intended_rule: Optional[Callable[[str, Any], Any]] = None,
    optimizer_state: Any = None,
    include_entries: bool = False,
) -> dict:
    """The machine-checkable memory plan bench.py embeds: sharding audit
    summary (entry list elided unless ``include_entries``) + watermark."""
    out: dict = {"watermark": hbm_watermark()}
    if target is not None:
        rep = sharding_report(
            target,
            intended_rule=intended_rule,
            optimizer_state=optimizer_state,
        )
        if not include_entries:
            rep = {k: v for k, v in rep.items() if k != "entries"}
        out["sharding"] = rep
    return out


def device_hbm_budget() -> Optional[int]:
    """This device's real HBM capacity (PJRT ``bytes_limit``, min over
    devices), or None where the backend reports none (the CPU mesh) —
    the honest default budget for :func:`capacity_plan` consumers that
    were not given an explicit one."""
    from ..utils.profiling import device_memory_stats

    limits = [
        s["bytes_limit"]
        for s in device_memory_stats().values()
        if isinstance(s.get("bytes_limit"), int) and s["bytes_limit"] > 0
    ]
    return min(limits) if limits else None


def capacity_plan(
    components: dict,
    *,
    budget_bytes: Optional[int] = None,
) -> dict:
    """The live HBM capacity planner (``tdx-capacity-v1``): roll named
    per-device byte components — weights, optimizer state, KV pool,
    per-program temp/peak from the cost observatory's cards — into one
    budget report.  ``projected_peak_bytes`` is the sum (the components
    must co-reside: the KV slab and the weights are both live while a
    dispatch's temps peak).  With a budget (explicit, or falling back
    to :func:`device_hbm_budget`) the report carries headroom and a
    ``fits`` verdict — what ``ServeEngine``'s admission gate refuses
    on.  Budget-less hosts (the CPU mesh with no explicit budget)
    report ``fits: None``: unknown, never "yes"."""
    comps = {
        k: int(v)
        for k, v in (components or {}).items()
        if isinstance(v, (int, float)) and not isinstance(v, bool)
    }
    total = sum(comps.values())
    if budget_bytes is None:
        budget_bytes = device_hbm_budget()
        budget_source = "pjrt_bytes_limit" if budget_bytes else None
    else:
        budget_bytes = int(budget_bytes)
        budget_source = "explicit"
    out: dict = {
        "schema": "tdx-capacity-v1",
        "components": comps,
        "projected_peak_bytes": total,
        "budget_bytes": budget_bytes,
        "budget_source": budget_source,
        "headroom_bytes": (
            None if budget_bytes is None else budget_bytes - total
        ),
        "fits": None if budget_bytes is None else total <= budget_bytes,
    }
    return out


_LAST_MATERIALIZE: Optional[dict] = None


def record_materialize(n_tensors: int, total_bytes: int) -> dict:
    """Called by ``materialize_module`` after each replay: stamps the
    watermark and totals so callers (bench.py's 7B phase, the flight
    recorder) can pick up the most recent materialization's footprint
    without re-walking the module."""
    global _LAST_MATERIALIZE
    _LAST_MATERIALIZE = {
        "n_tensors": n_tensors,
        "total_bytes": total_bytes,
        "watermark": hbm_watermark(),
    }
    from .trace import get_tracer

    get_tracer().counter(
        "materialize_bytes", total=float(total_bytes)
    )
    return _LAST_MATERIALIZE


def last_materialize_report() -> Optional[dict]:
    return _LAST_MATERIALIZE
