"""Private or shape-drifting jax introspection, read in one place.

Three surfaces of the installed jax (0.9.0) that are not public API or
whose return shape is awkward, each with exactly one reader here:
the private jit ``_cache_size`` (:func:`jit_cache_size`) and the
compiled-executable pair behind ``obs.cost``
(:func:`compiled_cost_analysis` / :func:`compiled_memory_analysis`,
normalized to plain dicts with the peak's SOURCE always named).
Everything else — ``jax.shard_map``, ``lax.axis_size``,
``jax.experimental.io_callback``, ``jax.monitoring`` — is called
directly at its use site.
"""

from __future__ import annotations

__all__ = [
    "jit_cache_size",
    "compiled_cost_analysis",
    "compiled_memory_analysis",
]


def jit_cache_size(fn):
    """Compiled-executable count behind a jitted callable, or None for a
    callable that is not a jit wrapper.

    ``_cache_size`` is a private jax API; every consumer
    (``ServeEngine.num_compiled_programs``,
    ``utils.benchmarks.warm_to_steady_state``, the jit-cache metrics
    collector) reads it through here.  None means "unknown", never
    "zero" — callers must fall back to another steadiness signal, not
    assume no compiles."""
    cache_size = getattr(fn, "_cache_size", None)
    if cache_size is None:
        return None
    return int(cache_size())


def compiled_cost_analysis(compiled):
    """XLA cost analysis of a ``Compiled`` executable as one plain dict
    (``{"flops": ..., "bytes accessed": ...}``), or None where the
    backend offers none."""
    ca = compiled.cost_analysis()
    return dict(ca) if isinstance(ca, dict) else None


#: CompiledMemoryStats attribute -> normalized dict key.  Only the
#: device-side sizes; host_* duplicates are deliberately dropped.
_MEMORY_FIELDS = (
    ("argument_size_in_bytes", "arg_bytes"),
    ("output_size_in_bytes", "out_bytes"),
    ("temp_size_in_bytes", "temp_bytes"),
    ("alias_size_in_bytes", "alias_bytes"),
    ("generated_code_size_in_bytes", "generated_code_bytes"),
)


def compiled_memory_analysis(compiled):
    """Buffer-assignment sizes of a ``Compiled`` executable as a plain
    dict (``arg_bytes``/``out_bytes``/``temp_bytes``/``alias_bytes``/
    ``generated_code_bytes`` + ``peak_bytes`` with its source NAMED), or
    None where the backend reports none.

    ``peak_source`` says where ``peak_bytes`` came from: ``"xla_peak"``
    (the backend filled ``peak_memory_in_bytes``) or ``"arg+out+temp"``
    (it left the peak at zero, as the CPU backend does — the sum is the
    executable's worst-case live footprint with no overlap credit, an
    upper bound).  Callers that fall further back (e.g. to
    ``obs.memory.hbm_watermark``) must keep naming the source — a peak
    whose provenance is unknown is how HBM-overcommit postmortems go
    wrong."""
    ma = compiled.memory_analysis()
    if ma is None:
        return None
    out = {}
    for attr, key in _MEMORY_FIELDS:
        v = getattr(ma, attr, None)
        if isinstance(v, int):
            out[key] = v
    if not out:
        return None
    peak = getattr(ma, "peak_memory_in_bytes", None)
    if isinstance(peak, int) and peak > 0:
        out["peak_bytes"] = peak
        out["peak_source"] = "xla_peak"
    else:
        out["peak_bytes"] = (
            out.get("arg_bytes", 0)
            + out.get("out_bytes", 0)
            + out.get("temp_bytes", 0)
        )
        out["peak_source"] = "arg+out+temp"
    return out
